"""The cell `multichannel-4ch` with the chips' look skipped: the driver
`multichannel` over a planted stand-in for the sharded program (the host's
own ECDSA over the limb stacks the program would be given, so everything of
`MultiChannelValidator.validate` but the kernel runs and no XLA program is
compiled), at small blocks, handed to run.one_run.  A sound run is `correct`;
each control and each planted fault is not."""

import json
import time

import numpy as np
import pytest

from benchmarks import run as bench_run

WORKLOAD = "multichannel-4ch"


@pytest.fixture(autouse=True)
def host_sized_cell(monkeypatch):
    """24-tx blocks (71 lanes a channel, the 128-lane bucket) with one poison
    of each kind, a backlog the host path does not exhaust in half a second,
    and no fork from a test process that has threads."""
    from benchmarks import harness as hs

    real = hs.Run.__init__

    def sized(self, *args, **kwargs):
        real(self, *args, **kwargs)
        self.config.update(block_txs=24, poisons_per_kind=1)
        self.traffic.update(steps_built_per_second=40, warmup_steps=1, workers=1)

    monkeypatch.setattr(hs.Run, "__init__", sized)


class Device:
    def __init__(self, id):
        self.id = id


class DeviceArray:
    """What the jitted call returns, as far as the validator reads it."""

    def __init__(self, mask, device_ids):
        self._mask = mask
        self.sharding = type("Sharding", (), {})()
        self.sharding.device_set = {Device(i) for i in device_ids}

    def __array__(self, dtype=None, copy=None):
        return self._mask


def host_ecdsa(e, r, s, qx, qy):
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec, utils

    try:
        key = ec.EllipticCurvePublicNumbers(qx, qy, ec.SECP256R1()).public_key()
        key.verify(
            utils.encode_dss_signature(r, s), e.to_bytes(32, "big"),
            ec.ECDSA(utils.Prehashed(hashes.SHA256())),
        )
    except (ValueError, InvalidSignature):
        return False
    return True


class HostSharded:
    """ShardedVerify's surface over four chips that are not there.  `alter`
    is applied to the (4, lanes) mask where the devices would produce it."""

    channel_size, data_size = 4, 1

    def __init__(self, alter=None, device_ids=(0, 1, 2, 3), raise_at=None):
        self._alter = alter
        self._device_ids = device_ids
        self._raise_at = raise_at
        self.launches = 0

    def channels_program(self):
        return self

    def lower(self, *stacked):
        return None

    def dispatch_channels(self, e, r, s, qx, qy, ok):
        import fabric_tpu.ops.bignum as bn

        self.launches += 1
        if self.launches == self._raise_at:
            raise RuntimeError("the device went away")
        mask = np.zeros(ok.shape, dtype=bool)
        for c, lane in zip(*np.nonzero(ok)):
            mask[c, lane] = host_ecdsa(*(
                bn.limbs_to_int(a[c, :, lane]) for a in (e, r, s, qx, qy)
            ))
        if self._alter is not None:
            mask = self._alter(mask, ok)
        return DeviceArray(mask, self._device_ids)


def two_channels_swapped(mask, ok):
    return mask[[1, 0, 2, 3]]


def one_channel_left_out(mask, ok):
    """Channel 2's lanes come back as the host's prechecks left them: never
    verified."""
    out = mask.copy()
    out[2] = ok[2]
    return out


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    rows = [json.loads(row) for row in out[:-1]]
    line["_controls"] = [row for row in rows if row.get("phase") == "control"]
    line["_rehearsal"] = [
        row for row in rows
        if row.get("phase") == "rehearsal_numbers_not_measurements"
    ]
    return line


def a_run(seed="2147483659", sharded=None, extra=(), trace=False):
    from benchmarks import harness as hs

    args = bench_run.parse_args(
        ["--workload", WORKLOAD, "--seed", seed, "--seconds", "0.5",
         "--trace", "1" if trace else "0", "--rehearse-on-cpu", *extra]
    )
    loaded = bench_run.load_cell(WORKLOAD)
    r = hs.Run(
        WORKLOAD, loaded["config"], loaded["traffic"], args.seed,
        args.seconds, trace, True, time.perf_counter(),
        chips=int(loaded["cell"]["chips"]),
        controls=bench_run.controls_of(args),
        provider_factory=sharded or HostSharded,
    )
    return r, args, loaded


def run_cell(capsys, sharded=None, extra=(), trace=False):
    rc = bench_run.one_run(*a_run(sharded=sharded, extra=extra, trace=trace))
    return rc, last_line(capsys)


ZERO = {"value": 0, "limit": 0}


def test_a_sound_run_is_correct_with_every_check_at_zero(capsys):
    rc, line = run_cell(capsys)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"] == {} and line["rehearsal"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["checks"]) == {
        "bucket", "chain_exhausted", "steps_unanswered", "device_lanes",
        "chips_running", "compiles_in_window", "threads_left",
        "filter_mismatch_bytes", "reference_vs_plan_bytes", "no_poison_found",
        "end_to_end_metrics_missing",
    }
    assert all(row == ZERO for row in line["checks"].values())


@pytest.mark.parametrize("rule", ["accept_high_s", "skip_policy"])
def test_each_control_fails_the_cell(capsys, rule):
    rc, line = run_cell(capsys, extra=("--control", rule))
    assert rc == 0 and line["correct"] is True  # the program's own line
    (control,) = line["_controls"]
    assert control["rule"] == rule and control["correct"] is False
    assert control["checks"]["filter_mismatch_bytes"]["value"] > 0


@pytest.mark.parametrize("alter", [two_channels_swapped, one_channel_left_out])
def test_an_altered_mask_fails_the_cell(capsys, alter):
    rc, line = run_cell(capsys, lambda: HostSharded(alter))
    assert rc != 0 and line["correct"] is False
    assert line["checks"]["filter_mismatch_bytes"]["value"] > 0
    assert line["checks"]["steps_unanswered"] == ZERO


def test_output_on_fewer_than_four_devices_fails_the_cell(capsys):
    rc, line = run_cell(capsys, lambda: HostSharded(device_ids=(0, 1, 2)))
    assert rc != 0 and line["correct"] is False
    assert line["checks"]["chips_running"]["value"] == 1
    assert line["checks"]["filter_mismatch_bytes"] == ZERO


def test_a_step_that_raises_is_a_step_unanswered(capsys):
    rc, line = run_cell(capsys, lambda: HostSharded(raise_at=3))
    assert rc != 0 and line["correct"] is False
    assert line["checks"]["steps_unanswered"]["value"] == 1
    assert line["failed"] == 1


def test_an_exhausted_chain_fails_the_run(capsys, monkeypatch):
    from benchmarks import harness as hs

    sized = hs.Run.__init__

    def short_chain(self, *args, **kwargs):
        sized(self, *args, **kwargs)
        self.traffic.update(steps_built_per_second=2)

    monkeypatch.setattr(hs.Run, "__init__", short_chain)
    rc, line = run_cell(capsys)
    assert rc != 0 and line["correct"] is False
    assert line["checks"]["chain_exhausted"]["value"] == 1


def test_a_program_without_the_spans_fails_at_once(capsys, monkeypatch):
    """The parent of PR 34: the driver says so before it builds a backlog."""
    from fabric_tpu.parallel.sharded import ShardedVerify

    monkeypatch.delattr(ShardedVerify, "dispatch_channels")
    t0 = time.perf_counter()
    rc = bench_run.one_run(*a_run(seed="1"))
    captured = capsys.readouterr()
    assert rc == 2 and '"correct"' not in captured.out
    assert "cannot be checked" in captured.err
    assert time.perf_counter() - t0 < 5.0


def test_the_traced_run_reads_the_five_span_metrics(capsys):
    """On the CPU there is no device plane, so the device metrics stay
    silent; the span metrics read the ring of the undisturbed half."""
    rc, line = run_cell(capsys, trace=True)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"] == {}  # a rehearsal's line carries none
    (numbers,) = line["_rehearsal"]
    read = {name for name in numbers if name != "phase"}
    assert read >= {
        "mc_prepare_ms_per_step", "mc_stack_ms_per_step",
        "mc_dispatch_ms_per_step", "mc_resolve_ms_per_step",
        "mc_epilogue_ms_per_step",
    }
    assert not read & {
        "verify_kernel_ms.mc", "verify_roofline.mc", "device_idle_pct.mc",
        "launch_skew_ms.mc",
    }
    assert all(numbers[name] > 0 for name in read)
