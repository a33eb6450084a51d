"""The rest of a run with the chip's look skipped and the timed path broken
underneath: `correct` has to come out false.  The provider is the program's
SoftwareProvider (no kernel is compiled here), planted in a Run that the test
builds itself and hands to run.one_run, at --rehearse-on-cpu's 64-tx blocks."""

import json
import time

import pytest

from benchmarks import run as bench_run


@pytest.fixture(autouse=True)
def host_sized_traffic(monkeypatch):
    """The software path at 64-tx blocks commits far more blocks a second
    than the chip's cells are sized for: build enough of them."""
    from benchmarks import harness as hs

    real = hs.Run.__init__

    def sized(self, *args, **kwargs):
        real(self, *args, **kwargs)
        self.traffic.update(
            chain_blocks_per_second=300, requests_built_per_second=300,
            workers=1,  # no fork from a test process that has threads
        )

    monkeypatch.setattr(hs.Run, "__init__", sized)


def software():
    from fabric_tpu.crypto.bccsp import SoftwareProvider

    return SoftwareProvider()


class Altered:
    """A provider whose verdicts are altered where they are produced."""

    def __init__(self, alter):
        self._sw = software()
        self._alter = alter

    def __getattr__(self, name):
        return getattr(self._sw, name)

    def batch_verify(self, keys, sigs, digests):
        return self._alter(self._sw.batch_verify(keys, sigs, digests))

    def batch_verify_async(self, keys, sigs, digests):
        return lambda: self.batch_verify(keys, sigs, digests)


def all_true(mask):
    return [True] * len(mask)


def second_half_left_out(mask):
    half = len(mask) // 2
    return list(mask[:half]) + [True] * (len(mask) - half)


def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    line = json.loads(out[-1])
    line["_controls"] = [
        row for row in map(json.loads, out[:-1]) if row.get("phase") == "control"
    ]
    return line


def run_cell(workload, capsys, provider_factory=software, extra=()):
    from benchmarks import harness as hs

    args = bench_run.parse_args(
        ["--workload", workload, "--seed", "2147483659", "--seconds", "1",
         "--trace", "0", "--rehearse-on-cpu", *extra]
    )
    loaded = bench_run.load_cell(workload)
    r = hs.Run(
        workload, loaded["config"], loaded["traffic"], args.seed,
        args.seconds, False, True, time.perf_counter(),
        controls=bench_run.controls_of(args),
        provider_factory=provider_factory, serve_engine="host",
    )
    return bench_run.one_run(r, args, loaded), last_line(capsys)


def test_a_sound_commit_run_is_correct_and_prints_no_metric(capsys):
    rc, line = run_cell("peer-catchup", capsys)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"] == {} and line["rehearsal"]
    assert list(line)[-2:] == ["checks", "_controls"]  # checks comes last
    assert line["checks"]["filter_mismatch_bytes"] == {"value": 0, "limit": 0}
    assert line["attempted"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("alter", [all_true, second_half_left_out])
def test_an_altered_verdict_fails_the_commit_cell(capsys, alter):
    rc, line = run_cell("peer-catchup", capsys, lambda: Altered(alter))
    assert rc != 0 and line["correct"] is False
    assert line["checks"]["filter_mismatch_bytes"]["value"] > 0
    assert line["checks"]["state_mismatch_keys"]["value"] > 0


def test_a_block_whose_state_is_left_unchanged_fails_the_commit_cell(
    capsys, monkeypatch
):
    from fabric_tpu.peer.channel import Channel

    real = Channel.store_block

    def store_block(self, block, prepared=None):
        if block.header.number == 3:  # says it committed, and did not
            return None
        return real(self, block, prepared=prepared)

    monkeypatch.setattr(Channel, "store_block", store_block)
    rc, line = run_cell("peer-catchup", capsys)
    assert rc != 0 and line["correct"] is False
    assert line["checks"]["pipeline"]["value"] == 1
    assert line["checks"]["height_gap"]["value"] > 0


@pytest.mark.parametrize("rule", ["accept_high_s", "skip_policy", "skip_mvcc"])
def test_each_control_fails_the_commit_cell(capsys, rule):
    rc, line = run_cell("peer-catchup", capsys, extra=("--control", rule))
    assert rc == 0 and line["correct"] is True  # the program's own line
    (control,) = line["_controls"]
    assert control["rule"] == rule and control["correct"] is False
    assert control["checks"]["filter_mismatch_bytes"]["value"] > 0
    assert control["checks"]["state_mismatch_keys"]["value"] > 0


def test_a_sound_sidecar_run_is_correct(capsys):
    rc, line = run_cell("sidecar-1peer", capsys)
    assert rc == 0 and line["correct"] is True and line["metrics"] == {}
    assert line["checks"]["mask_mismatch_lanes"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("alter", [all_true, second_half_left_out])
def test_an_altered_mask_fails_the_sidecar_cell(capsys, monkeypatch, alter):
    from fabric_tpu.serve import server

    monkeypatch.setattr(server, "build_provider", lambda engine="auto": (Altered(alter), "host"))
    rc, line = run_cell("sidecar-1peer", capsys)
    assert rc != 0 and line["correct"] is False
    assert line["checks"]["mask_mismatch_lanes"]["value"] > 0


def test_the_control_fails_the_sidecar_cell(capsys):
    rc, line = run_cell("sidecar-1peer", capsys, extra=("--control", "accept_high_s"))
    assert rc == 0 and line["correct"] is True  # the program's own line
    (control,) = line["_controls"]
    assert control["correct"] is False
    assert control["checks"]["mask_mismatch_lanes"]["value"] > 0


def test_an_exhausted_chain_fails_the_run(capsys, monkeypatch):
    from benchmarks import harness as hs

    sized = hs.Run.__init__

    def short_chain(self, *args, **kwargs):
        sized(self, *args, **kwargs)
        self.traffic.update(chain_blocks_per_second=0.5)

    monkeypatch.setattr(hs.Run, "__init__", short_chain)
    rc, line = run_cell("peer-catchup", capsys)
    assert rc != 0 and line["correct"] is False
    assert line["checks"]["chain_exhausted"]["value"] == 1


def test_no_tpu_means_no_result(capsys):
    rc = bench_run.main(
        ["--workload", "peer-catchup", "--seed", "1", "--seconds", "1", "--trace", "0"]
    )
    captured = capsys.readouterr()
    assert rc != 0
    assert '"correct"' not in captured.out
    assert "no TPU" in captured.err
