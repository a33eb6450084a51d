"""Driver ``multichannel``: one peer joined to several channels, one channel
per chip.  Every step hands one block of each channel to
``MultiChannelValidator.validate`` on ``grid_mesh(channels, 1)`` together, as
BASELINE #5 lays it out; one closed-loop submitter offers the next step when
the filters of the last are back, so every launch has one shape.

Timed path: ``MultiChannelValidator.validate({channel: block})`` (per channel
parse -> collect_sig_jobs -> prep_limbs on the host, one stack, ONE sharded
launch over the mesh's channel axis, the mask copied back, per channel
finish_sig_results -> the policy stage).  The submitter unmarshals each block
first, as a deliver client does.  The path ends at the TRANSACTIONS_FILTER of
the signature and policy checks: no MVCC, no ledger write (the
configuration's ``guarantees`` say so).

Output check: once the window has closed, every step's filters are compared,
byte for byte, with the plain reference's for the same serialized blocks,
each channel under its own membership.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

from benchmarks import generator as gen
from benchmarks import harness as hs
from benchmarks import reference as ref
from benchmarks import reference_filter as flt
from benchmarks import window_series as ws

# what a device-idle gap is labelled with (trace_reduce.longest_idle_gaps):
# the program's own spans, which lie inside `bench.step`, and the harness's
# unmarshal, which the step holds too
ANNOTATIONS = (
    "mc.prepare", "mc.stack", "mc.dispatch", "mc.resolve", "mc.epilogue",
    "bench.unmarshal",
)
WINDOW_SPAN = "bench.window"
# the program's spans kept per step in the run's series (window_series.py):
# one submitter in a closed loop, so the k-th span of a name is the k-th step's
SERIES_SPANS = ("mc.validate", "mc.stack", "mc.dispatch", "mc.resolve")


def run(r: hs.Run) -> Dict:
    cfg, traffic = r.config, r.traffic
    channels = list(cfg["channel_ids"])
    if len(channels) != int(cfg["channels"]) or len(channels) != r.chips:
        raise hs.SeamGaveWay(
            f"{len(channels)} channel ids for {cfg['channels']} channels on "
            f"{r.chips} chip(s): the deployment is one channel per chip"
        )
    warmup = int(traffic["warmup_steps"])
    n_steps = hs.backlog_length(
        warmup, traffic["steps_built_per_second"], r.seconds
    )
    r.mark("imports_done")
    world = gen.build_world(cfg)
    r.mark("world_built")
    roots = gen.msp_roots(world)
    policy = {"n": int(cfg["policy_n"]), "mspids": list(cfg["policy_mspids"])}
    memberships: Dict[int, ref.Membership] = {}  # each worker's own copies

    def build_block(task) -> Dict:
        """Block `number` of channel `index`: the channel's name and index go
        into the generator's seed, so the four plans of a step differ."""
        index, number = task
        return gen.build_envelopes(
            world, dict(cfg, channel=channels[index]), number,
            r.seed + (index << 32),
        )

    def check_block(task) -> bytes:
        index, raw, rule = task
        if index not in memberships:
            memberships[index] = ref.Membership(roots)
        return flt.sigpolicy_filter(ref.check_signatures_and_policy(
            ref.block_envelopes(raw)[1], memberships[index], policy, rule
        ))

    workers = gen.ForkedWorkers(
        {"build": build_block, "check": check_block},
        gen.worker_count(traffic),
    )
    try:
        return _run(r, world, workers, channels, n_steps, warmup)
    finally:
        workers.close()


def _require_the_program() -> None:
    """On a checkout whose MultiChannelValidator has no spans, no counter and
    no `last_device_ids` (the parent of PR 34), the cell cannot be run: say so
    at once instead of measuring a window whose checks must fail."""
    from fabric_tpu.parallel.multichannel import MultiChannelValidator
    from fabric_tpu.parallel.sharded import ShardedVerify

    if not (hasattr(MultiChannelValidator, "last_device_ids")
            and hasattr(ShardedVerify, "dispatch_channels")):
        raise hs.SeamGaveWay(
            "this checkout's MultiChannelValidator counts no device lanes "
            "and does not say which devices ran (no `last_device_ids`, no "
            "ShardedVerify.dispatch_channels): the cell cannot be checked on it"
        )


def _run(r: hs.Run, world, workers, channels: List[str], n_steps: int,
         warmup: int) -> Dict:
    _require_the_program()  # before the workers are given the backlog to build
    cfg = r.config
    n_ch = len(channels)
    # chains[c][k]: block k of channel c, sealed onto the channel's own chain
    chains: List[List[Dict]] = [[] for _ in channels]
    prev = [b""] * n_ch

    def tasks(steps) -> List:
        return [(c, k) for k in steps for c in range(n_ch)]

    def take(built, steps) -> None:
        for (c, k), entry in zip(tasks(steps), built):
            prev[c] = gen.seal_block(entry, k, prev[c])
            chains[c].append(entry)

    # the warm-up's steps first; the workers then build the rest, and keep
    # it, while this process traces, lowers and loads the program
    take(workers.run("build", tasks(range(warmup))), range(warmup))
    rest = workers.start("build", tasks(range(warmup, n_steps)))

    r.start_backend()
    r.mark("backend_up")
    from fabric_tpu.crypto.bccsp import SoftwareProvider
    from fabric_tpu.crypto.tpu_provider import _bucket
    from fabric_tpu.parallel.mesh import grid_mesh
    from fabric_tpu.parallel.multichannel import MultiChannelValidator
    from fabric_tpu.protos import common_pb2
    from fabric_tpu.validation.validator import BlockValidator

    before = set(threading.enumerate())
    sw = SoftwareProvider()  # identities and the policy stage; no lane goes here
    validators = {
        ch: BlockValidator(ch, gen.msp_manager(world, sw), sw, world["registry"])
        for ch in channels
    }
    planted = r.provider_factory() if r.provider_factory is not None else None
    mesh = None if planted is not None else grid_mesh(n_ch, 1, r.devices)
    mc = MultiChannelValidator(mesh, validators)
    if planted is not None:  # the tests' stand-in for the sharded program
        mc.sharded = planted
    r.mark("validator")

    def step_lanes(step: int) -> int:
        return sum(chains[c][step]["lanes"] for c in range(n_ch))

    def bucket_of(step: int) -> None:
        for c in range(n_ch):
            lanes = chains[c][step]["lanes"]
            hs.check_bucket(lanes, _bucket(lanes), r.want_bucket)

    checks = hs.Checks()
    filters: Dict[int, List[bytes]] = {}
    devices_seen: Dict[int, int] = {}
    started_at: Dict[int, float] = {}
    done_at: Dict[int, float] = {}
    unmarshal_ms: Dict[int, float] = {}
    step_raised = None

    def one_step(step: int, timed: bool) -> None:
        """Unmarshal the step's four blocks as a deliver client would, hand
        them to validate() together, keep the four filters it left."""
        t_unmarshal = time.perf_counter()
        with hs.annotate("bench.unmarshal", r.trace and timed):
            blocks = {
                ch: gen.parse_block_bytes(chains[c][step]["raw"])
                for c, ch in enumerate(channels)
            }
        t_called = time.perf_counter()
        mc.validate(blocks)
        t_back = time.perf_counter()
        if timed:
            unmarshal_ms[step] = (t_called - t_unmarshal) * 1e3
            started_at[step], done_at[step] = t_called, t_back
        filters[step] = [
            bytes(blocks[ch].metadata.metadata[common_pb2.TRANSACTIONS_FILTER])
            for ch in channels
        ]
        devices_seen[step] = len(mc.last_device_ids)

    # warm-up: the first step traces, lowers and compiles (or loads) the one
    # program shape, outside the window
    for step in range(warmup):
        bucket_of(step)
        one_step(step, timed=False)
    warm_log = r.compiles.since_mark()
    r.mark("warm")
    take(workers.collect(rest), range(warmup, n_steps))
    checks.seam(
        "bucket", lambda: [bucket_of(k) for k in range(warmup, n_steps)]
    )
    hs.GcLog.settle()
    r.mark("chains_built")
    hs.say(
        phase="setup", seconds_since_start=r.marks, workload=r.workload,
        steps_built=n_steps, channels=channels,
        block_txs=int(cfg["block_txs"]), lanes_per_step=step_lanes(0),
        bucket=_bucket(chains[0][0]["lanes"]),
        mesh=None if mesh is None else {k: int(v) for k, v in mesh.shape.items()},
        output_device_ids=sorted(mc.last_device_ids), warmup=warm_log,
        compile_cache_dir=hs.compile_cache_dir(),
        native_library=hs.native_library(),
    )

    nxt = warmup
    exhausted = False
    with hs.UndisturbedSpan(WINDOW_SPAN, r.tracer), hs.GcLog() as gc_log:
        t0 = time.perf_counter()
        setup_s = t0 - r.t_process_start
        t_end = t0 + r.seconds
        while time.perf_counter() < t_end:
            r.tracer.tick(time.perf_counter(), t0, r.seconds)
            if nxt >= n_steps:
                exhausted = True
                break
            try:
                with hs.annotate("bench.step", r.trace):
                    one_step(nxt, timed=True)
            except Exception as exc:  # noqa: BLE001 - a step that raises is a step unanswered
                step_raised = repr(exc)
                nxt += 1
                break
            nxt += 1
        remaining = t_end - time.perf_counter()
        if remaining > 0 and step_raised is None:  # only when the steps ran out
            time.sleep(remaining)
    r.tracer.stop()
    window_log = r.compiles.since_mark()

    peak = hs.memory_peak_bytes(r.devices)
    sent = nxt
    answered = sorted(filters)  # a step that raised left none
    checks.add("chain_exhausted", int(exhausted))
    checks.add("steps_unanswered", sent - len(answered))
    # the validator's own counter: the real lanes of every channel of every
    # step answered (the warm-up's too), counted once the mask was back
    checks.seam(
        "device_lanes",
        lambda: hs.check_device_lanes(
            r.obs.snapshot(), sum(step_lanes(k) for k in answered)
        ),
    )
    checks.seam(
        "chips_running", lambda: _every_chip_ran(devices_seen, answered, n_ch)
    )
    checks.seam("compiles_in_window", lambda: hs.check_no_compiles(window_log))
    checks.seam("threads_left", lambda: _threads_gone(before))

    _compare(r, workers, chains, channels, sent, filters, checks)

    walls = [(done_at[k] - started_at[k]) * 1e3 for k in done_at]
    hs.say(
        phase="window", workload=r.workload, seconds=r.seconds,
        steps=len(walls), samples=len(walls),
        steps_per_s=sum(1 for at in done_at.values() if at <= t_end) / r.seconds,
        verdict_ms_percentiles={
            str(q): round(hs.percentile(walls, q), 2) for q in (50, 75, 90, 95, 99)
        } if walls else None,
        unmarshal_ms_per_step=(
            round(sum(unmarshal_ms.values()) / len(unmarshal_ms), 2)
            if unmarshal_ms else None
        ),
        slowest_steps=hs.slowest(walls), step_raised=step_raised,
        python_gc=gc_log.summary(), generator_lateness_ms=0.0,
        note="closed loop: the next step begins when the filters are back, "
             "so the generator is never late by construction",
        compiles=window_log,
    )
    out = {
        "attempted": sent - warmup,
        "failed": sent - warmup - len(done_at),
        "end_to_end": {"setup_s": setup_s},
        "checks": checks,
        "memory_peak_bytes": peak,
        "layer": {"annotations": ANNOTATIONS},
    }
    if walls:
        lanes = [step_lanes(k) for k in done_at]
        out["end_to_end"]["verdict_lanes_per_s"] = hs.rate_in_window(
            list(done_at.values()), lanes, t0, r.seconds
        )
        out["end_to_end"]["verdict_p95_ms"] = hs.percentile(walls, 95)
        # ONE chip's useful lanes a launch: a module event is one plane's
        out["layer"]["lanes_per_launch"] = sum(lanes) / len(lanes) / n_ch
    spans = (
        hs.spans_in_window(r.obs, WINDOW_SPAN) if r.trace or r.series else []
    )
    if r.trace:
        out["layer"]["spans"] = spans
    if r.series:
        in_order = ws.spans_in_order(spans, SERIES_SPANS)
        out["series"] = {
            "unit": "step",
            "offered_at": [started_at[k] - t0 for k in done_at],
            "done_at": [done_at[k] - t0 for k in done_at],
            "harness_ms": [unmarshal_ms[k] for k in done_at],
            "harness_work": "gen.parse_block_bytes x 4: the deliver client's unmarshal",
            "spans": {
                name: rows for name, rows in in_order.items()
                if len(rows) == len(done_at)
            },
        }
    return out


def _every_chip_ran(devices_seen: Dict[int, int], answered: List[int],
                    chips: int) -> None:
    """The output of every answered step lived on all the chips (the
    validator read the set off the device array, before copying it back)."""
    short = {k: devices_seen[k] for k in answered if devices_seen[k] != chips}
    if short:
        raise hs.SeamGaveWay(
            f"the output of {len(short)} of {len(answered)} steps was not on "
            f"{chips} devices: {{step: devices}} {dict(list(short.items())[:5])}"
        )


def _threads_gone(before) -> None:
    deadline = time.monotonic() + 5.0
    while True:
        leaked = [
            t.name for t in threading.enumerate()
            if t not in before and t.is_alive()
        ]
        if not leaked:
            return
        if time.monotonic() > deadline:
            raise hs.SeamGaveWay(f"the validator left threads running: {leaked}")
        time.sleep(0.05)


def _compare(r: hs.Run, workers, chains: List[List[Dict]], channels: List[str],
             sent: int, filters: Dict[int, List[bytes]],
             checks: hs.Checks) -> None:
    """The reference's filter for every block of every step sent, against
    the filter the timed path left in the block.  With --control, the
    reference with that guarantee broken is then put in the program's place
    and compared the same way, on a line of its own: it has to come out not
    correct."""
    t0 = time.perf_counter()
    n_ch = len(channels)
    blocks = [(k, c) for k in range(sent) for c in range(n_ch)]

    def filters_of(rule) -> Dict[int, List[bytes]]:
        # the workers read the serialized blocks themselves
        rows = workers.run(
            "check", [(c, chains[c][k]["raw"], rule) for k, c in blocks]
        )
        out: Dict[int, List[bytes]] = {}
        for (k, _), row in zip(blocks, rows):
            out.setdefault(k, []).append(row)
        return out

    truth = filters_of(None)

    def gaps(got: Dict[int, List[bytes]], into: hs.Checks) -> None:
        gap = 0
        for k, want in truth.items():
            if k in got:  # a missing step counts under steps_unanswered
                gap += sum(
                    flt.mismatch_bytes(mine, theirs)
                    for mine, theirs in zip(got[k], want)
                ) + sum(len(w) for w in want[len(got[k]):])
        into.add("filter_mismatch_bytes", gap)

    gaps(filters, checks)
    # the generator's own plan is a third witness: the reference must find
    # exactly the poisons this path rejects, and no two channels of a step
    # may reject the same positions (a swap of two masks would then not show)
    plan_gap = steps_alike = steps_clean = 0
    for k, want in truth.items():
        rejected = []
        for c, found in enumerate(want):
            planned = flt.planned_filter(chains[c][k]["codes"], len(found))
            plan_gap += flt.mismatch_bytes(planned, found)
            rejected.append(tuple(flt.rejected_positions(found)))
        steps_clean += int(not all(rejected))
        steps_alike += int(len(set(rejected)) < len(rejected))
    checks.add("reference_vs_plan_bytes", plan_gap)
    checks.add("no_poison_found", steps_clean + steps_alike)
    hs.say(
        phase="output_check", steps_compared=len(truth),
        blocks_compared=len(blocks),
        non_valid_txs=sum(
            len(flt.rejected_positions(f)) for want in truth.values() for f in want
        ),
        reference_seconds=round(time.perf_counter() - t0, 2),
    )
    for rule in r.controls:
        control = hs.Checks()
        gaps(filters_of(rule), control)
        hs.say(phase="control", rule=rule, correct=control.correct,
               checks=control.rows)
