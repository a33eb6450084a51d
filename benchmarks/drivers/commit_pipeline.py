"""Driver ``commit_pipeline``: a peer catching up.  A pre-built chain of
blocks is submitted back to back to ``CommitPipeline.submit`` on one thread,
as a deliver loop does; the pipeline's own queue is the only back-pressure.

Timed path: ``Channel`` + ``CommitPipeline`` with the provider
``default_provider()`` returns on the chip (parse -> collect_sig_jobs ->
TPUProvider -> kernel -> policy -> MVCC -> kvledger.commit with fsync).

Output check: once the window has closed, every block the ledger holds is
read back (TRANSACTIONS_FILTER, height, the state of every key a transaction
asked to write) and compared with the plain reference, exactly.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List

from benchmarks import generator as gen
from benchmarks import harness as hs
from benchmarks import reference as ref
from benchmarks import window_series as ws

ANNOTATIONS = ("bench.submit",)
WINDOW_SPAN = "bench.window"
# the program's spans kept per block in the run's series (window_series.py)
SERIES_SPANS = (
    "pipeline.prepare", "pipeline.commit", "commit.await_verdicts",
    "commit.validate", "commit.rwsets", "commit.assemble_pvt", "ledger.mvcc",
    "ledger.block_append", "ledger.state_commit", "tpu.dispatch", "tpu.resolve",
)


def run(r: hs.Run) -> Dict:
    cfg, traffic = r.config, r.traffic
    warmup = int(traffic["warmup_blocks"])
    n_blocks = hs.backlog_length(
        warmup, traffic["chain_blocks_per_second"], r.seconds
    )
    r.mark("imports_done")
    world = gen.build_world(cfg)
    r.mark("world_built")
    membership_roots = gen.msp_roots(world)
    policy = {"n": int(cfg["policy_n"]), "mspids": list(cfg["policy_mspids"])}

    def check_block(task):
        envelopes, rule = task
        return ref.check_signatures_and_policy(
            envelopes, ref.Membership(membership_roots), policy, rule
        )

    workers = gen.ForkedWorkers(
        {
            "build": lambda n: gen.build_envelopes(world, cfg, n, r.seed),
            "check": check_block,
        },
        gen.worker_count(traffic),
    )
    try:
        return _run(r, world, workers, n_blocks, warmup)
    finally:
        workers.close()


def _run(r: hs.Run, world, workers, n_blocks: int, warmup: int) -> Dict:
    cfg = r.config
    chain: List[Dict] = []
    prev = b""

    def take(built) -> None:
        nonlocal prev
        for entry in built:
            prev = gen.seal_block(entry, len(chain), prev)
            chain.append(entry)

    # the warm-up's blocks first; the workers then build the rest, and keep
    # it, while this process traces, lowers and loads the program
    take(workers.run("build", range(warmup)))
    rest = workers.start("build", range(warmup, n_blocks))

    r.start_backend()
    r.mark("backend_up")
    from fabric_tpu.crypto.tpu_provider import _bucket
    from fabric_tpu.peer.channel import Channel
    from fabric_tpu.peer.pipeline import CommitPipeline

    provider = r.device_provider()
    r.mark("provider")
    ledger_dir = os.path.join(r.workdir, "ledger")
    channel = Channel(
        cfg["channel"], ledger_dir, gen.msp_manager(world, provider),
        world["registry"], provider,
    )
    r.mark("ledger_open")
    committed_at: Dict[int, float] = {}
    pipe = CommitPipeline(
        channel,
        on_commit=lambda b, f: committed_at.__setitem__(
            b.header.number, time.perf_counter()
        ),
        depth=int(cfg["pipeline_depth"]),
    )
    checks = hs.Checks()
    submitted_at: Dict[int, float] = {}
    unmarshal_ms: Dict[int, float] = {}
    exhausted = False
    try:
        # warm-up: the first blocks trace, lower and compile (or load) the
        # one program shape, outside the window, while the workers go on
        # building the rest of the chain
        for entry in chain:
            hs.check_bucket(entry["lanes"], _bucket(entry["lanes"]), r.want_bucket)
            pipe.submit(gen.parse_block_bytes(entry["raw"]))
        if not pipe.drain(timeout=1100):
            raise hs.SeamGaveWay(
                f"warm-up did not commit: {len(committed_at)} of {warmup} "
                f"blocks, last_error {pipe.last_error!r}"
            )
        hs.check_pipeline(pipe)
        warm_log = r.compiles.since_mark()
        r.mark("warm")
        take(workers.collect(rest))
        for entry in chain[warmup:]:
            hs.check_bucket(entry["lanes"], _bucket(entry["lanes"]), r.want_bucket)
        hs.GcLog.settle()
        r.mark("chain_built")
        ledger_filesystem = hs.filesystem_type(ledger_dir)
        hs.say(
            phase="setup", seconds_since_start=r.marks, workload=r.workload,
            chain_blocks=len(chain),
            block_txs=int(cfg["block_txs"]), lanes_per_block=chain[0]["lanes"],
            bucket=_bucket(chain[0]["lanes"]),
            ledger_filesystem=ledger_filesystem, ledger_dir=ledger_dir,
            backend=provider.describe_backend(), warmup=warm_log,
            compile_cache_dir=hs.compile_cache_dir(),
            native_library=hs.native_library(),
        )

        nxt = warmup
        with hs.UndisturbedSpan(WINDOW_SPAN, r.tracer), hs.GcLog() as gc_log:
            t0 = time.perf_counter()
            setup_s = t0 - r.t_process_start
            t_end = t0 + r.seconds
            while time.perf_counter() < t_end:
                r.tracer.tick(time.perf_counter(), t0, r.seconds)
                if nxt >= len(chain):
                    exhausted = True
                    break
                t_unmarshal = time.perf_counter()
                block = gen.parse_block_bytes(chain[nxt]["raw"])
                submitted_at[nxt] = time.perf_counter()
                unmarshal_ms[nxt] = (submitted_at[nxt] - t_unmarshal) * 1e3
                with hs.annotate("bench.submit", r.trace):
                    pipe.submit(block)
                nxt += 1
            remaining = t_end - time.perf_counter()
            if remaining > 0:  # only when the chain ran out
                time.sleep(remaining)
        r.tracer.stop()
        window_log = r.compiles.since_mark()
        # a block submitted inside the window is waited for: late is late,
        # and counts in the tail
        drained = pipe.drain(timeout=60.0)
    finally:
        pipe.stop()

    peak = hs.memory_peak_bytes(r.devices)
    sent = nxt
    lanes_sent = sum(e["lanes"] for e in chain[:sent])
    checks.add("chain_exhausted", int(exhausted))
    checks.add("blocks_never_committed", sent - len(committed_at))
    checks.seam("pipeline", lambda: hs.check_pipeline(pipe))
    checks.seam("drained", lambda: _require(drained, "drain timed out"))
    checks.seam("ledger_fsync", lambda: hs.check_fsync_costs(ledger_filesystem))
    if r.device_path:
        checks.seam("provider", lambda: hs.check_provider_seams(provider))
        checks.seam(
            "device_lanes",
            lambda: hs.check_device_lanes(r.obs.snapshot(), lanes_sent),
        )
    checks.seam("compiles_in_window", lambda: hs.check_no_compiles(window_log))
    try:
        _compare(r, workers, chain[:sent], _ledger_answers(channel.ledger), checks)
    finally:
        channel.ledger.close()

    # every block submitted inside the window, each waited for
    timed = [n for n in submitted_at if n in committed_at]
    latencies = [(committed_at[n] - submitted_at[n]) * 1e3 for n in timed]
    done = [committed_at[n] for n in timed]
    hs.say(
        phase="window", workload=r.workload, seconds=r.seconds,
        blocks_submitted=len(submitted_at), blocks_committed=len(timed),
        blocks_committed_by_the_clock=sum(1 for at in done if at <= t_end),
        block_commit_p50_ms=hs.percentile(latencies, 50) if latencies else None,
        samples=len(latencies), slowest_blocks=hs.slowest(latencies),
        python_gc=gc_log.summary(), generator_lateness_ms=0.0,
        note="closed loop: the next block is offered when submit() returns, "
             "so the generator is never late by construction",
        compiles=window_log,
    )
    out = {
        "attempted": len(submitted_at),
        "failed": len(submitted_at) - len(timed),
        "end_to_end": {"setup_s": setup_s},
        "checks": checks,
        "memory_peak_bytes": peak,
        "layer": {"annotations": ANNOTATIONS},
    }
    if timed:
        out["end_to_end"]["commit_tx_per_s"] = hs.rate_in_window(
            done, [int(cfg["block_txs"])] * len(timed), t0, r.seconds
        )
        out["end_to_end"]["block_commit_p90_ms"] = hs.percentile(latencies, 90)
        lanes = [chain[n]["lanes"] for n in timed]
        out["layer"]["lanes_per_launch"] = sum(lanes) / len(lanes)
    spans = (
        hs.spans_in_window(r.obs, WINDOW_SPAN) if r.trace or r.series else []
    )
    if r.trace:
        out["layer"]["spans"] = spans
    if r.series:
        out["series"] = {
            "unit": "block",
            "offered_at": [submitted_at[n] - t0 for n in timed],
            "done_at": [committed_at[n] - t0 for n in timed],
            "harness_ms": [unmarshal_ms[n] for n in timed],
            "harness_work": "gen.parse_block_bytes: the deliver client's unmarshal",
            "spans": {
                name: [rows.get(n) for n in timed]
                for name, rows in
                ws.spans_per_unit(spans, SERIES_SPANS, "block").items()
            },
        }
    return out


def _require(ok: bool, why: str) -> None:
    if not ok:
        raise hs.SeamGaveWay(why)


def _ledger_answers(ledger) -> Dict:
    """What the timed path left in the ledger: height, every block's
    TRANSACTIONS_FILTER, and a reader of the state."""
    from fabric_tpu.protos import common_pb2

    return {
        "height": ledger.height,
        "filters": [
            bytes(
                ledger.block_store.get_block_by_number(n).metadata.metadata[
                    common_pb2.TRANSACTIONS_FILTER
                ]
            )
            for n in range(ledger.height)
        ],
        "get": ledger.get_state,
    }


def _compare(r: hs.Run, workers, chain: List[Dict], answers: Dict,
             checks: hs.Checks) -> None:
    """The reference over every block sent, against what the ledger holds.
    With --control, the reference with that guarantee broken is then put in
    the program's place and compared the same way, on a line of its own: it
    has to come out not correct."""
    t0 = time.perf_counter()
    blocks = [ref.block_envelopes(e["raw"]) for e in chain]

    def ledger_of(rule):
        rows_of = workers.run(
            "check", [(envelopes, rule) for _, envelopes in blocks]
        )
        ledger = ref.Ledger(rule)
        for (number, _), rows in zip(blocks, rows_of):
            ledger.commit(number, rows)
        return ledger, rows_of

    truth, checked = ledger_of(None)
    keys = [k for rows in checked for k in ref.written_keys(rows)]

    def gaps(got: Dict, into: hs.Checks) -> None:
        filter_gap = 0
        for n, want in enumerate(truth.filters):
            mine = got["filters"][n] if n < len(got["filters"]) else b""
            filter_gap += abs(len(mine) - len(want)) + sum(
                1 for a, b in zip(mine, want) if a != b
            )
        into.add("filter_mismatch_bytes", filter_gap)
        into.add("state_mismatch_keys", sum(
            1 for namespace, key in keys
            if got["get"](namespace, key) != truth.get(namespace, key)
        ))
        into.add("height_gap", abs(got["height"] - truth.height))

    gaps(answers, checks)
    non_valid = sum(1 for f in truth.filters for c in f if c != ref.VALID)
    # the generator's own plan is a third witness: the reference must find
    # exactly the poisons that were planted
    plan_gap = 0
    for entry, want in zip(chain, truth.filters):
        planned = bytearray(len(want))
        for i, code in entry["codes"].items():
            planned[i] = code
        plan_gap += sum(1 for a, b in zip(planned, want) if a != b)
    checks.add("reference_vs_plan_bytes", plan_gap)
    checks.add("no_poison_found", int(non_valid == 0))
    hs.say(
        phase="output_check", blocks_compared=len(truth.filters),
        keys_read_back=len(keys), non_valid_txs=non_valid,
        reference_seconds=round(time.perf_counter() - t0, 2),
    )
    for rule in r.controls:
        stand_in, _ = ledger_of(rule)
        control = hs.Checks()
        gaps({"height": stand_in.height, "filters": stand_in.filters,
              "get": stand_in.get}, control)
        hs.say(phase="control", rule=rule, correct=control.correct,
               checks=control.rows)
