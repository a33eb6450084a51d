"""Driver ``sidecar_client``: one peer's signature lanes through a device
sidecar.  A ``SidecarServer(engine="device")`` on a unix socket and a
``SidecarProvider`` client live in the one process that holds the chip (as
chip_smoke.py's serve phase); one closed-loop client sends the lanes of one
block per request and the next when the mask is back, so every launch has one
shape (two clients or more coalesce into larger launches: another driver's,
which warms those shapes).

Timed path: ``SidecarProvider.batch_verify`` (encode -> socket -> decode ->
VerifyBatcher -> TPUProvider -> kernel -> mask back).

Output check: once the window has closed, the mask of every request sent is
compared, lane for lane, with the plain reference's.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Dict, List

from benchmarks import generator as gen
from benchmarks import harness as hs
from benchmarks import reference as ref
from benchmarks import window_series as ws

ANNOTATIONS = ("bench.batch_verify",)
WINDOW_SPAN = "bench.window"
# the program's spans kept per request in the run's series (window_series.py):
# one client in a closed loop, so the k-th span of a name is the k-th request's
SERIES_SPANS = (
    "client.roundtrip", "serve.verify", "tpu.dispatch", "tpu.resolve",
)


def run(r: hs.Run) -> Dict:
    cfg, traffic = r.config, r.traffic
    warmup = int(traffic["warmup_requests"])
    n_requests = hs.backlog_length(
        warmup, traffic["requests_built_per_second"], r.seconds
    )
    r.mark("imports_done")
    world = gen.build_world(cfg)
    r.mark("world_built")

    def build_request(number: int) -> Dict:
        built = gen.build_envelopes(world, cfg, number, r.seed)
        return gen.block_lanes(world, cfg, built["envelopes"], r.seed, number)

    def check_request(task):
        points, sigs, digests, rule = task
        return ref.verify_lanes(points, sigs, digests, rule)

    workers = gen.ForkedWorkers(
        {"build": build_request, "check": check_request},
        gen.worker_count(traffic),
    )
    try:
        return _run(r, workers, n_requests, warmup)
    finally:
        workers.close()


def _run(r: hs.Run, workers, n_requests: int, warmup: int) -> Dict:
    cfg = r.config
    first_built = workers.run("build", range(warmup))
    rest = workers.start("build", range(warmup, n_requests))
    r.start_backend()
    r.mark("backend_up")
    from fabric_tpu.crypto.bccsp import ECDSAPublicKey
    from fabric_tpu.crypto.tpu_provider import _bucket
    from fabric_tpu.serve.client import SidecarProvider
    from fabric_tpu.serve.server import SidecarServer

    key_objects: Dict = {None: None}

    def with_keys(request: Dict) -> Dict:
        for point in request["points"]:
            if point not in key_objects:
                key_objects[point] = ECDSAPublicKey(*point)
        request["keys"] = [key_objects[p] for p in request["points"]]
        hs.check_bucket(
            request["lanes"], _bucket(request["lanes"]), r.want_bucket
        )
        return request

    before = set(threading.enumerate())
    # the unix socket the configuration states, whatever TMPDIR is: sun_path
    # holds 108 bytes, so the socket's directory is named through its open
    # descriptor and the address stays short
    sock_dir = tempfile.mkdtemp(prefix="bench_serve_")
    sock_dir_fd = os.open(sock_dir, os.O_RDONLY | os.O_DIRECTORY)
    address = f"/proc/self/fd/{sock_dir_fd}/s.sock"
    # warm_ladder="off" and no warm(): the ladder compiles a second (limb)
    # program the serving path never calls, and warm()'s 8-lane batch would
    # compile the 128-lane bucket
    server = SidecarServer(
        address=address, engine=r.serve_engine, warm_ladder="off",
        buckets=(int(cfg["lane_bucket"]),),
    )
    server.start()
    client = SidecarProvider(address=server.address)
    checks = hs.Checks()
    requests: List[Dict] = []
    masks: Dict[int, List[bool]] = {}
    started_at: Dict[int, float] = {}
    done_at: Dict[int, float] = {}
    exhausted = False
    try:
        for request in first_built:
            requests.append(with_keys(request))
        # the one program shape is traced, lowered and compiled (or loaded)
        # by the sidecar's own provider before the first request: over the
        # wire it would outlast the client's reply timeout.  SidecarServer's
        # warm() is not used: it compiles the 128-lane bucket.
        first = requests[0]
        server.provider.batch_verify(first["keys"], first["sigs"], first["digests"])
        for n, req in enumerate(requests):
            masks[n] = client.batch_verify(req["keys"], req["sigs"], req["digests"])
        warm_log = r.compiles.since_mark()
        r.mark("warm")
        for request in workers.collect(rest):
            requests.append(with_keys(request))
        hs.GcLog.settle()
        r.mark("requests_built")
        hs.say(
            phase="setup", seconds_since_start=r.marks, workload=r.workload, requests_built=len(requests),
            lanes_per_request=requests[0]["lanes"],
            bucket=_bucket(requests[0]["lanes"]), engine=r.serve_engine,
            backend=server.provider.describe_backend(), warmup=warm_log,
            address=f"unix socket in {sock_dir}",
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
                if nxt >= len(requests):
                    exhausted = True
                    break
                req = requests[nxt]
                started_at[nxt] = time.perf_counter()
                with hs.annotate("bench.batch_verify", r.trace):
                    masks[nxt] = client.batch_verify(
                        req["keys"], req["sigs"], req["digests"]
                    )
                done_at[nxt] = time.perf_counter()
                nxt += 1
            remaining = t_end - time.perf_counter()
            if remaining > 0:  # only when the requests ran out
                time.sleep(remaining)
        r.tracer.stop()
        window_log = r.compiles.since_mark()
        stats = client.client.stats()
        serve_provider = server.provider
    finally:
        client.client.close()
        server.stop()
        try:
            os.unlink(address)
        except OSError:
            pass
        os.close(sock_dir_fd)
        os.rmdir(sock_dir)

    peak = hs.memory_peak_bytes(r.devices)
    sent = nxt
    lanes_sent = sum(q["lanes"] for q in requests[:sent])
    checks.add("requests_exhausted", int(exhausted))
    checks.add("requests_unanswered", sent - len(masks))
    checks.seam("client", lambda: hs.check_sidecar_client(client))
    checks.seam(
        "op_stats",
        lambda: hs.check_sidecar_stats(stats, lanes_sent, sent, r.serve_engine),
    )
    if r.device_path:
        checks.seam("provider", lambda: hs.check_provider_seams(serve_provider))
        checks.seam(
            "device_lanes",
            lambda: hs.check_device_lanes(
                r.obs.snapshot(), lanes_sent + requests[0]["lanes"]
            ),
        )
    checks.seam("compiles_in_window", lambda: hs.check_no_compiles(window_log))
    checks.seam("threads_left", lambda: _threads_gone(before))

    _compare(r, workers, requests[:sent], masks, checks)

    walls = [(done_at[n] - started_at[n]) * 1e3 for n in done_at]
    hs.say(
        phase="window", workload=r.workload, seconds=r.seconds,
        requests=len(walls),
        verdict_p50_ms=hs.percentile(walls, 50) if walls else None,
        samples=len(walls), launches=stats["launches"],
        slowest_requests=hs.slowest(walls), python_gc=gc_log.summary(),
        generator_lateness_ms=0.0,
        note="closed loop: the next request is sent when the mask is back, "
             "so the generator is never late by construction",
        compiles=window_log,
    )
    out = {
        "attempted": len(started_at),
        "failed": len(started_at) - len(done_at),
        "end_to_end": {"setup_s": setup_s},
        "checks": checks,
        "memory_peak_bytes": peak,
        "layer": {
            "annotations": ANNOTATIONS,
            "client_wall_ms": [
                (done_at[n] - started_at[n]) * 1e3 for n in done_at
                if done_at[n] <= t0 + hs.undisturbed_seconds(r)
            ],
        },
    }
    if walls:
        lanes = [requests[n]["lanes"] for n in done_at]
        rate = hs.rate_in_window(list(done_at.values()), lanes, t0, r.seconds)
        out["end_to_end"]["verdict_lanes_per_s"] = rate
        out["end_to_end"]["verdict_p95_ms"] = hs.percentile(walls, 95)
        out["layer"]["lanes_per_launch"] = sum(lanes) / len(lanes)
    spans = (
        hs.spans_in_window(r.obs, WINDOW_SPAN) if r.trace or r.series else []
    )
    if r.trace:
        out["layer"]["spans"] = spans
    if r.series:
        in_order = ws.spans_in_order(spans, SERIES_SPANS)
        out["series"] = {
            "unit": "request",
            "offered_at": [started_at[n] - t0 for n in done_at],
            "done_at": [done_at[n] - t0 for n in done_at],
            "harness_ms": [],
            "harness_work": "none: the request's operands are built before the window",
            "spans": {
                name: rows for name, rows in in_order.items()
                if len(rows) == len(done_at)
            },
        }
    return out


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
            raise hs.SeamGaveWay(f"sidecar stop left threads running: {leaked}")
        time.sleep(0.05)


def _compare(r: hs.Run, workers, requests: List[Dict],
             masks: Dict[int, List[bool]], checks: hs.Checks) -> None:
    """The reference's mask for every request sent, against the mask the
    sidecar returned.  With --control, the reference with that guarantee
    broken is then put in the program's place and compared the same way, on
    a line of its own: it has to come out not correct."""
    t0 = time.perf_counter()

    def masks_of(rule):
        return workers.run("check", [
            (q["points"], q["sigs"], q["digests"], rule) for q in requests
        ])

    truth = masks_of(None)

    def gaps(got: Dict[int, List[bool]], into: hs.Checks) -> None:
        gap = 0
        for n, want in enumerate(truth):
            if n in got:  # a missing one counts under requests_unanswered
                gap += abs(len(got[n]) - len(want)) + sum(
                    1 for a, b in zip(got[n], want) if bool(a) != b
                )
        into.add("mask_mismatch_lanes", gap)

    gaps(masks, checks)
    false_lanes = sum(1 for want in truth for v in want if not v)
    checks.add("no_false_lane_found", int(false_lanes == 0))
    hs.say(
        phase="output_check", requests_compared=len(truth),
        lanes_compared=sum(len(m) for m in truth), false_lanes=false_lanes,
        reference_seconds=round(time.perf_counter() - t0, 2),
    )
    for rule in r.controls:
        control = hs.Checks()
        gaps(dict(enumerate(masks_of(rule))), control)
        hs.say(phase="control", rule=rule, correct=control.correct,
               checks=control.rows)
