"""Driver ``sidecar_fleet``: four peers' signature lanes through one device
sidecar.  ``SidecarServer(engine="device", warm_ladder="verify",
buckets=(2048, 4096, 8192))`` listens on a unix socket in the one process
that holds the chip; each peer is a process of its own, forked before that
process touches JAX, with a ``SidecarProvider`` client and a chain of blocks
no other peer sends.  A peer runs a closed loop: one block's lanes per
request, the next request when the mask is back.  The sidecar's batcher
coalesces what the four send at once into launches of 1-4 requests, at the
widths its ``warm()`` built.

Timed path, per request: encode in the peer -> socket -> decode ->
VerifyBatcher (linger, coalescing, launches in flight) -> TPUProvider's bytes
program at 2,048, 4,096 or 8,192 lanes -> the request's own lanes of the
coalesced mask -> reply -> decode in the peer.

Output check: once the window has closed, each peer compares the mask of
every request it sent, lane for lane, with the plain reference's (computed
by its own forked workers); the driver adds up what the four found.

A program whose sidecar cannot warm the program it serves (its ``verify``
ladder compiled another) fails at once, before anything is built or JAX is
touched: the driver checks for it first.
"""

from __future__ import annotations

import math
import multiprocessing
import os
import tempfile
import threading
import time
from typing import Dict, List, Optional

from benchmarks import generator as gen
from benchmarks import harness as hs
from benchmarks import reference as ref
from benchmarks.drivers import sidecar_client as sc

# what covers a gap of the device in the sidecar process (the peers'
# annotations are in other processes, on no trace)
ANNOTATIONS = ("batcher.linger", "batcher.launch", "batcher.settle",
               "serve.decode", "serve.reply")
WINDOW_SPAN = "bench.window"
# the whole window (the traced run reads its launches over the profiler's
# slice, in the second half) and the instant the slice opened
WHOLE_WINDOW_SPAN = "bench.fleet_window"
SLICE_OPENED_SPAN = "bench.fleet_slice_opened"
# how far ahead of the window's opening the peers are told of it
WINDOW_START_S = 0.25
# how long a peer may take to answer once it should have (a request never
# takes seconds; building the backlog and the reference's check do)
PEER_PATIENCE_S = 600.0


def require_served_warm() -> None:
    """The sidecar's ``verify`` ladder warms the program the provider
    serves with, or the run ends now (exit 2, before a block is built or
    JAX is touched): where it warms another, launches of 4,096 and 8,192
    lanes would compile inside the window."""
    try:
        from fabric_tpu.serve.server import SidecarServer
    except ImportError as exc:
        raise hs.SeamGaveWay(f"no sidecar in this program: {exc}") from exc
    if not hasattr(SidecarServer, "_warm_served"):
        raise hs.SeamGaveWay(
            "the sidecar's verify ladder does not warm the program it serves "
            "(serve/server.py has no SidecarServer._warm_served)"
        )


def run(r: hs.Run) -> Dict:
    require_served_warm()
    cfg, traffic = r.config, r.traffic
    peers = int(cfg["peers"])
    warmup = int(traffic["warmup_requests"])
    total = hs.backlog_length(
        peers * warmup, traffic["requests_built_per_second"], r.seconds
    )
    r.mark("imports_done")
    world = gen.build_world(cfg)
    r.mark("world_built")
    # the socket's directory is named through a descriptor that the peers
    # inherit: sun_path holds 108 bytes, whatever TMPDIR is
    sock_dir = tempfile.mkdtemp(prefix="bench_fleet_")
    sock_dir_fd = os.open(sock_dir, os.O_RDONLY | os.O_DIRECTORY)
    address = f"/proc/self/fd/{sock_dir_fd}/s.sock"
    fleet = _Fleet(
        world, cfg, r.seed, peers, math.ceil(total / peers), warmup,
        max(1, gen.worker_count(traffic) // peers), address,
    )
    try:
        return _run(r, fleet, address)
    finally:
        fleet.close()
        try:
            os.unlink(os.path.join(sock_dir, "s.sock"))
        except OSError:
            pass
        os.close(sock_dir_fd)
        os.rmdir(sock_dir)


# ---------------------------------------------------------------------------
# the peers
# ---------------------------------------------------------------------------


def _peer_main(conn, peer: int, world, cfg: Dict, seed: int, peers: int,
               n_requests: int, warmup: int, workers: int,
               address: str) -> None:
    """One peer process: builds its chain with forked workers of its own,
    then does what the sidecar's process tells it, one phase at a time."""
    cfg = dict(cfg, channel=f"{cfg['channel']}-peer{peer}")

    def build(i: int) -> Dict:
        # block numbers are distinct over the peers, and so is everything
        # the generator draws from them
        number = i * peers + peer
        built = gen.build_envelopes(world, cfg, number, seed)
        return gen.block_lanes(world, cfg, built["envelopes"], seed, number)

    def check(task):
        points, sigs, digests, rule = task
        return ref.verify_lanes(points, sigs, digests, rule)

    pool = gen.ForkedWorkers({"build": build, "check": check}, workers)
    try:
        _Peer(conn, pool, warmup, n_requests, address).serve()
    except Exception as exc:  # noqa: BLE001 - reported to the sidecar's process
        try:
            conn.send(("error", f"peer {peer}: {exc!r}"))
        except (OSError, ValueError):
            pass
    finally:
        pool.close()
        conn.close()


class _Peer:
    def __init__(self, conn, pool, warmup: int, n_requests: int,
                 address: str):
        self.conn = conn
        self.pool = pool
        self.warmup = warmup
        self.address = address
        self.first = pool.start("build", range(warmup))
        self.rest = pool.start("build", range(warmup, n_requests))
        self.requests: List[Dict] = []
        self.masks: Dict[int, List[bool]] = {}
        self.rows: List[List[float]] = []
        self.keys: Dict = {None: None}
        self.client = None
        self.sent = 0

    def serve(self) -> None:
        while True:
            message = self.conn.recv()
            if message[0] == "warm":
                self.conn.send(("warmed", self.warm()))
            elif message[0] == "window":
                self.conn.send(("done", self.window(*message[1:])))
            elif message[0] == "check":
                self.conn.send(("checked", self.check(message[1])))
            elif message[0] == "stop":
                return

    def _keyed(self, request: Dict) -> Dict:
        from fabric_tpu.crypto.bccsp import ECDSAPublicKey

        for point in request["points"]:
            if point not in self.keys:
                self.keys[point] = ECDSAPublicKey(*point)
        request["keys"] = [self.keys[p] for p in request["points"]]
        return request

    def _send(self, n: int) -> None:
        req = self.requests[n]
        self.masks[n] = self.client.batch_verify(
            req["keys"], req["sigs"], req["digests"]
        )

    def warm(self) -> Dict:
        """The warm-up requests, then the rest of the chain collected."""
        from fabric_tpu.crypto.bccsp import SoftwareProvider
        from fabric_tpu.serve.client import SidecarProvider

        # a request the sidecar cannot serve is verified here, on the host
        # (never on a device this process must not touch), and shows as
        # `degraded`
        self.client = SidecarProvider(
            address=self.address, fallback=SoftwareProvider()
        )
        for request in self.pool.collect(self.first):
            self.requests.append(self._keyed(request))
        for n in range(self.warmup):
            self._send(n)
        self.sent = self.warmup
        for request in self.pool.collect(self.rest):
            self.requests.append(self._keyed(request))
        return {"built": len(self.requests),
                "lanes_per_request": self.requests[0]["lanes"]}

    def window(self, t0: float, t_end: float) -> Dict:
        """The closed loop, on the system's monotonic clock; the client is
        closed when the window is."""
        exhausted = False
        delay = t0 - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        while time.monotonic() < t_end:
            if self.sent >= len(self.requests):
                exhausted = True
                break
            n = self.sent
            self.sent += 1
            sent_at = time.monotonic()
            self._send(n)
            self.rows.append([sent_at, time.monotonic(),
                              self.requests[n]["lanes"]])
        self.client.client.close()
        return {
            "rows": self.rows, "exhausted": exhausted,
            "sent": self.sent, "answered": len(self.masks),
            "lanes_sent": sum(q["lanes"] for q in self.requests[:self.sent]),
            "degraded": bool(self.client.degraded),
        }

    def check(self, rules) -> Dict:
        """Every request sent against the reference's mask, and against
        the reference with each control's guarantee broken."""
        sent = self.requests[:self.sent]

        def masks_of(rule):
            return self.pool.run("check", [
                (q["points"], q["sigs"], q["digests"], rule) for q in sent
            ])

        def gap(got: Dict[int, List[bool]], want: List[List[bool]]) -> int:
            return sum(
                abs(len(got[n]) - len(w))
                + sum(1 for a, b in zip(got[n], w) if bool(a) != b)
                for n, w in enumerate(want) if n in got
            )

        truth = masks_of(None)
        return {
            "mask_mismatch_lanes": gap(self.masks, truth),
            "false_lanes": sum(1 for w in truth for v in w if not v),
            "requests_compared": len(truth),
            "lanes_compared": sum(len(w) for w in truth),
            "controls": {
                rule: gap(dict(enumerate(masks_of(rule))), truth)
                for rule in rules
            },
        }


class _Fleet:
    """The peer processes and a pipe to each.  ``ask`` sends every peer
    that is still running a message and returns each one's answer; a peer
    that died, or answered with an error, is out from then on."""

    def __init__(self, world, cfg: Dict, seed: int, peers: int,
                 per_peer: int, warmup: int, workers: int, address: str):
        # forked, as generator.ForkedWorkers are: the world's private keys
        # cannot be pickled, and this process has no thread and no JAX
        # backend yet
        ctx = multiprocessing.get_context("fork")
        self.procs: List = []
        self.conns: List = []
        self.errors: List[str] = []
        for p in range(peers):
            ours, theirs = ctx.Pipe()
            proc = ctx.Process(
                target=_peer_main,
                args=(theirs, p, world, cfg, seed, peers, per_peer, warmup,
                      workers, address),
                # not a daemon: a peer forks workers of its own
                daemon=False,
            )
            proc.start()
            theirs.close()
            self.procs.append(proc)
            self.conns.append(ours)
        self.alive = list(range(peers))

    def tell(self, message) -> None:
        for p in list(self.alive):
            try:
                self.conns[p].send(message)
            except (OSError, ValueError) as exc:
                self._lose(p, f"peer {p} gone: {exc!r}")

    def answers(self, expect: str, deadline: float) -> Dict[int, Dict]:
        """Each running peer's answer, waited for until ``deadline`` (the
        monotonic clock)."""
        got: Dict[int, Dict] = {}
        for p in list(self.alive):
            conn = self.conns[p]
            try:
                if not conn.poll(max(0.0, deadline - time.monotonic())):
                    self._lose(p, f"peer {p} gave no {expect!r} answer")
                    continue
                kind, body = conn.recv()
            except (EOFError, OSError) as exc:
                self._lose(p, f"peer {p} died before its {expect!r}: {exc!r}")
                continue
            if kind != expect:
                self._lose(p, str(body))
                continue
            got[p] = body
        return got

    def ask(self, message, expect: str) -> Dict[int, Dict]:
        self.tell(message)
        return self.answers(expect, time.monotonic() + PEER_PATIENCE_S)

    def _lose(self, p: int, why: str) -> None:
        self.alive.remove(p)
        self.errors.append(why)

    def close(self) -> None:
        for p in self.alive:
            try:
                self.conns[p].send(("stop",))
            except (OSError, ValueError):
                pass
        for proc in self.procs:
            proc.join(timeout=10)
            if proc.is_alive():
                proc.kill()
                proc.join()
        for conn in self.conns:
            conn.close()
        self.alive = []


# ---------------------------------------------------------------------------
# the sidecar's process
# ---------------------------------------------------------------------------


def _run(r: hs.Run, fleet: _Fleet, address: str) -> Dict:
    cfg = r.config
    peers = int(cfg["peers"])
    buckets = tuple(int(b) for b in cfg["buckets"])
    r.start_backend()
    r.mark("backend_up")
    from fabric_tpu.common import fabobs
    from fabric_tpu.serve.client import SidecarClient
    from fabric_tpu.serve.server import SidecarServer

    before = set(threading.enumerate())
    server = SidecarServer(
        address=address, engine=r.serve_engine,
        provider=(
            r.provider_factory() if r.provider_factory is not None else None
        ),
        warm_ladder="verify", buckets=buckets,
    )
    checks = hs.Checks()
    answered_all = set(fleet.alive)
    try:
        warm_report = server.warm()
        warm_log = r.compiles.since_mark()
        r.mark("warm")
        server.start()
        warmed = fleet.ask(("warm",), "warmed")
        answered_all &= set(warmed)
        warmup_log = r.compiles.since_mark()
        hs.GcLog.settle()
        r.mark("requests_built")
        lanes_per_request = next(iter(warmed.values()))["lanes_per_request"] \
            if warmed else None
        if r.on_chip and lanes_per_request is not None:
            hs.check_bucket(
                lanes_per_request,
                server.provider.bucket_for(lanes_per_request), r.want_bucket,
            )
        hs.say(
            phase="setup", seconds_since_start=r.marks, workload=r.workload,
            peers=peers, peers_warmed=len(warmed),
            requests_built=sum(w["built"] for w in warmed.values()),
            lanes_per_request=lanes_per_request, buckets=list(buckets),
            engine=r.serve_engine,
            backend=server.provider.describe_backend(),
            warm=warm_report, warm_compiles=warm_log,
            warmup_requests_compiles=warmup_log,
            address="unix socket, its directory named through a descriptor",
            compile_cache_dir=hs.compile_cache_dir(),
            native_library=hs.native_library(),
        )

        # the four loops open the window at one instant, which every peer
        # is told before it comes: no peer's first request leads the
        # others' by the time the peers took to read their messages
        t0 = time.monotonic() + WINDOW_START_S
        t_end = t0 + r.seconds
        fleet.tell(("window", t0, t_end))
        time.sleep(max(0.0, t0 - time.monotonic()))
        with hs.UndisturbedSpan(WINDOW_SPAN, r.tracer), hs.GcLog() as gc_log:
            t0_perf = time.perf_counter()
            setup_s = t0_perf - r.t_process_start
            slice_opened: Optional[float] = None
            while time.monotonic() < t_end:
                r.tracer.tick(time.perf_counter(), t0_perf, r.seconds)
                started = r.tracer._started_at
                if slice_opened is None and started is not None:
                    slice_opened = started
                    fabobs.obs_record_span(
                        SLICE_OPENED_SPAN, started, started
                    )
                time.sleep(0.005)
        r.tracer.stop()
        done = fleet.answers("done", t_end + PEER_PATIENCE_S)
        answered_all &= set(done)
        window_log = r.compiles.since_mark()
        fabobs.obs_record_span(WHOLE_WINDOW_SPAN, t0_perf, time.perf_counter())
        stats_client = SidecarClient(server.address)
        try:
            stats = stats_client.stats()
        finally:
            stats_client.close()
        serve_provider = server.provider
        launches = server.batcher.launches
    finally:
        server.stop()

    peak = hs.memory_peak_bytes(r.devices)
    sent = sum(d["sent"] for d in done.values())
    lanes_sent = sum(d["lanes_sent"] for d in done.values())
    checks.add("requests_exhausted",
               sum(1 for d in done.values() if d["exhausted"]))
    checks.add("requests_unanswered",
               sum(d["sent"] - d["answered"] for d in done.values()))
    checks.seam("client", lambda: _no_peer_degraded(done))
    checks.seam(
        "op_stats",
        lambda: hs.check_sidecar_stats(stats, lanes_sent, sent, r.serve_engine),
    )
    if r.device_path:
        checks.seam("provider", lambda: hs.check_provider_seams(serve_provider))
        checks.seam(
            "device_lanes",
            lambda: hs.check_device_lanes(r.obs.snapshot(), lanes_sent),
        )
    checks.seam("compiles_in_window", lambda: hs.check_no_compiles(window_log))
    checks.seam(
        "launch_buckets",
        lambda: check_launch_buckets(r.obs.snapshot(), buckets, launches),
    )
    checks.seam("threads_left", lambda: sc._threads_gone(before))

    checked = _compare(r, fleet)
    answered_all &= set(checked)
    checks.add("mask_mismatch_lanes",
               sum(c["mask_mismatch_lanes"] for c in checked.values()))
    checks.add("no_false_lane_found",
               int(sum(c["false_lanes"] for c in checked.values()) == 0))
    for why in fleet.errors:
        hs.say(phase="peer_lost", why=why)
    checks.add("peers_running", peers - len(answered_all))

    rows = [row for d in done.values() for row in d["rows"]]
    walls = [(got - sent_at) * 1e3 for sent_at, got, _ in rows]
    hs.say(
        phase="window", workload=r.workload, seconds=r.seconds,
        requests=len(walls), peers_answered=len(done),
        requests_by_peer={p: len(d["rows"]) for p, d in done.items()},
        verdict_p50_ms=hs.percentile(walls, 50) if walls else None,
        launches=stats["launches"], batched_lanes=stats["batched_lanes"],
        slowest_requests=hs.slowest(walls), python_gc=gc_log.summary(),
        generator_lateness_ms=0.0,
        note="closed loops: each peer sends its next request when its mask "
             "is back, so the generator is never late by construction",
        compiles=window_log,
    )
    out = {
        "attempted": len(rows),
        "failed": sent - sum(d["answered"] for d in done.values()),
        "end_to_end": {"setup_s": setup_s},
        "checks": checks,
        "memory_peak_bytes": peak,
        "layer": {"annotations": ANNOTATIONS},
    }
    if walls:
        out["end_to_end"]["verdict_lanes_per_s"] = hs.rate_in_window(
            [got for _, got, _ in rows], [lanes for _, _, lanes in rows],
            t0, r.seconds,
        )
        out["end_to_end"]["verdict_p95_ms"] = hs.percentile(walls, 95)
    if r.trace:
        out["layer"]["spans"] = hs.spans_in_window(r.obs, WINDOW_SPAN)
        whole = hs.spans_in_window(r.obs, WHOLE_WINDOW_SPAN)
        out["layer"]["fleet_launches"] = [
            e for e in whole if e["name"] == "batcher.launch"
        ]
        opened = [e["ts"] for e in whole if e["name"] == SLICE_OPENED_SPAN]
        out["layer"]["slice_opened_us"] = opened[0] if opened else None
    return out


def _no_peer_degraded(done: Dict[int, Dict]) -> None:
    degraded = sorted(p for p, d in done.items() if d["degraded"])
    if degraded:
        raise hs.SeamGaveWay(
            f"SidecarProvider.degraded is set in peer(s) {degraded}: a request "
            "was served in the peer, not by the sidecar"
        )


def launch_buckets(snapshot: Dict) -> Dict[int, int]:
    """{bucket: launches} of fabric_batcher_launches_by_bucket_total."""
    series = snapshot.get(
        "fabric_batcher_launches_by_bucket_total", {}
    ).get("series", {})
    return {int(k.split("=", 1)[1]): int(v) for k, v in series.items()}


def check_launch_buckets(snapshot: Dict, buckets, launches: int) -> None:
    counted = launch_buckets(snapshot)
    unwarmed = sorted(set(counted) - set(buckets))
    if unwarmed:
        raise hs.SeamGaveWay(
            f"launches at widths the sidecar did not warm: {unwarmed} "
            f"(warmed {list(buckets)}; by bucket {counted})"
        )
    if sum(counted.values()) != launches:
        raise hs.SeamGaveWay(
            f"fabric_batcher_launches_by_bucket_total counts "
            f"{sum(counted.values())} launches, the batcher made {launches}"
        )


def _compare(r: hs.Run, fleet: _Fleet) -> Dict[int, Dict]:
    """Each peer's requests against the plain reference (and, with
    --control, against the reference with that guarantee broken, which has
    to come out not correct)."""
    t0 = time.perf_counter()
    checked = fleet.ask(("check", list(r.controls)), "checked")
    hs.say(
        phase="output_check", peers=len(checked),
        requests_compared=sum(c["requests_compared"] for c in checked.values()),
        lanes_compared=sum(c["lanes_compared"] for c in checked.values()),
        false_lanes=sum(c["false_lanes"] for c in checked.values()),
        reference_seconds=round(time.perf_counter() - t0, 2),
    )
    for rule in r.controls:
        control = hs.Checks()
        control.add("mask_mismatch_lanes",
                    sum(c["controls"][rule] for c in checked.values()))
        hs.say(phase="control", rule=rule, correct=control.correct,
               checks=control.rows)
    return checked
