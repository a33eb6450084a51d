"""The resident validation sidecar process.

One long-lived process owns the verify backends (host EC ladder or the
device provider), pre-warms the bucketed program registry at startup,
and serves whole-batch validation requests over a local socket — the
software analogue of 2104.06968's attached hardware validator, serving
1907.08367's reordered validation stages from a warm process.  What a
cold bench run pays per invocation (backend init, pool spin-up, minutes
of XLA compile), the sidecar pays once per process lifetime.

Request flow per VERIFY frame::

    decode -> serve.dispatch fault seam -> QoS CLASS ADMISSION
    (per-class lane quotas, work-conserving borrowing) -> ADMISSION
    (VerifyBatcher bounded lanes, non-blocking) -> coalesced launch ->
    mask reply

Admission control is two-tiered protocol backpressure: the per-class
:class:`~fabric_tpu.serve.qos.ClassLedger` quota first (priority-aware
— a zipf spam channel can borrow idle lanes but never a paying
channel's reservation), then the VerifyBatcher's bounded-lane budget.
A request that does not fit NOW is REJECTED with ``ST_BUSY`` + a
per-class ``retry_after_ms`` instead of blocking the socket thread —
the client shim paces retries with ``common.retry`` and the peer's
deliver loop stalls exactly like the reference's WaitReady discipline.
Every shed is a protocol-level reply, never a silent drop.

Shutdown is fail-closed *and* mask-exact: in-flight requests settled by
a dying batcher are answered ``ST_STOPPING`` (never an OK carrying
guessed verdicts), so the client re-verifies in-process and masks stay
bit-exact through a sidecar kill.  ``drain()`` (OP_DRAIN / SIGTERM) is
the rolling-restart half: NEW work answers ``ST_STOPPING`` immediately
while in-flight requests settle with their real computed verdicts, so
restarting every sidecar behind a router under load never costs a mask
bit.

Run it::

    python -m fabric_tpu.serve --address /tmp/fabserve.sock \
        --engine host --warm demo --aot-dir .jax_cache/serve_aot
"""

from __future__ import annotations

import collections
import json
import os
import socket
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from fabric_tpu.common import fabobs
from fabric_tpu.common.faults import fault_point
from fabric_tpu.common.flogging import must_get_logger
from fabric_tpu.common.metrics import latency_summary
from fabric_tpu.serve import protocol as proto
from fabric_tpu.serve.qos import ClassLedger
from fabric_tpu.serve.registry import (
    BucketProgramRegistry,
    DEFAULT_BUCKETS,
    demo_limb_program,
    verify_limb_program,
)

logger = must_get_logger("serve.server")

ENGINES = ("auto", "host", "device")
WARM_LADDERS = ("off", "demo", "verify")


# wire-level address parsing lives with the protocol (shared by both
# ends); re-exported here for back-compat with existing importers
parse_address = proto.parse_address


class ServeStats:
    """Request accounting with a dual surface: ``summary()`` stays the
    STATS reply and the ``configs.serve`` bench column (exact, local,
    provider-free), while every recording call ALSO drives the fabobs
    metric SPI — so a scrape of the mounted ops server's ``/metrics``
    sees the same traffic as live ``fabric_serve_*`` series.  The SPI
    emission is the zero-when-disabled fabobs hook; nothing here blocks
    or raises on an obs failure."""

    RESERVOIR = 8192

    def __init__(self):
        self._lock = threading.Lock()
        self.requests = 0
        self.lanes = 0
        self.rejects = 0
        self.errors = 0
        self.degraded_replies = 0
        # tail-tolerance accounting (protocol rev 3): work shed because
        # its wire budget provably could not be met, and requests the
        # client abandoned via OP_CANCEL (pre-dispatch sheds vs replies
        # suppressed after the verdict was computed)
        self.deadline_shed = 0
        self.class_deadline_shed: Dict[str, int] = {}
        self.cancelled_pre = 0
        self.cancelled_post = 0
        # monotone per-bucket service-time floor: the fastest this
        # sidecar has EVER served the bucket — the evidence behind the
        # "provably cannot finish" deadline shed (no evidence = serve)
        self.min_service_s: Dict[int, float] = {}
        # newest-win sliding window: a long-lived sidecar that slows
        # down later must not keep reporting startup-era p50/p99
        self._latency_s: collections.deque = collections.deque(
            maxlen=self.RESERVOIR
        )
        self.per_bucket: Dict[int, int] = {}
        # per-class request/lane/shed accounting (protocol rev 2): the
        # qos_storm scorecard proves priority-aware shedding off these
        # numbers, and every shed here was an explicit ST_BUSY reply
        self.class_served: Dict[str, int] = {}
        self.class_lanes: Dict[str, int] = {}
        self.class_busy: Dict[str, int] = {}
        # per-class latency windows back the per-class p99 the fleet
        # bench reports (same newest-win discipline as the global one)
        self._class_latency_s: Dict[str, collections.deque] = {}

    def record(
        self, lanes: int, bucket: int, seconds: float,
        qos_class: int = proto.DEFAULT_QOS,
    ) -> None:
        cls = proto.qos_name(qos_class)
        with self._lock:
            self.requests += 1
            self.lanes += lanes
            self.per_bucket[bucket] = self.per_bucket.get(bucket, 0) + 1
            self._latency_s.append(seconds)
            prior = self.min_service_s.get(bucket)
            if prior is None or seconds < prior:
                self.min_service_s[bucket] = seconds
            self.class_served[cls] = self.class_served.get(cls, 0) + 1
            self.class_lanes[cls] = self.class_lanes.get(cls, 0) + lanes
            window = self._class_latency_s.get(cls)
            if window is None:
                window = self._class_latency_s[cls] = collections.deque(
                    maxlen=self.RESERVOIR
                )
            window.append(seconds)
        fabobs.obs_count("fabric_serve_requests_total", status="ok")
        fabobs.obs_count("fabric_serve_lanes_total", lanes)
        fabobs.obs_count("fabric_serve_class_lanes_total", lanes, cls=cls)
        fabobs.obs_count(
            "fabric_serve_bucket_requests_total", bucket=str(bucket)
        )
        fabobs.obs_observe("fabric_serve_request_seconds", seconds)

    def reject(self, qos_class: int = proto.DEFAULT_QOS) -> None:
        cls = proto.qos_name(qos_class)
        with self._lock:
            self.rejects += 1
            self.class_busy[cls] = self.class_busy.get(cls, 0) + 1
        fabobs.obs_count("fabric_serve_requests_total", status="busy")
        fabobs.obs_count("fabric_serve_class_busy_total", cls=cls)

    def error(self) -> None:
        with self._lock:
            self.errors += 1
        fabobs.obs_count("fabric_serve_requests_total", status="error")

    def stopping_reply(self) -> None:
        with self._lock:
            self.degraded_replies += 1
        fabobs.obs_count("fabric_serve_requests_total", status="stopping")

    def deadline_reject(self, qos_class: int = proto.DEFAULT_QOS) -> None:
        """An explicit ST_BUSY shed because the request's wire budget
        provably cannot be met — counted apart from admission rejects
        (the QoS ledger never saw this request, so the qos_storm
        ledger/stats cross-check stays exact), attributed per class
        like every other shed."""
        cls = proto.qos_name(qos_class)
        with self._lock:
            self.deadline_shed += 1
            self.class_deadline_shed[cls] = (
                self.class_deadline_shed.get(cls, 0) + 1
            )
        fabobs.obs_count(
            "fabric_serve_deadline_expired_total", seam="serve.server"
        )
        fabobs.obs_count(
            "fabric_serve_requests_total", status="deadline_shed"
        )

    def cancel(self, pre_dispatch: bool) -> None:
        with self._lock:
            if pre_dispatch:
                self.cancelled_pre += 1
            else:
                self.cancelled_post += 1

    def floor_s(self, bucket: int) -> Optional[float]:
        """The bucket's best-ever service time (evidence floor for the
        deadline shed), or None before the first served request."""
        with self._lock:
            return self.min_service_s.get(bucket)

    def summary(self) -> Dict:
        with self._lock:
            return {
                "requests": self.requests,
                "lanes": self.lanes,
                "rejects": self.rejects,
                "errors": self.errors,
                "degraded_replies": self.degraded_replies,
                "deadline_shed": self.deadline_shed,
                "cancelled_pre": self.cancelled_pre,
                "cancelled_post": self.cancelled_post,
                "per_bucket": {str(k): v for k, v in self.per_bucket.items()},
                "request_latency": latency_summary(list(self._latency_s)),
                "per_class": {
                    cls: {
                        "served": self.class_served.get(cls, 0),
                        "lanes": self.class_lanes.get(cls, 0),
                        "busy": self.class_busy.get(cls, 0),
                        "deadline_shed": self.class_deadline_shed.get(
                            cls, 0
                        ),
                        "latency": latency_summary(
                            list(self._class_latency_s.get(cls, ()))
                        ),
                    }
                    for cls in proto.QOS_NAMES
                    if self.class_served.get(cls, 0)
                    or self.class_busy.get(cls, 0)
                    or self.class_deadline_shed.get(cls, 0)
                },
            }


class _CancelSet:
    """Per-connection registry of OP_CANCELled request ids, shared by
    the read loop (writer) and the verify workers (consumers).  Bounded
    LRU: a cancel that arrives after its request already settled leaves
    an id nobody will ever take — the cap stops a cancel-spamming
    client from growing server memory."""

    MAX = 1024

    def __init__(self):
        self._lock = threading.Lock()
        self._ids: "collections.OrderedDict[int, None]" = (
            collections.OrderedDict()
        )

    def add(self, req_id: int) -> None:
        with self._lock:
            self._ids[req_id] = None
            self._ids.move_to_end(req_id)
            while len(self._ids) > self.MAX:
                self._ids.popitem(last=False)

    def take(self, req_id: int) -> bool:
        """True exactly once per cancelled id (the taker owns the
        suppression; a second racer sees False — no double-count)."""
        with self._lock:
            return self._ids.pop(req_id, 0) is None


def build_provider(engine: str = "auto"):
    """The sidecar's verify backend.  'host' is the SW EC ladder
    (fastec -> hostec_np -> hostec); 'device' is the accelerator
    provider; 'auto' defers to the shared bounded probe ladder
    (``bccsp.probe_provider`` — one copy of the probe/degrade policy,
    not a local fork that could drift)."""
    from fabric_tpu.crypto.bccsp import SoftwareProvider

    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r} (expected {ENGINES})")
    if engine == "auto":
        from fabric_tpu.crypto.bccsp import probe_provider

        provider = probe_provider()
        return provider, (
            "host" if isinstance(provider, SoftwareProvider) else "device"
        )
    if engine == "device":
        from fabric_tpu.crypto.tpu_provider import TPUProvider

        return TPUProvider(), "device"
    return SoftwareProvider(), "host"


class SidecarServer:
    """Resident sidecar: socket front, VerifyBatcher middle, warm
    bucketed backends behind.  Usable in-process (tests, fabchaos
    serve_flap) or as the ``python -m fabric_tpu.serve`` daemon."""

    def __init__(
        self,
        address: str,
        engine: str = "auto",
        provider=None,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        max_pending_lanes: int = 65536,
        linger_s: float = 0.002,
        warm_ladder: str = "off",
        aot_dir: Optional[str] = None,
        retry_after_base_ms: int = 25,
        ops_address: Optional[str] = None,
        qos_shares: Optional[Dict[str, float]] = None,
        drain_timeout_s: float = 5.0,
        chaos_key: Optional[int] = None,
    ):
        from fabric_tpu.parallel.batcher import VerifyBatcher

        if warm_ladder not in WARM_LADDERS:
            raise ValueError(
                f"unknown warm ladder {warm_ladder!r} (expected {WARM_LADDERS})"
            )
        self.address = address
        self.buckets = tuple(buckets)
        if provider is not None:
            self.provider, self.engine = provider, engine
        else:
            self.provider, self.engine = build_provider(engine)
        self.batcher = VerifyBatcher(
            self.provider,
            max_pending_lanes=max_pending_lanes,
            linger_s=linger_s,
        )
        self.max_pending_lanes = max_pending_lanes
        self.retry_after_base_ms = retry_after_base_ms
        # per-class admission in FRONT of the batcher's global budget:
        # the ledger's lanes are held submit -> dispatch, the SAME
        # window as the batcher's own permits (released through its
        # on_dispatch hook), so the class quotas partition exactly the
        # budget the batcher enforces and shedding is priority-aware
        self.qos = ClassLedger(max_pending_lanes, qos_shares)
        self.drain_timeout_s = drain_timeout_s
        # chaos addressing: when set, the serve.dispatch fault point is
        # keyed by this int so a plan's at= pin can fault ONE sidecar
        # of an in-process fleet (the gray-failure scenarios); None
        # keeps the PR 12 unkeyed per-site stream semantics unchanged
        self.chaos_key = chaos_key
        self._draining = False
        self._active_verifies = 0
        self._drain_cv = threading.Condition()
        self.stats = ServeStats()
        self.registry: Optional[BucketProgramRegistry] = None
        self.warm_ladder = warm_ladder
        self.aot_dir = aot_dir
        self.warm_report: Dict = {}
        self._listener: Optional[socket.socket] = None
        self._threads: List[threading.Thread] = []
        self._conns: List[socket.socket] = []
        self._conn_lock = threading.Lock()
        self._stopping = False
        self._started = False
        # optional mounted ops plane: /metrics + /healthz for THIS
        # sidecar (started in start(), torn down in stop()).  The obs
        # registry is enabled NOW, not at mount time, so warm() — which
        # runs before start() — already lands its per-bucket series on
        # the provider the ops server will scrape.
        self.ops_address = ops_address
        self.ops = None
        if ops_address:
            fabobs.ensure_enabled()

    # -- warm-up -----------------------------------------------------------
    def warm(self) -> Dict:
        """Pre-warm before accepting traffic: spin the host pools with
        one small batch, and AOT-warm the jax bucket ladder when asked.
        Returns the warm report (bench's ``configs.serve.warm``)."""
        t0 = time.perf_counter()
        report: Dict = {"engine": self.engine, "ladder": self.warm_ladder}
        report["host_warm_ms"] = round(self._warm_host() * 1000.0, 1)
        if self.warm_ladder != "off":
            fn, shapes_for = (
                demo_limb_program()
                if self.warm_ladder == "demo"
                else verify_limb_program()
            )
            self.registry = BucketProgramRegistry.for_jax_program(
                fn,
                shapes_for,
                buckets=self.buckets,
                label=f"serve-{self.warm_ladder}",
                aot_dir=self.aot_dir,
            )
            self.registry.warm()
            report["per_bucket"] = {
                str(k): v for k, v in self.registry.warm_report.items()
            }
            report["traces"] = self.registry.traces
        report["total_warm_ms"] = round((time.perf_counter() - t0) * 1000.0, 1)
        self.warm_report = report
        self._export_warm_metrics(report)
        return report

    @staticmethod
    def _export_warm_metrics(report: Dict) -> None:
        """Registry warm accounting -> per-bucket gauge series, so a
        /metrics scrape carries the same cold/cache/AOT story as the
        warm report without re-deriving it."""
        for bucket, rep in (report.get("per_bucket") or {}).items():
            fabobs.obs_gauge(
                "fabric_serve_bucket_warm_ms",
                rep.get("warm_ms", 0.0), bucket=str(bucket),
            )
            fabobs.obs_gauge(
                "fabric_serve_bucket_xla_compiles",
                rep.get("xla_compiles", 0), bucket=str(bucket),
            )
            fabobs.obs_gauge(
                "fabric_serve_bucket_cache_hits",
                rep.get("cache_hits", 0), bucket=str(bucket),
            )
            fabobs.obs_gauge(
                "fabric_serve_bucket_aot_hit",
                1.0 if rep.get("aot_hit") else 0.0, bucket=str(bucket),
            )

    def _warm_host(self) -> float:
        """One tiny batch through the provider so pool spin-up and key
        tables are paid before the first real request."""
        from fabric_tpu.crypto.bccsp import ECDSAPublicKey, ec_backend

        t0 = time.perf_counter()
        ec = ec_backend()
        kp = ec.generate_keypair()
        import hashlib as _hashlib

        from fabric_tpu.common import der as _der

        digest = _hashlib.sha256(b"serve warm lane").digest()
        r, s = ec.sign_digest(kp.priv, digest)
        sig = _der.marshal_signature(r, s)
        key = ECDSAPublicKey(*kp.pub)
        n = 8
        mask = self.batcher.verify_batch([key] * n, [sig] * n, [digest] * n)
        if list(mask) != [True] * n:
            raise RuntimeError("warm-up batch failed verification")
        return time.perf_counter() - t0

    # -- socket front ------------------------------------------------------
    def start(self) -> str:
        """Bind + accept loop; returns the bound address (TCP port
        resolved).  ``warm()`` is NOT implied — call it first so the
        READY line means 'steady state will not compile'."""
        family, target = parse_address(self.address)
        listener = socket.socket(family, socket.SOCK_STREAM)
        if family == socket.AF_UNIX:
            try:
                os.unlink(target)
            except FileNotFoundError:
                pass
        else:
            listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        listener.bind(target)
        listener.listen(64)
        if family != socket.AF_UNIX:
            host, port = listener.getsockname()[:2]
            self.address = f"{host}:{port}"
        self._listener = listener
        self._started = True
        accept = threading.Thread(
            target=self._accept_loop, name="serve-accept", daemon=True
        )
        accept.start()
        with self._conn_lock:
            self._threads.append(accept)
        if self.ops_address:
            self.mount_operations()
        logger.info("sidecar serving on %s (engine %s)", self.address, self.engine)
        return self.address

    # -- mounted operations plane ------------------------------------------
    def mount_operations(self) -> str:
        """Start the node-admin HTTP server inside the sidecar process:
        ``/metrics`` serves the fabobs data-plane series live (batcher,
        ladder rungs, serve requests, registry warm, faults, retries)
        and ``/healthz`` runs the sidecar's registered checkers.  The
        obs registry and the ops provider are the SAME PrometheusProvider
        — first enabler wins, so a process already observed (env
        FABRIC_TPU_OBS) mounts its existing provider."""
        from fabric_tpu.operations import Options as OpsOptions, System

        reg = fabobs.active()
        if reg is not None:
            system = System(
                OpsOptions(
                    listen_address=self.ops_address, provider=reg.provider
                )
            )
        else:
            system = System(OpsOptions(listen_address=self.ops_address))
            fabobs.ensure_enabled(provider=system.provider)
        self._register_health_checkers(system)
        addr = system.start()
        self.ops = system
        self.ops_address = addr
        logger.info("sidecar ops plane on %s (/metrics /healthz)", addr)
        return addr

    def _register_health_checkers(self, system) -> None:
        """The sidecar's /healthz surface (healthz checker contract:
        raise = unhealthy): batcher alive, registry warm, EC pool not in
        cooldown, listener accepting."""

        def batcher_check():
            if self._stopping:
                raise RuntimeError("sidecar is stopping")
            thread = getattr(self.batcher, "_thread", None)
            if getattr(self.batcher, "_stopped", False) or (
                thread is not None and not thread.is_alive()
            ):
                raise RuntimeError("verify batcher is stopped or dead")

        def registry_check():
            if self.warm_ladder != "off" and (
                self.registry is None or not self.registry.warmed
            ):
                raise RuntimeError(
                    f"bucket registry not warmed (ladder {self.warm_ladder})"
                )

        def pool_check():
            from fabric_tpu.crypto.bccsp import ec_pool_ready

            if not ec_pool_ready():
                raise RuntimeError(
                    "EC verify pool is in rebuild cooldown (serving inline)"
                )

        def listener_check():
            if self._listener is None or self._stopping:
                raise RuntimeError("sidecar listener is not accepting")
            if self._draining:
                # a draining sidecar flips unhealthy NOW so router
                # health probes evict it before the restart, not after
                raise RuntimeError("sidecar is draining (rolling restart)")

        system.register_checker("batcher", batcher_check)
        system.register_checker("registry", registry_check)
        system.register_checker("ec-pool", pool_check)
        system.register_checker("listener", listener_check)

    def _accept_loop(self) -> None:
        while not self._stopping:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return  # listener closed by stop()
            t = threading.Thread(
                target=self._serve_conn, args=(conn,),
                name="serve-conn", daemon=True,
            )
            fabobs.obs_count("fabric_serve_connections_total", event="open")
            with self._conn_lock:
                if self._stopping:
                    conn.close()
                    return
                self._conns.append(conn)
                # register BEFORE start: a connection that EOFs
                # instantly would otherwise run its own cleanup-remove
                # before the append, leaking a dead Thread object in
                # the resident process forever
                self._threads.append(t)
            t.start()

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            self._serve_conn_inner(conn)
        finally:
            # a resident process accumulates reconnecting clients for
            # its whole lifetime: drop this connection's bookkeeping as
            # it closes or _conns/_threads grow without bound
            try:
                conn.close()
            except OSError:
                pass
            with self._conn_lock:
                try:
                    self._conns.remove(conn)
                except ValueError:
                    pass  # stop() already claimed it
                try:
                    self._threads.remove(threading.current_thread())
                except ValueError:
                    pass
            fabobs.obs_count("fabric_serve_connections_total", event="close")

    def _serve_conn_inner(self, conn: socket.socket) -> None:
        # one writer lock per connection: verify requests settle on
        # worker threads (the read loop keeps draining frames so a
        # client's pipelined requests coalesce in the batcher instead of
        # serializing behind each other), and interleaved sendall calls
        # on a stream socket would corrupt frames
        send_lock = threading.Lock()
        workers: List[threading.Thread] = []
        cancelled = _CancelSet()
        try:
            while True:
                frame = proto.recv_frame_ex(conn)
                if frame is None:
                    return
                opcode, req_id, payload, version = frame
                if opcode == proto.OP_CANCEL:
                    # fire-and-forget by contract: NO reply frame (a
                    # response here could collide with the cancelled
                    # request's own reply in the client's demux).  The
                    # worker that owns req_id sheds pre-dispatch or
                    # suppresses its reply; a cancel for an id that
                    # already settled ages out of the bounded set.
                    cancelled.add(req_id)
                elif opcode == proto.OP_PING:
                    self._send(
                        conn, proto.OP_PING, req_id,
                        proto.encode_verify_response(proto.ST_OK, mask=[]),
                        send_lock, version=version,
                    )
                elif opcode == proto.OP_STATS:
                    self._send(
                        conn, proto.OP_STATS, req_id,
                        json.dumps(self.describe(), sort_keys=True).encode(),
                        send_lock,
                        version=version,
                    )
                elif opcode == proto.OP_SHUTDOWN:
                    self._send(
                        conn, proto.OP_SHUTDOWN, req_id,
                        proto.encode_verify_response(proto.ST_OK, mask=[]),
                        send_lock, version=version,
                    )
                    # registered on _threads like every other serve
                    # thread: stop() skips joining current_thread, so
                    # the self-stop cannot deadlock on itself
                    st = threading.Thread(
                        target=self.stop, name="serve-shutdown", daemon=True
                    )
                    with self._conn_lock:
                        self._threads.append(st)
                    st.start()
                    return
                elif opcode == proto.OP_DRAIN:
                    # rolling restart: refuse new work NOW, settle the
                    # in-flight requests with real verdicts, then stop.
                    # The OK reply goes out before the drain so the
                    # restart orchestrator is not racing its own ack.
                    self._send(
                        conn, proto.OP_DRAIN, req_id,
                        proto.encode_verify_response(proto.ST_OK, mask=[]),
                        send_lock, version=version,
                    )
                    dt = threading.Thread(
                        target=self.drain_and_stop,
                        name="serve-drain", daemon=True,
                    )
                    with self._conn_lock:
                        self._threads.append(dt)
                    dt.start()
                    return
                elif opcode == proto.OP_VERIFY:
                    # concurrency is bounded by the batcher's admission
                    # control: a request only occupies its worker past
                    # decode if try_submit admitted its lanes
                    w = threading.Thread(
                        target=self._handle_verify,
                        args=(conn, req_id, payload, send_lock, version,
                              cancelled),
                        name="serve-verify", daemon=True,
                    )
                    w.start()
                    workers.append(w)
                    workers = [t for t in workers if t.is_alive()]
                else:
                    self._send(
                        conn, opcode, req_id,
                        proto.encode_verify_response(
                            proto.ST_ERROR,
                            message=f"unknown opcode {opcode}",
                        ),
                        send_lock, version=version,
                    )
        except proto.ProtocolError as exc:
            # a desynced STREAM is unusable (bad magic/oversized frame —
            # recv_frame cannot resync): answer if possible, close.
            # Payload-level decode failures never reach here; they are
            # answered ST_ERROR per request in _handle_verify.
            logger.warning("protocol error on %s: %s", self.address, exc)
            self._try_reply_error(conn, 0, exc, send_lock)
        except OSError:
            pass  # peer went away; nothing to answer
        finally:
            for w in workers:
                w.join(timeout=2.0)
            try:
                conn.close()
            except OSError:
                pass

    # -- the verify path ---------------------------------------------------
    def _handle_verify(
        self, conn, req_id: int, payload: bytes, send_lock=None,
        version: int = 1, cancelled: Optional[_CancelSet] = None,
    ) -> None:
        """Decode, class-admit, admit, launch, reply (on a per-request
        worker thread; replies may interleave out of order — the client
        demuxes by request id).  Every failure path answers the client
        with a non-OK status (the client's degrade path owns the mask
        then) — this function must never reply OK with verdicts it did
        not compute, and every shed is an explicit ST_BUSY frame (a
        cancelled request excepted: its client explicitly abandoned the
        reply, which is the one sanctioned silence)."""
        t0 = time.perf_counter()
        qos_class = proto.DEFAULT_QOS
        release_qos: Optional[Callable[[], None]] = None
        entered = False
        try:
            # chaos seam: an injected dispatch fault fails THIS request
            # with ST_ERROR before any batcher state is touched (keyed
            # only when the operator addressed this sidecar explicitly)
            fault_point("serve.dispatch", key=self.chaos_key)
            with fabobs.span("serve.decode", req_id=req_id):
                (keys, sigs, digests, qos_class, channel,
                 deadline_ms) = self._decode_lanes(payload, version)
            if self._stopping or self._draining:
                # draining: NEW work is refused here while in-flight
                # requests (already past this gate) settle with their
                # real verdicts below — the rolling-restart contract
                self.stats.stopping_reply()
                self._reply_status(
                    conn, req_id, proto.ST_STOPPING, send_lock=send_lock,
                    version=version,
                )
                return
            entered = self._enter_verify()
            if not entered:
                self.stats.stopping_reply()
                self._reply_status(
                    conn, req_id, proto.ST_STOPPING, send_lock=send_lock,
                    version=version,
                )
                return
            if cancelled is not None and cancelled.take(req_id):
                # the client abandoned this request before any batcher
                # state was touched: shed uncomputed, nothing to reply
                # (the one silence the protocol sanctions), no lanes to
                # release — the QoS ledger never saw the request
                self.stats.cancel(pre_dispatch=True)
                return
            if deadline_ms > 0:
                bucket_est = (
                    self.registry.bucket_for(len(keys))
                    if self.registry is not None else len(keys)
                )
                floor = self.stats.floor_s(bucket_est)
                if floor is not None and deadline_ms / 1000.0 < floor:
                    # the budget is smaller than the FASTEST this
                    # sidecar has ever served the bucket: provably
                    # unfinishable — shed as an explicit ST_BUSY so the
                    # client fails over/degrades NOW instead of paying
                    # the full service time for a verdict it will drop
                    self.stats.deadline_reject(qos_class)
                    self._reply_status(
                        conn, req_id, proto.ST_BUSY,
                        retry_after_ms=self.retry_after_ms(qos_class),
                        send_lock=send_lock, version=version,
                    )
                    return
            if not self.qos.try_acquire(qos_class, len(keys)):
                self.stats.reject(qos_class)
                self._reply_status(
                    conn, req_id, proto.ST_BUSY,
                    retry_after_ms=self.retry_after_ms(qos_class),
                    send_lock=send_lock, version=version,
                )
                return
            # the ledger mirrors the batcher's admission window exactly:
            # class lanes release when the dispatcher picks the request
            # up (on_dispatch), the same moment the batcher's own lane
            # permits release — one-shot so the failure-path release in
            # the finally block can never double-free
            release_qos = self._qos_release_once(qos_class, len(keys))
            # made here and entered once admitted: the batcher's own spans
            # for this request link to it across the dispatcher thread
            verify_span = fabobs.span(
                "serve.verify", req_id=req_id, lanes=len(keys),
                cls=proto.qos_name(qos_class), channel=channel,
            )
            resolver = self.batcher.try_submit(
                keys, sigs, digests, on_dispatch=release_qos,
                deadline_s=(
                    time.monotonic() + deadline_ms / 1000.0
                    if deadline_ms > 0 else None
                ),
                parent=verify_span,
            )
            if resolver is None:
                self.stats.reject(qos_class)
                self._reply_status(
                    conn, req_id, proto.ST_BUSY,
                    retry_after_ms=self.retry_after_ms(qos_class),
                    send_lock=send_lock, version=version,
                )
                return
            with verify_span:
                mask = resolver()
            if self._stopping:
                # the batcher may have settled this request fail-closed
                # during shutdown; an OK here could carry guessed
                # verdicts — tell the client to re-verify in-process
                self.stats.stopping_reply()
                self._reply_status(
                    conn, req_id, proto.ST_STOPPING, send_lock=send_lock,
                    version=version,
                )
                return
            if cancelled is not None and cancelled.take(req_id):
                # a cancel lost the race to the settlement: the verdict
                # was computed but the client stopped listening —
                # suppress the reply (the client's demux would drop it
                # anyway) and account the wasted work.  QoS lanes were
                # already released at dispatcher pickup; the one-shot
                # release makes the finally-block release a no-op, so a
                # cancel racing a settle can neither leak nor
                # double-release lanes.
                self.stats.cancel(pre_dispatch=False)
                return
            bucket = (
                self.registry.bucket_for(len(mask))
                if self.registry is not None
                else len(mask)
            )
            # record BEFORE the reply frame: any client that has seen
            # the OK must also see it in STATS (the chaos scorecard's
            # served_after_restart reads stats right after a reply —
            # recording after send made that a same-seed determinism
            # race).  The local-socket send itself is excluded from the
            # latency sample; it is microseconds against lane math.
            self.stats.record(
                len(mask), bucket, time.perf_counter() - t0, qos_class
            )
            with fabobs.span("serve.reply", req_id=req_id):
                self._send(
                    conn, proto.OP_VERIFY, req_id,
                    proto.encode_verify_response(proto.ST_OK, mask=mask),
                    send_lock, version=version,
                )
        except Exception as exc:  # noqa: BLE001 - per-request fail-closed
            # includes a payload-level ProtocolError: recv_frame already
            # consumed the whole length-prefixed frame, so the stream is
            # still in sync — a malformed payload fails THIS request
            # with ST_ERROR, never the connection's other requests
            logger.warning("verify request failed (%s); replying ST_ERROR", exc)
            self.stats.error()
            self._try_reply_error(conn, req_id, exc, send_lock, version)
        finally:
            if release_qos is not None:
                # covers every path where the dispatcher never fired
                # the hook (batcher reject, exception); idempotent
                release_qos()
            if entered:
                self._exit_verify()

    def _qos_release_once(
        self, qos_class: int, lanes: int
    ) -> Callable[[], None]:
        """One-shot ledger release shared by the dispatch hook and the
        handler's failure paths (whichever fires first wins)."""
        state = {"done": False}
        state_lock = threading.Lock()

        def release() -> None:
            with state_lock:
                if state["done"]:
                    return
                state["done"] = True
            self.qos.release(qos_class, lanes)

        return release

    def _enter_verify(self) -> bool:
        """Count this worker into the drain barrier; False when the
        sidecar began draining while the worker was being scheduled."""
        with self._drain_cv:
            if self._draining or self._stopping:
                return False
            self._active_verifies += 1
            return True

    def _exit_verify(self) -> None:
        with self._drain_cv:
            self._active_verifies -= 1
            if self._active_verifies <= 0:
                self._drain_cv.notify_all()

    def _decode_lanes(self, payload: bytes, version: int = 1):
        """Wire lanes -> provider lanes.  A key that fails SEC1 import
        becomes None — the EC ladder verifies such lanes False, exactly
        like the in-process parse path (fail-closed, never an error that
        would take down the batch's good lanes)."""
        from fabric_tpu.common import p256
        from fabric_tpu.crypto.bccsp import ECDSAPublicKey

        (key_bytes, lanes, qos_class, channel,
         deadline_ms) = proto.decode_verify_request(payload, version)
        key_objs: List[Optional[ECDSAPublicKey]] = []
        for raw in key_bytes:
            try:
                x, y = p256.pubkey_from_bytes(raw)
                key_objs.append(ECDSAPublicKey(x, y))
            except Exception as exc:  # noqa: BLE001 - bad key: dead lane below
                logger.debug("unusable key in verify request (%s)", exc)
                key_objs.append(None)
        keys = [
            key_objs[idx] if idx != proto.NO_KEY else None
            for idx, _, _ in lanes
        ]
        sigs = [sig for _, sig, _ in lanes]
        digests = [d for _, _, d in lanes]
        return keys, sigs, digests, qos_class, channel, deadline_ms

    def retry_after_ms(self, qos_class: Optional[int] = None) -> int:
        """Admission-control hint: scale the base backoff by queue
        fill so a saturated sidecar pushes clients further away.  With
        a class, the CLASS's quota fill is the signal — a saturated
        bulk lane pushes bulk clients away without inflating the hint
        a high-priority client sees for its own idle quota."""
        fill = self.batcher.pending_lanes / max(self.max_pending_lanes, 1)
        if qos_class is not None:
            fill = max(fill, self.qos.fill(qos_class))
        return max(5, int(self.retry_after_base_ms * (1.0 + 3.0 * fill)))

    @staticmethod
    def _send(
        conn, opcode: int, req_id: int, payload: bytes, send_lock=None,
        version: int = proto.PROTOCOL_VERSION,
    ):
        """One frame out, serialized under the connection's writer lock
        when given (worker threads reply concurrently; interleaved
        sendall calls would corrupt the stream).  Replies echo the
        REQUEST frame's version so a v1 client never sees a v2 header
        its recv loop would refuse."""
        if send_lock is not None:
            with send_lock:
                proto.send_frame(sock=conn, opcode=opcode, req_id=req_id,
                                 payload=payload, version=version)
        else:
            proto.send_frame(sock=conn, opcode=opcode, req_id=req_id,
                             payload=payload, version=version)

    def _reply_status(
        self, conn, req_id: int, status: int, retry_after_ms: int = 0,
        send_lock=None, version: int = 1,
    ) -> None:
        reply = proto.encode_verify_response(
            status, message="", retry_after_ms=retry_after_ms
        )
        try:
            self._send(conn, proto.OP_VERIFY, req_id, reply, send_lock,
                       version=version)
        except OSError as exc:
            logger.warning("reply failed (%s); client will degrade", exc)

    def _try_reply_error(
        self, conn, req_id: int, exc: BaseException, send_lock=None,
        version: int = 1,
    ) -> None:
        reply = proto.encode_verify_response(
            proto.ST_ERROR, message=f"{type(exc).__name__}: {exc}"
        )
        try:
            self._send(conn, proto.OP_VERIFY, req_id, reply, send_lock,
                       version=version)
        except OSError as send_exc:
            logger.warning(
                "error reply failed (%s) after %s; client will degrade",
                send_exc, exc,
            )

    # -- introspection -----------------------------------------------------
    def describe(self) -> Dict:
        out = {
            "address": self.address,
            "engine": self.engine,
            "buckets": list(self.buckets),
            "max_pending_lanes": self.max_pending_lanes,
            "pending_lanes": self.batcher.pending_lanes,
            "launches": self.batcher.launches,
            "batched_lanes": self.batcher.lanes,
            "warm": self.warm_report,
            "stats": self.stats.summary(),
            "qos": self.qos.snapshot(),
            "stopping": self._stopping,
            "draining": self._draining,
            "ops_address": self.ops_address if self.ops is not None else None,
        }
        if self.registry is not None:
            out["registry"] = self.registry.stats()
        return out

    # -- drain (rolling restart) -------------------------------------------
    def drain(self, timeout_s: Optional[float] = None) -> bool:
        """Refuse NEW verify work (``ST_STOPPING``) while in-flight
        requests settle with their real computed verdicts; returns True
        when the last in-flight request settled inside the timeout.
        Unlike stop(), the batcher stays alive, so nothing settles
        fail-closed — a drained sidecar has answered every admitted
        request with the mask it actually computed (the rolling-restart
        bit-exactness contract)."""
        if timeout_s is None:
            timeout_s = self.drain_timeout_s
        with self._drain_cv:
            self._draining = True
        logger.info("sidecar on %s draining (timeout %.1fs)",
                    self.address, timeout_s)
        deadline = time.monotonic() + max(0.0, timeout_s)
        with self._drain_cv:
            while self._active_verifies > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    logger.warning(
                        "drain timed out with %d verify worker(s) in "
                        "flight; stop() will settle them ST_STOPPING",
                        self._active_verifies,
                    )
                    return False
                self._drain_cv.wait(min(remaining, 0.2))
        return True

    def drain_and_stop(self) -> None:
        """The OP_DRAIN / SIGTERM path: settle in-flight, then exit."""
        self.drain()
        self.stop()

    # -- shutdown ----------------------------------------------------------
    def stop(self) -> None:
        """Idempotent: refuse new work, settle the batcher (fail-closed),
        close the socket front.  In-flight verify handlers observe
        ``_stopping`` and answer ST_STOPPING, never guessed verdicts."""
        with self._conn_lock:
            if self._stopping:
                return
            self._stopping = True
        if self.ops is not None:
            try:
                self.ops.stop()
            except Exception as exc:  # noqa: BLE001 - ops teardown best-effort
                logger.warning("ops server stop failed (%s)", exc)
        if self._listener is not None:
            # close() alone does NOT wake a thread blocked in accept()
            # (the syscall keeps blocking on the detached fd — every
            # stop used to eat the full 2s join timeout on the accept
            # thread, ~25s across the serve test suite): shutdown the
            # listener first, then poke it with a throwaway connect so
            # the accept loop observes the stop NOW on platforms where
            # shutdown on a listening socket is a no-op
            try:
                self._listener.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                family, target = parse_address(self.address)
                poke = socket.socket(family, socket.SOCK_STREAM)
                poke.settimeout(0.2)
                try:
                    poke.connect(target)
                except OSError:
                    pass
                finally:
                    poke.close()
            except OSError:
                pass
            try:
                self._listener.close()
            except OSError:
                pass
        self.batcher.stop()
        with self._conn_lock:
            conns = list(self._conns)
            self._conns.clear()
        for conn in conns:
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass
        with self._conn_lock:
            threads = list(self._threads)
        for t in threads:
            if t is not threading.current_thread():
                try:
                    t.join(timeout=2.0)
                except RuntimeError:
                    pass  # registered but not yet started (append-before-start window)
        family, target = parse_address(self.address)
        if family == socket.AF_UNIX and self._started:
            try:
                os.unlink(target)
            except OSError:
                pass
        logger.info("sidecar on %s stopped", self.address)


# ---------------------------------------------------------------------------
# CLI entrypoint: python -m fabric_tpu.serve
# ---------------------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    import signal

    ap = argparse.ArgumentParser(
        prog="fabric_tpu.serve",
        description="resident validation sidecar: warm bucketed "
        "executables + admission-controlled batch verify serving",
    )
    ap.add_argument(
        "--address",
        default=os.environ.get("FABRIC_TPU_SERVE_ADDR", "/tmp/fabserve.sock"),
        help="unix socket path (contains '/') or host:port",
    )
    ap.add_argument("--engine", default="auto", choices=ENGINES)
    ap.add_argument(
        "--buckets",
        default="",
        help="comma-separated lane bucket ladder (default: "
        + ",".join(str(b) for b in DEFAULT_BUCKETS) + ")",
    )
    ap.add_argument(
        "--warm", default="off", choices=WARM_LADDERS,
        help="jax bucket ladder to pre-warm: 'verify' = the real ECDSA "
        "limb kernel (minutes cold), 'demo' = the CI-able ops.bignum "
        "exponentiation ladder, 'off' = host warm-up only",
    )
    ap.add_argument(
        "--aot-dir", default="",
        help="directory for serialized AOT executables (warm restarts "
        "skip trace AND compile); empty = persistent compile cache only",
    )
    ap.add_argument("--max-pending-lanes", type=int, default=65536)
    ap.add_argument("--linger-ms", type=float, default=2.0)
    ap.add_argument(
        "--qos-shares", default="",
        help="per-class admission lane shares, e.g. "
        "'high=0.5,normal=0.35,bulk=0.15' (empty = defaults)",
    )
    ap.add_argument(
        "--drain-timeout-s", type=float, default=None,
        help="rolling-restart drain budget: how long SIGTERM/OP_DRAIN "
        "waits for in-flight requests to settle with real verdicts "
        "(default: FABRIC_TPU_SERVE_DRAIN_S or 5)",
    )
    ap.add_argument(
        "--ops-address", default=os.environ.get("FABRIC_TPU_OPS_ADDR", ""),
        help="mount the operations HTTP server (/metrics /healthz) on "
        "host:port ('127.0.0.1:0' = loopback ephemeral); empty = off",
    )
    args = ap.parse_args(argv)

    buckets = (
        tuple(int(b) for b in args.buckets.split(",") if b.strip())
        if args.buckets
        else DEFAULT_BUCKETS
    )
    from fabric_tpu.serve.qos import parse_shares

    qos_shares = parse_shares(args.qos_shares) if args.qos_shares else None
    drain_timeout_s = args.drain_timeout_s
    if drain_timeout_s is None:
        # shared env read discipline: a malformed value degrades the
        # knob to its default, never breaks the sidecar start
        raw = os.environ.get("FABRIC_TPU_SERVE_DRAIN_S", "")
        try:
            drain_timeout_s = float(raw) if raw else 5.0
        except ValueError:
            drain_timeout_s = 5.0
    server = SidecarServer(
        args.address,
        engine=args.engine,
        buckets=buckets,
        max_pending_lanes=args.max_pending_lanes,
        linger_s=args.linger_ms / 1000.0,
        warm_ladder=args.warm,
        aot_dir=args.aot_dir or None,
        ops_address=args.ops_address or None,
        qos_shares=qos_shares,
        drain_timeout_s=drain_timeout_s,
    )
    warm = server.warm()
    addr = server.start()
    # the READY line is the contract with scripts/serve_gate.sh,
    # scripts/obs_gate.sh (reads ops_address) and the warm-restart
    # test: one JSON line, stdout, after warm-up completes
    print(
        "SERVE_READY " + json.dumps(
            {
                "address": addr,
                "ops_address": server.ops_address
                if server.ops is not None else None,
                "warm": warm,
            },
            sort_keys=True,
        ),
        flush=True,
    )

    done = threading.Event()

    def _stop(signum, frame):  # noqa: ARG001 - signal signature
        done.set()

    signal.signal(signal.SIGTERM, _stop)
    signal.signal(signal.SIGINT, _stop)
    try:
        while not done.is_set() and not server._stopping:
            done.wait(0.2)
    finally:
        if not server._stopping:
            # SIGTERM/SIGINT: drain first — in-flight requests settle
            # with real verdicts before the socket front goes away, so
            # a rolling restart under load never converts a computed
            # mask into a fail-closed settlement
            server.drain()
        server.stop()
        print(
            "SERVE_EXIT " + json.dumps(server.stats.summary(), sort_keys=True),
            flush=True,
        )
    return 0
