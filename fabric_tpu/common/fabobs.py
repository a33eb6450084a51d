"""fabobs — process-wide observability registry for the validation data
plane.

The runtime carries named *obs points* at its hot seams — the same
discipline as :mod:`fabric_tpu.common.faults`: one module-global load
and a ``None`` check when observability is disabled, so production code
pays nothing until an operator turns the registry on.  Enabled, every
hook drives two layers at once:

1. **Metrics** — the Fabric-faithful :mod:`fabric_tpu.common.metrics`
   ``Provider`` SPI.  Families come from one canonical table
   (:data:`CANONICAL_METRICS`): family name, kind, labels, and the seam
   that emits it.  Enabling the registry eagerly registers every family,
   so a ``/metrics`` scrape always shows the full canonical surface
   (``# TYPE`` lines) even before traffic arrives.
2. **Spans + flight recorder** — ``span(name)`` context managers with
   monotonic clocks, thread-propagated parent links (a
   ``threading.local`` stack; cross-thread hand-offs pass an explicit
   ``parent=``), all landing in a bounded ring buffer.  A child takes
   its parent's ``block`` / ``req_id`` / ``req_ids`` unless it names its
   own, so every span of one block or one sidecar request carries the
   same identifier.  ``record_span(name, t0, t1)`` writes a span whose
   start lies in the past or on another thread (a queue wait, clock
   reads a function already takes).  ``dump()`` renders the ring as
   Chrome trace-event JSON (``chrome://tracing`` / Perfetto);
   :func:`obs_trigger` snapshots it to disk automatically on
   degrade/fail-closed events so the moments worth debugging are the
   moments that self-record.

   **One clock with the device trace**: in a process that has imported
   JAX, an executed span also enters and exits a
   ``jax.profiler.TraceAnnotation`` of its own name (attributes stay in
   the ring), so a profiler session sees the program's spans on the
   trace's host lines.  fabobs imports no JAX: it finds the module in
   ``sys.modules`` or does nothing.

Mask safety contract (this file rides the fabflow MASK tier): no
function here produces or transforms a verdict mask, and every enabled
path is wrapped so an observability failure is swallowed with a debug
log — instrumentation can slow a verify path down, never alter it or
fail it.  The hooks are therefore safe to call from inside mask-critical
code without try/except at the call site.

Enable programmatically (tests use the scoped form)::

    from fabric_tpu.common import fabobs
    reg = fabobs.enable()                     # fresh PrometheusProvider
    with fabobs.obs_installed() as reg: ...   # scoped; restores previous

or from the environment (same warn-never-raise discipline as
``FABRIC_TPU_FAULTS``)::

    FABRIC_TPU_OBS=1                 # enable (prometheus provider)
    FABRIC_TPU_OBS_RING=8192         # flight-recorder ring size
    FABRIC_TPU_OBS_DUMP_DIR=/tmp/ft  # auto-dump traces on obs_trigger
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
import types
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from fabric_tpu.common import metrics as metrics_mod
from fabric_tpu.common.flogging import must_get_logger

logger = must_get_logger("fabobs")

# latency histograms: the shared prometheus-style seconds ladder
LATENCY_BUCKETS = metrics_mod.DEFAULT_BUCKETS
# lane-count histograms (batch sizes): powers of four up to the
# max_pending_lanes default, so bucket edges track the bucket ladder
LANE_BUCKETS = (1.0, 8.0, 64.0, 256.0, 1024.0, 4096.0, 16384.0, 65536.0)
# pipeline-stage latency: the default ladder extended downward — warm
# host-ladder prepare sits in the sub-millisecond range the 5ms lowest
# default bucket would flatten.
STAGE_BUCKETS = (0.0005, 0.001, 0.0025) + LATENCY_BUCKETS


@dataclass(frozen=True)
class MetricSpec:
    """One canonical family: the README metric-name table is generated
    from these entries, and the obs_gate asserts every one appears on a
    live ``/metrics`` scrape."""

    name: str
    kind: str  # counter | gauge | histogram
    labels: Tuple[str, ...]
    help: str
    seam: str
    buckets: Tuple[float, ...] = ()


#: The canonical metric-name table.  Adding an obs hook to a new seam
#: means adding its family here FIRST — an unknown family is swallowed
#: (debug log + dropped counter), never implicitly registered.
CANONICAL_METRICS: Tuple[MetricSpec, ...] = (
    # -- VerifyBatcher (parallel/batcher.py) ---------------------------
    MetricSpec(
        "fabric_batcher_pending_lanes", "gauge", (),
        "lanes admitted but not yet dispatched (admission-control fill)",
        "parallel/batcher.py _admit/_run",
    ),
    MetricSpec(
        "fabric_batcher_batch_lanes", "histogram", (),
        "coalesced lanes per device/provider launch",
        "parallel/batcher.py _run", LANE_BUCKETS,
    ),
    MetricSpec(
        "fabric_batcher_submit_wait_seconds", "histogram", (),
        "submit -> settle latency per request",
        "parallel/batcher.py _settle", LATENCY_BUCKETS,
    ),
    MetricSpec(
        "fabric_batcher_launches_total", "counter", ("mode",),
        "provider launches by transport mode (coalesce|passthrough)",
        "parallel/batcher.py _run",
    ),
    MetricSpec(
        "fabric_batcher_launches_by_bucket_total", "counter", ("bucket",),
        "provider launches by the lane width the provider padded them to",
        "parallel/batcher.py _run",
    ),
    MetricSpec(
        "fabric_batcher_busy_rejects_total", "counter", (),
        "try_submit admissions rejected (ST_BUSY backpressure)",
        "parallel/batcher.py _admit",
    ),
    MetricSpec(
        "fabric_batcher_dispatch_retries_total", "counter", (),
        "transient launch failures retried by the dispatch policy",
        "parallel/batcher.py _launch",
    ),
    MetricSpec(
        "fabric_batcher_fail_closed_total", "counter", (),
        "requests settled all-False by a stopping/hung batcher",
        "parallel/batcher.py stop",
    ),
    # -- backend ladder rungs (crypto/, serve/client.py) ---------------
    MetricSpec(
        "fabric_verify_lanes_total", "counter", ("rung",),
        "ECDSA signature lanes verified per ladder rung "
        "(fastec|hostec_np|hostec|p256|device|serve)",
        "crypto/bccsp.py, crypto/tpu_provider.py, serve/client.py",
    ),
    MetricSpec(
        "fabric_idemix_lanes_total", "counter", ("rung",),
        "Idemix signatures verified per rung (device|hostbn|scheme): on "
        "the commit path one lane is a signer's association proof and "
        "its nym signature",
        "idemix/batch.py, crypto/tpu_provider.py",
    ),
    MetricSpec(
        "fabric_mc_steps_total", "counter", ("program",),
        "MultiChannelValidator steps by the sharded program that took "
        "them: bytes (unpack and key gather on the devices) | limbs (a "
        "channel's block had more distinct keys than KEY_BUCKET)",
        "parallel/multichannel.py validate",
    ),
    MetricSpec(
        "fabric_verify_seconds", "histogram", ("rung",),
        "batch verify time per ladder rung (device: the provider's own "
        "host prep + dispatch + resolve, not the time a resolver waited "
        "to be called; other rungs: wall time)",
        "crypto/bccsp.py, crypto/tpu_provider.py, serve/client.py",
        LATENCY_BUCKETS,
    ),
    MetricSpec(
        "fabric_degrade_total", "counter", ("seam",),
        "degrade transitions (sidecar->in-process, pool->inline, "
        "device->software)",
        "serve/client.py, crypto/hostec*.py, crypto/tpu_provider.py",
    ),
    MetricSpec(
        "fabric_pool_rebuilds_total", "counter", ("pool",),
        "process-pool constructions (hostec|hostec_np)",
        "crypto/hostec.py, crypto/hostec_np.py",
    ),
    MetricSpec(
        "fabric_pool_cooldowns_total", "counter", ("pool",),
        "broken-pool teardowns arming the rebuild cooldown",
        "crypto/hostec.py, crypto/hostec_np.py",
    ),
    # -- serve sidecar (serve/server.py) -------------------------------
    MetricSpec(
        "fabric_serve_requests_total", "counter", ("status",),
        "verify requests by reply status (ok|busy|error|stopping)",
        "serve/server.py ServeStats",
    ),
    MetricSpec(
        "fabric_serve_lanes_total", "counter", (),
        "lanes served OK by the sidecar",
        "serve/server.py ServeStats",
    ),
    MetricSpec(
        "fabric_serve_request_seconds", "histogram", (),
        "decode -> reply latency of served verify requests",
        "serve/server.py ServeStats", LATENCY_BUCKETS,
    ),
    MetricSpec(
        "fabric_serve_bucket_requests_total", "counter", ("bucket",),
        "served requests per registry lane bucket",
        "serve/server.py ServeStats",
    ),
    MetricSpec(
        "fabric_serve_connections_total", "counter", ("event",),
        "client connection churn (open|close)",
        "serve/server.py _accept_loop/_serve_conn",
    ),
    MetricSpec(
        "fabric_serve_class_lanes_total", "counter", ("cls",),
        "lanes served OK per admission class (high|normal|bulk)",
        "serve/server.py ServeStats",
    ),
    MetricSpec(
        "fabric_serve_class_busy_total", "counter", ("cls",),
        "ST_BUSY sheds per admission class — every rejection is a "
        "protocol-level reply, never a silent drop",
        "serve/server.py ServeStats",
    ),
    MetricSpec(
        "fabric_serve_endpoint_healthy", "gauge", ("endpoint",),
        "router endpoint health (1 = in rotation, 0 = evicted/cooling)",
        "serve/router.py _Endpoint",
    ),
    # -- tail tolerance (fabtail: serve/router.py, serve/server.py,
    #    serve/client.py) ----------------------------------------------
    MetricSpec(
        "fabric_serve_hedges_total", "counter", (),
        "hedged requests fired at a second endpoint after the primary "
        "stayed silent past its learned hedge delay",
        "serve/router.py _await_hedged",
    ),
    MetricSpec(
        "fabric_serve_hedge_wins_total", "counter", (),
        "hedges whose verdict arrived before the primary's (the loser "
        "is cancelled best-effort via OP_CANCEL)",
        "serve/router.py _await_hedged",
    ),
    MetricSpec(
        "fabric_serve_deadline_expired_total", "counter", ("seam",),
        "wire-deadline budgets that ran out (serve.server = provably-"
        "unfinishable work shed ST_BUSY; serve.client / serve.router = "
        "batches handed back to the in-process ladder)",
        "serve/server.py ServeStats, serve/client.py, serve/router.py",
    ),
    MetricSpec(
        "fabric_serve_slow_evictions_total", "counter", ("endpoint",),
        "gray-failure evictions: endpoints alive but latency outliers "
        "(EWMA far above the fleet best, or consecutive lost hedges) "
        "pulled from rotation through the cooldown ladder",
        "serve/router.py _evict_slow",
    ),
    MetricSpec(
        "fabric_serve_bucket_warm_ms", "gauge", ("bucket",),
        "per-bucket warm wall ms (registry warm report)",
        "serve/server.py warm",
    ),
    MetricSpec(
        "fabric_serve_bucket_xla_compiles", "gauge", ("bucket",),
        "XLA compiles the bucket warm paid (0 = AOT/cache)",
        "serve/server.py warm",
    ),
    MetricSpec(
        "fabric_serve_bucket_cache_hits", "gauge", ("bucket",),
        "persistent compile-cache hits during the bucket warm",
        "serve/server.py warm",
    ),
    MetricSpec(
        "fabric_serve_bucket_aot_hit", "gauge", ("bucket",),
        "1 when the bucket loaded its serialized AOT artifact",
        "serve/server.py warm",
    ),
    # -- commit pipeline (peer/pipeline.py) ----------------------------
    MetricSpec(
        "fabric_pipeline_stage_seconds", "histogram", ("stage",),
        "per-stage latency (prepare|commit) of the two-stage pipeline",
        "peer/pipeline.py", STAGE_BUCKETS,
    ),
    MetricSpec(
        "fabric_pipeline_commit_failures_total", "counter", (),
        "commit-stage exceptions surfaced to the owner",
        "peer/pipeline.py _commit_loop",
    ),
    # -- shared retry/backoff (common/retry.py) ------------------------
    MetricSpec(
        "fabric_retry_attempts_total", "counter", (),
        "backoff sleeps taken across every retry loop",
        "common/retry.py Backoff.sleep",
    ),
    MetricSpec(
        "fabric_retry_backoff_seconds", "histogram", (),
        "nominal delay per backoff sleep",
        "common/retry.py Backoff.sleep", LATENCY_BUCKETS,
    ),
    # -- fault injection (common/faults.py) ----------------------------
    MetricSpec(
        "fabric_fault_fired_total", "counter", ("site",),
        "injected faults that actually fired, per site",
        "common/faults.py fault_point",
    ),
    # -- crash-consistent commit plane (fabcrash, ledger/) -------------
    MetricSpec(
        "fabric_ledger_recovered_blocks_total", "counter", (),
        "blocks replayed into state/pvt by restart recovery (the gap "
        "between the block store and the state savepoint)",
        "ledger/kvledger.py _recover",
    ),
    MetricSpec(
        "fabric_state_reads_total", "counter", ("how",),
        "committed-state keys the commit path read, by how: preloaded "
        "(the block's one bulk read) | point (a key the preload lacked, "
        "or a metadata-only write's value)",
        "ledger/statedb.py BlockPreload.account",
    ),
    MetricSpec(
        "fabric_ledger_torn_tail_total", "counter", ("store",),
        "torn tail records truncated on recovery (chain|pvtdata)",
        "ledger/blockstore.py _rebuild_index, ledger/pvtdatastore.py "
        "_recover",
    ),
    MetricSpec(
        "fabric_ledger_recovery_refusals_total", "counter", ("reason",),
        "recoveries refused fail-closed (corrupt-chain|corrupt-pvtdata|"
        "statedb-ahead): inconsistency recovery cannot repair forward",
        "ledger/blockstore.py _refuse, ledger/pvtdatastore.py _refuse, "
        "ledger/kvledger.py _recover",
    ),
    MetricSpec(
        "fabric_mvcc_table_invalidations_total", "counter", (),
        "resident MVCC version tables dropped because the state db "
        "generation moved behind their back (stale reads fail closed)",
        "ledger/mvcc_device.py ResidentDeviceValidator",
    ),
)

CANONICAL_BY_NAME: Dict[str, MetricSpec] = {
    s.name: s for s in CANONICAL_METRICS
}


# ---------------------------------------------------------------------------
# Span / flight-recorder layer
# ---------------------------------------------------------------------------

_tls = threading.local()


def _span_stack() -> List["Span"]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def current_span() -> Optional["Span"]:
    """The innermost open span on THIS thread (cross-thread hand-offs
    pass it as ``span(..., parent=...)`` explicitly)."""
    stack = _span_stack()
    return stack[-1] if stack else None


# what a child span takes from its parent unless it names its own: the
# identifier that ties every span of one block, or of one sidecar request
# (``req_ids``: of one coalesced launch), together
_INHERITED_ATTRS = ("block", "req_id", "req_ids")


def _adopt(attrs: Dict, explicit: Optional["Span"]) -> int:
    """Find a new span's parent (the one handed over, unless it is absent
    or the no-op span; else the thread's open span), copy the inherited
    identifiers into ``attrs`` and return the parent's id (0: a root)."""
    parent = explicit
    if parent is None or not parent.span_id:
        parent = current_span()
    if parent is None:
        return 0
    for key in _INHERITED_ATTRS:
        if key not in attrs and key in parent.attrs:
            attrs[key] = parent.attrs[key]
    return parent.span_id


def _enter_annotation(name: str):
    """The span on the profiler's clock: an entered
    ``jax.profiler.TraceAnnotation(name)``, or None in a process that
    never imported JAX (it has no device trace to align with)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    annotation = jax.profiler.TraceAnnotation(name)
    annotation.__enter__()
    return annotation


class Span:
    """One timed section.  Entering pushes it on the thread's span
    stack; exiting records a Chrome ``ph:"X"`` complete event into the
    registry's flight ring.  Failures inside the obs machinery are
    swallowed (``_swallow``); exceptions from the *wrapped* code
    propagate untouched — a span can never eat a verify error."""

    __slots__ = (
        "name", "attrs", "span_id", "parent_id", "_reg", "_t0", "_parent",
        "_annotation",
    )

    def __init__(self, reg: "ObsRegistry", name: str, attrs: Dict,
                 parent: Optional["Span"] = None):
        self._reg = reg
        self.name = name
        self.attrs = attrs
        self.span_id = next(reg._span_ids)
        self.parent_id = 0
        self._parent = parent
        self._t0 = 0.0
        self._annotation = None

    def set(self, **attrs) -> None:
        """Attributes the wrapped code learns late (an id the section
        itself allocates).  They land in the ring record even when the
        span has already exited: the record keeps this very dict."""
        try:
            self.attrs.update(attrs)
        except Exception as exc:  # noqa: BLE001 - obs must never raise
            self._reg._swallow("span.set", exc)

    def __enter__(self) -> "Span":
        try:
            self.parent_id = _adopt(self.attrs, self._parent)
            _span_stack().append(self)
        except Exception as exc:  # noqa: BLE001 - obs must never raise
            self._reg._swallow("span.enter", exc)
        try:
            self._annotation = _enter_annotation(self.name)
        except Exception as exc:  # noqa: BLE001 - obs must never raise
            self._reg._swallow("span.annotate", exc)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t1 = time.perf_counter()
        try:
            if self._annotation is not None:
                self._annotation.__exit__(exc_type, exc, tb)
        except Exception as swallow_exc:  # noqa: BLE001 - obs must never raise
            self._reg._swallow("span.annotate", swallow_exc)
        try:
            stack = _span_stack()
            if stack and stack[-1] is self:
                stack.pop()
            elif self in stack:  # tolerate mis-nested exits
                stack.remove(self)
            args = self.attrs  # not a copy: see set()
            args["span_id"] = self.span_id
            if self.parent_id:
                args["parent_id"] = self.parent_id
            if exc_type is not None:
                args["error"] = exc_type.__name__
            self._reg._record_event(
                {
                    "name": self.name,
                    "ph": "X",
                    "ts": self._reg._us(self._t0),
                    "dur": round((t1 - self._t0) * 1e6, 1),
                    "args": args,
                }
            )
        except Exception as swallow_exc:  # noqa: BLE001 - obs must never raise
            self._reg._swallow("span.exit", swallow_exc)
        # never suppress the wrapped code's exception (implicit None)


class _NoopSpan:
    """Shared do-nothing span: what ``span()`` returns when the registry
    is disabled, and what enabled hooks fall back to on internal
    failure.  Reentrant and stateless."""

    __slots__ = ()
    name = "noop"
    span_id = 0
    parent_id = 0
    attrs = types.MappingProxyType({})

    def set(self, **attrs) -> None:
        return None

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NOOP_SPAN = _NoopSpan()


# ---------------------------------------------------------------------------
# The registry
# ---------------------------------------------------------------------------


class ObsRegistry:
    """One process-wide observability hub: metric instruments for every
    canonical family plus the span flight ring.  All mutable state is
    guarded by ``_lock`` (fabdep unguarded-shared-write discipline);
    metric series carry their own per-family locks inside the SPI."""

    def __init__(
        self,
        provider: Optional[metrics_mod.Provider] = None,
        ring: int = 4096,
        dump_dir: Optional[str] = None,
        max_dumps: int = 8,
    ):
        self.provider = provider or metrics_mod.PrometheusProvider()
        self.dump_dir = dump_dir
        self.max_dumps = max_dumps
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=max(16, int(ring)))
        self._epoch = time.perf_counter()
        self._span_ids = itertools.count(1)  # next() is GIL-atomic
        self._dumps = 0
        self._dumped_paths: List[str] = []
        self.dropped = 0  # obs failures swallowed (self-accounting)
        self._warned_families: set = set()
        self._instruments: Dict[str, object] = {}
        for spec in CANONICAL_METRICS:
            try:
                self._instruments[spec.name] = self._build(spec)
            except Exception as exc:  # noqa: BLE001 - obs must never raise
                self._swallow(f"register:{spec.name}", exc)

    # -- instrument construction ----------------------------------------
    def _build(self, spec: MetricSpec):
        if spec.kind == "counter":
            return self.provider.new_counter(
                metrics_mod.CounterOpts(
                    name=spec.name, help=spec.help, label_names=spec.labels
                )
            )
        if spec.kind == "gauge":
            return self.provider.new_gauge(
                metrics_mod.GaugeOpts(
                    name=spec.name, help=spec.help, label_names=spec.labels
                )
            )
        if spec.kind == "histogram":
            return self.provider.new_histogram(
                metrics_mod.HistogramOpts(
                    name=spec.name,
                    help=spec.help,
                    label_names=spec.labels,
                    buckets=spec.buckets or LATENCY_BUCKETS,
                )
            )
        raise ValueError(f"unknown metric kind {spec.kind!r}")

    def _lookup(self, name: str, labels: Dict[str, str]):
        inst = self._instruments.get(name)
        if inst is None:
            with self._lock:
                first = name not in self._warned_families
                self._warned_families.add(name)
            if first:
                logger.debug(
                    "obs point %r is not in the canonical metric table; "
                    "dropped", name,
                )
            return None
        if labels:
            flat: List[str] = []
            for k, v in labels.items():
                flat.append(k)
                flat.append(str(v))
            inst = inst.with_labels(*flat)
        return inst

    # -- hot-path sinks (never raise) ------------------------------------
    def count(self, name: str, n: float = 1.0, **labels) -> None:
        try:
            inst = self._lookup(name, labels)
            if inst is not None:
                inst.add(n)
        except Exception as exc:  # noqa: BLE001 - obs must never raise
            self._swallow(name, exc)

    def gauge(self, name: str, value: float, **labels) -> None:
        try:
            inst = self._lookup(name, labels)
            if inst is not None:
                inst.set(value)
        except Exception as exc:  # noqa: BLE001 - obs must never raise
            self._swallow(name, exc)

    def observe(self, name: str, value: float, **labels) -> None:
        try:
            inst = self._lookup(name, labels)
            if inst is not None:
                inst.observe(value)
        except Exception as exc:  # noqa: BLE001 - obs must never raise
            self._swallow(name, exc)

    def span(self, name: str, parent: Optional[Span] = None, **attrs) -> Span:
        try:
            return Span(self, name, attrs, parent=parent)
        except Exception as exc:  # noqa: BLE001 - obs must never raise
            self._swallow(name, exc)
            return _NOOP_SPAN  # type: ignore[return-value]

    def record_span(self, name: str, t0: float, t1: float,
                    parent: Optional[Span] = None, **attrs) -> None:
        """A span nobody executed: ``t0``/``t1`` are ``time.perf_counter``
        reads the caller already holds, maybe taken on another thread (a
        queue wait, back-pressure, the phases of a function that times
        itself).  Parent and inherited attributes as for ``span()``;
        no ``TraceAnnotation`` (the profiler cannot be told of the
        past)."""
        try:
            parent_id = _adopt(attrs, parent)
            attrs["span_id"] = next(self._span_ids)
            if parent_id:
                attrs["parent_id"] = parent_id
            self._record_event(
                {
                    "name": name,
                    "ph": "X",
                    "ts": self._us(t0),
                    "dur": round((t1 - t0) * 1e6, 1),
                    "args": attrs,
                }
            )
        except Exception as exc:  # noqa: BLE001 - obs must never raise
            self._swallow(name, exc)

    def event(self, name: str, **attrs) -> None:
        """Instant flight-recorder mark (Chrome ``ph:"i"``)."""
        try:
            self._record_event(
                {
                    "name": name,
                    "ph": "i",
                    "ts": self._us(time.perf_counter()),
                    "s": "p",
                    "args": attrs,
                }
            )
        except Exception as exc:  # noqa: BLE001 - obs must never raise
            self._swallow(name, exc)

    def trigger(self, reason: str, **attrs) -> Optional[str]:
        """A degrade/fail-closed moment: record the event AND, when a
        dump dir is configured, snapshot the flight ring to disk (capped
        at ``max_dumps`` per process so a flapping seam cannot fill a
        disk).  Returns the dump path when one was written."""
        try:
            self.event(f"trigger:{reason}", **attrs)
            if not self.dump_dir:
                return None
            with self._lock:
                if self._dumps >= self.max_dumps:
                    return None
                self._dumps += 1
                seq = self._dumps
            safe = "".join(
                c if (c.isalnum() or c in "-_.") else "_" for c in reason
            )
            path = os.path.join(
                self.dump_dir, f"fabobs-{os.getpid()}-{seq:02d}-{safe}.json"
            )
            self.dump(path)
            with self._lock:
                self._dumped_paths.append(path)
            logger.warning("flight recorder dumped to %s (%s)", path, reason)
            return path
        except Exception as exc:  # noqa: BLE001 - obs must never raise
            self._swallow(f"trigger:{reason}", exc)
            return None

    # -- flight recorder --------------------------------------------------
    def _us(self, t: float) -> float:
        return round((t - self._epoch) * 1e6, 1)

    def _record_event(self, record: Dict) -> None:
        record.setdefault("pid", os.getpid())
        record.setdefault("tid", threading.get_ident())
        with self._lock:
            self._ring.append(record)

    def trace_events(self) -> List[Dict]:
        # args copied too: a span's record keeps the span's own dict, which
        # Span.set() may still write to
        with self._lock:
            return [dict(r, args=dict(r["args"])) for r in self._ring]

    def dump(self, path: Optional[str] = None) -> str:
        """Chrome trace-event JSON of the flight ring (load it in
        ``chrome://tracing`` or Perfetto).  Writes ``path`` when given,
        returns the JSON text either way."""
        payload = json.dumps(
            {
                "traceEvents": self.trace_events(),
                "displayTimeUnit": "ms",
                "otherData": {"source": "fabric_tpu.fabobs"},
            },
            sort_keys=True,
        )
        if path:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as fh:
                fh.write(payload)
            os.replace(tmp, path)
        return payload

    def dumped_paths(self) -> List[str]:
        with self._lock:
            return list(self._dumped_paths)

    # -- scrape-side views -------------------------------------------------
    def render(self) -> str:
        """Prometheus text exposition of the provider (empty string for
        non-prometheus providers — the ops server answers 404 then)."""
        gather = getattr(self.provider, "gather", None)
        return gather() if callable(gather) else ""

    def snapshot(self) -> Dict:
        """JSON-able {family: {kind, series}} snapshot.  Histogram series
        collapse to the bucket-quantized summary
        (:func:`metrics.summary_from_histogram_state`)."""
        out: Dict[str, Dict] = {}
        prov = self.provider
        if not isinstance(prov, metrics_mod.PrometheusProvider):
            return out
        with prov._lock:
            families = dict(prov._metrics)
        for name, metric in sorted(families.items()):
            with metric.lock:
                series = dict(metric.series)
            rendered: Dict[str, object] = {}
            for labels, value in sorted(series.items()):
                key = ",".join(
                    f"{n}={v}"
                    for n, v in zip(metric.opts.label_names, labels)
                ) or "_"
                if isinstance(value, metrics_mod._HistState):
                    rendered[key] = metrics_mod.summary_from_histogram_state(
                        value, metric.opts.buckets  # type: ignore[attr-defined]
                    )
                else:
                    rendered[key] = value
            if rendered:
                out[name] = {"kind": metric.kind, "series": rendered}
        return out

    def _swallow(self, where: str, exc: BaseException) -> None:
        """The one rule of this module: an observability failure is
        accounted and debug-logged, NEVER raised into the observed
        code."""
        try:
            with self._lock:
                self.dropped += 1
            logger.debug("obs failure at %s swallowed: %s", where, exc)
        except Exception:  # noqa: BLE001 - last-ditch: even the swallow must not raise into a verify path
            pass


# ---------------------------------------------------------------------------
# Process-wide installation (the faults.py discipline: _OBS is written
# only under _OBS_LOCK; the hot-path read is one GIL-atomic global load)
# ---------------------------------------------------------------------------

_OBS: Optional[ObsRegistry] = None
_OBS_LOCK = threading.Lock()


def enable(
    provider: Optional[metrics_mod.Provider] = None,
    ring: int = 4096,
    dump_dir: Optional[str] = None,
    max_dumps: int = 8,
) -> ObsRegistry:
    """Install a fresh registry process-wide and return it."""
    global _OBS
    reg = ObsRegistry(
        provider=provider, ring=ring, dump_dir=dump_dir, max_dumps=max_dumps
    )
    with _OBS_LOCK:
        _OBS = reg
    return reg


def ensure_enabled(
    provider: Optional[metrics_mod.Provider] = None, **kwargs
) -> ObsRegistry:
    """Enable unless a registry is already installed (first enabler
    wins — one process, one obs hub).  Used by the node shells so a
    peer and its ops server share the provider without trampling an
    operator's earlier installation.  The registry is built outside the
    lock (construction registers every canonical family) and installed
    only if no racer got there first — the loser's registry is
    discarded, so two concurrent enablers can never silently replace
    each other's installation."""
    global _OBS
    existing = _OBS
    if existing is None:
        candidate = ObsRegistry(provider=provider, **kwargs)
        with _OBS_LOCK:
            if _OBS is None:
                _OBS = candidate
                return candidate
            existing = _OBS
    if provider is not None and existing.provider is not provider:
        logger.warning(
            "fabobs already enabled; keeping the existing provider "
            "(a second ops surface will not see data-plane series)"
        )
    return existing


def disable() -> None:
    global _OBS
    with _OBS_LOCK:
        _OBS = None


def enabled() -> bool:
    return _OBS is not None


def active() -> Optional[ObsRegistry]:
    return _OBS


class obs_installed:
    """``with obs_installed() as reg:`` — scoped enablement for tests
    and gates; the previous registry (usually None) is restored on exit,
    mirroring ``faults.plan_installed``."""

    def __init__(self, registry: Optional[ObsRegistry] = None, **kwargs):
        self.registry = registry if registry is not None else ObsRegistry(**kwargs)
        self._prev: Optional[ObsRegistry] = None

    def __enter__(self) -> ObsRegistry:
        global _OBS
        with _OBS_LOCK:
            self._prev = _OBS
            _OBS = self.registry
        return self.registry

    def __exit__(self, *exc) -> None:
        global _OBS
        with _OBS_LOCK:
            _OBS = self._prev


# -- the hot-path hooks ------------------------------------------------------


def obs_count(name: str, n: float = 1.0, **labels) -> None:
    """Add ``n`` to a canonical counter.  Disabled cost: one global
    load and a ``None`` check."""
    reg = _OBS
    if reg is None:
        return
    reg.count(name, n, **labels)


def obs_gauge(name: str, value: float, **labels) -> None:
    reg = _OBS
    if reg is None:
        return
    reg.gauge(name, value, **labels)


def obs_observe(name: str, value: float, **labels) -> None:
    reg = _OBS
    if reg is None:
        return
    reg.observe(name, value, **labels)


def span(name: str, parent: Optional[Span] = None, **attrs):
    """Context manager timing one section into the flight ring.
    Disabled: returns the shared no-op span (no allocation)."""
    reg = _OBS
    if reg is None:
        return _NOOP_SPAN
    return reg.span(name, parent=parent, **attrs)


def obs_record_span(name: str, t0: float, t1: float,
                    parent: Optional[Span] = None, **attrs) -> None:
    """A span from two ``time.perf_counter`` reads the caller already
    holds (see :meth:`ObsRegistry.record_span`)."""
    reg = _OBS
    if reg is None:
        return
    reg.record_span(name, t0, t1, parent=parent, **attrs)


def obs_event(name: str, **attrs) -> None:
    reg = _OBS
    if reg is None:
        return
    reg.event(name, **attrs)


def obs_trigger(reason: str, **attrs) -> Optional[str]:
    """Degrade/fail-closed mark + automatic flight-recorder dump (when a
    dump dir is configured).  Call it where the system gives ground:
    sidecar degrade, pool -> inline, batcher fail-closed settlement."""
    reg = _OBS
    if reg is None:
        return None
    return reg.trigger(reason, **attrs)


def snapshot() -> Dict:
    """{} when disabled; else the active registry's metric snapshot."""
    reg = _OBS
    return {} if reg is None else reg.snapshot()


def metric_table() -> List[Dict[str, str]]:
    """The canonical table as rows (README/docs generation + gates)."""
    return [
        {
            "name": s.name,
            "kind": s.kind,
            "labels": ",".join(s.labels),
            "seam": s.seam,
            "help": s.help,
        }
        for s in CANONICAL_METRICS
    ]


def _truthy(raw: str) -> bool:
    return raw.strip().lower() not in ("", "0", "false", "off", "no")


def _install_from_env() -> None:
    """Honor FABRIC_TPU_OBS at import so external runs (bench, a node
    under soak, the obs_gate chaos re-run) can be observed without code
    changes.  Malformed values warn and install nothing — observability
    knobs must never poison a production import."""
    raw = os.environ.get("FABRIC_TPU_OBS", "")
    if not _truthy(raw):
        return
    try:
        ring = int(os.environ.get("FABRIC_TPU_OBS_RING", "4096"))
    except ValueError:
        ring = 4096
    dump_dir = os.environ.get("FABRIC_TPU_OBS_DUMP_DIR", "") or None
    try:
        ensure_enabled(ring=ring, dump_dir=dump_dir)
    except Exception as exc:  # noqa: BLE001 - env install is best-effort
        import warnings

        warnings.warn(
            f"FABRIC_TPU_OBS ignored (install failed: {exc})",
            RuntimeWarning,
            stacklevel=2,
        )


_install_from_env()
