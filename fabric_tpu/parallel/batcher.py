"""Cross-channel verify coalescing with bounded-queue backpressure
(SURVEY §2.13 P7).

The device wants few, large, fixed-shape launches; the peer produces
many small, bursty verify requests (one per block, per channel, plus
endorsement-path singles).  This batcher sits between them:

- requests enqueue onto ONE bounded queue (backpressure: submitters
  block when the device is behind — the reference achieves the same
  with its validator semaphore, core/ledger/kvledger/kv_ledger.go
  commit throttling);
- a dispatcher thread drains the queue into bucketed batches: it takes
  whatever is queued, lingers a few ms for stragglers while the bucket
  is small, then launches ONE device program for the whole batch via
  the provider's async path (overlapping host prep of the next batch
  with device execution of the current one, like the P4 pipeline); a
  batch never grows past ``max_launch_lanes`` (a sidecar's widest warm
  program), nor takes a request that would pad it wider than that
  request's own launch would be: the request leads the next launch;
- a settler thread waits on the launches in flight, oldest first, and
  hands each request exactly its lanes (its resolver is a future), so
  the dispatcher never waits on the device: requests that arrive while
  a launch runs are dispatched behind it, and the device is not left
  idle while its last launch's verdicts go out.

Coalescing across channels keeps lanes/launch high even when individual
blocks are small — the multi-channel aggregate (BASELINE config #5)
benefits most.

Launch-latency regime auto-detection: coalescing wins when launches are
compute-bound and loses when a fixed per-launch latency dominates
(serializing small requests behind one queue then costs more than the
lane-count gain). Neither regime has been measured on the attached chip
(ROADMAP D3). The batcher measures the RTT of its own small launches (dispatch -> verdicts, lanes <= RTT_PROBE_LANES
so device compute is negligible) and switches itself between:

- "coalesce": linger + merge (low-RTT regime);
- "passthrough": every request launches immediately as its own async
  program, overlapping in flight exactly like independent callers —
  while the bounded-lane admission (the P7 backpressure contract)
  stays in force in both modes.

FABRIC_TPU_BATCHER_MODE=coalesce|passthrough|auto (default auto)
forces a mode; FABRIC_TPU_BATCHER_RTT_MS (default 25) is the auto
threshold between a low per-launch latency (<10ms) and a high one
(100ms and up), with hysteresis against flapping.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from collections import deque
from typing import Callable, Deque, List, Optional, Sequence, Tuple

from fabric_tpu.common import fabobs
from fabric_tpu.common.faults import fault_point
from fabric_tpu.common.flogging import must_get_logger
from fabric_tpu.common.retry import DISPATCH_POLICY, RetryPolicy, call_with_retry

logger = must_get_logger("batcher")


class _Request:
    __slots__ = (
        "keys", "sigs", "digests", "event", "result", "error", "permits",
        "t_submit", "on_dispatch", "deadline_s", "parent",
    )

    def __init__(self, keys, sigs, digests, on_dispatch=None,
                 deadline_s=None, parent=None):
        self.keys = keys
        self.sigs = sigs
        self.digests = digests
        self.event = threading.Event()
        self.result: Optional[List[bool]] = None
        self.error: Optional[BaseException] = None
        self.permits = 0
        self.t_submit = time.perf_counter()
        # fired exactly when this request's lane permits are released
        # (dispatcher pickup) — the serve sidecar's per-class QoS
        # ledger mirrors the batcher's admission window through it
        self.on_dispatch = on_dispatch
        # wire-deadline discipline (serve protocol rev 3): the absolute
        # time.monotonic() moment this request's budget expires, or
        # None.  The dispatcher caps its coalescing linger by the
        # TIGHTEST deadline in the batch — lanes with a live budget are
        # launched, never lingered past it.
        self.deadline_s = deadline_s
        # the submitter's fabobs span (serve.verify, pipeline.prepare):
        # what the dispatcher thread records for this request links to it
        self.parent = parent

    def resolve(self) -> List[bool]:
        self.event.wait()  # fablife: disable=blocking-unbudgeted  # bounded by the batcher lifetime, not a wire budget: stop() settles every admitted request fail-closed (event.set), so this wait can never outlive the batcher; wire deadlines cap it upstream via deadline_s
        if self.error is not None:
            raise self.error
        assert self.result is not None
        return self.result

    def fail_closed(self) -> None:
        """Settle with all-False verdicts — a stopped/hung batcher must
        never leave resolve() blocked and must never guess True.  A race
        with a real settlement is benign: whichever lands first wins and
        both outcomes are fail-closed (real verdicts or all-False)."""
        if not self.event.is_set():
            self.result = [False] * len(self.keys)  # fabdep: disable=unguarded-shared-write  # documented benign race: both settlements are fail-closed, event.set publishes
            self.event.set()


class VerifyBatcher:
    """submit() returns a resolver; call it to block for the verdicts of
    exactly the submitted lanes."""

    def __init__(
        self,
        provider,
        max_batch: int = 16384,
        linger_s: float = 0.002,
        max_pending_lanes: int = 65536,
        dispatch_retry: Optional[RetryPolicy] = None,
        join_timeout_s: float = 10.0,
        max_launch_lanes: Optional[int] = None,
    ):
        self.provider = provider
        self.max_batch = max_batch
        self.linger_s = linger_s
        # the widest launch the provider has a warm program for (a
        # sidecar's largest bucket): a request that would take a launch
        # past it waits for the next one.  None: no cap
        self.max_launch_lanes = max_launch_lanes
        # the request that did not fit the last launch; only the
        # dispatcher thread reads or writes it
        self._held: Optional[_Request] = None
        # launches dispatched and not yet settled, oldest first: (requests,
        # resolver, dispatch time, lanes, launch span); None is the
        # settler's exit token.  The settler pops an entry only once it
        # is settled, so len() counts the launches in flight
        self._pending: Deque[Optional[Tuple]] = deque()
        self._pending_cv = threading.Condition()
        # stop()'s patience for the dispatcher thread before settling
        # stragglers fail-closed (shorten in tests with hung resolvers)
        self.join_timeout_s = join_timeout_s
        # bounded transient retry for a failed launch (pool hiccup,
        # injected fault) before the error fans out to every resolver
        self.dispatch_retry = dispatch_retry or DISPATCH_POLICY
        self._q: "queue.Queue[Optional[_Request]]" = queue.Queue()
        self._stop_lock = threading.Lock()
        # every admitted-but-unsettled request, so stop() can settle
        # stragglers fail-closed; guarded by its own lock (stop() holds
        # _stop_lock around the sentinel put — reusing it here would
        # deadlock the dispatcher's settle path against stop's join)
        self._req_lock = threading.Lock()
        self._inflight: set = set()
        self._max_pending_lanes = max_pending_lanes
        # all-or-nothing admission under one condition variable: a
        # per-lane semaphore loop would let two concurrent large submits
        # each grab a partial allocation and deadlock
        self._lanes_cv = threading.Condition()
        self._lanes_free = max_pending_lanes
        self._stopped = False
        self.launches = 0  # introspection: device programs dispatched
        self.lanes = 0  # total lanes verified
        # transport-regime detection (see module docstring)
        self._forced_mode = os.environ.get("FABRIC_TPU_BATCHER_MODE", "auto")
        self._rtt_threshold_ms = float(
            os.environ.get("FABRIC_TPU_BATCHER_RTT_MS", "25")
        )
        self.rtt_ema_ms: Optional[float] = None
        # _observe_rtt runs on the settler thread while the dispatcher
        # reads the mode; the lock pins the EWMA read-modify-write
        self._rtt_lock = threading.Lock()
        # probe only launches small enough that device compute is
        # negligible next to transport RTT even on an attached chip
        # (64 lanes at ~65k verifies/s is ~1ms of compute; a 2048-lane
        # coalesced launch is ~30ms of COMPUTE and would mis-flip a
        # low-RTT chip into passthrough)
        self.RTT_PROBE_LANES = 64
        self._settler = threading.Thread(
            target=self._settle_loop, name="verify-batcher-settle",
            daemon=True,
        )
        self._settler.start()
        self._thread = threading.Thread(
            target=self._run, name="verify-batcher", daemon=True
        )
        self._thread.start()

    @property
    def mode(self) -> str:
        if self._forced_mode in ("coalesce", "passthrough"):
            return self._forced_mode
        if self.rtt_ema_ms is None:
            return "coalesce"  # no signal yet: original default
        # hysteresis band around the threshold stops mode flapping
        if self.rtt_ema_ms > self._rtt_threshold_ms * 1.2:
            return "passthrough"
        if self.rtt_ema_ms < self._rtt_threshold_ms * 0.8:
            return "coalesce"
        return self._last_mode

    _last_mode = "coalesce"

    def _observe_rtt(self, lanes: int, elapsed_s: float) -> None:
        if lanes > self.RTT_PROBE_LANES:
            return
        ms = elapsed_s * 1000.0
        with self._rtt_lock:
            self.rtt_ema_ms = (
                ms
                if self.rtt_ema_ms is None
                else 0.8 * self.rtt_ema_ms + 0.2 * ms
            )
            self._last_mode = self.mode

    @property
    def pending_lanes(self) -> int:
        """Lanes currently admitted but not yet dispatched — the
        admission-control fill signal the serve sidecar scales its
        retry_after hint by."""
        with self._lanes_cv:
            return self._max_pending_lanes - self._lanes_free

    def submit(
        self,
        keys: Sequence,
        signatures: Sequence[bytes],
        digests: Sequence[bytes],
    ) -> Callable[[], List[bool]]:
        resolver = self._admit(keys, signatures, digests, block=True)
        assert resolver is not None  # blocking admission never rejects
        return resolver

    def try_submit(
        self,
        keys: Sequence,
        signatures: Sequence[bytes],
        digests: Sequence[bytes],
        on_dispatch: Optional[Callable[[], None]] = None,
        deadline_s: Optional[float] = None,
        parent=None,
    ) -> Optional[Callable[[], List[bool]]]:
        """Non-blocking admission (the serve sidecar's front door): the
        resolver when the lane budget admits the request NOW, else None
        — the caller turns that into an explicit reject-with-retry-after
        instead of stalling a socket thread on the condition variable.
        ``on_dispatch`` fires when the dispatcher picks the request up
        (the moment its lane permits are released) — callers keeping a
        parallel admission ledger release theirs in the same window.
        ``deadline_s`` (absolute ``time.monotonic()``) caps how long the
        dispatcher may linger this request for coalescing company.
        ``parent`` is the caller's fabobs span where it is not the
        thread's current one (the sidecar opens ``serve.verify`` after
        admission)."""
        return self._admit(
            keys, signatures, digests, block=False, on_dispatch=on_dispatch,
            deadline_s=deadline_s, parent=parent,
        )

    def _admit(
        self,
        keys: Sequence,
        signatures: Sequence[bytes],
        digests: Sequence[bytes],
        block: bool,
        on_dispatch: Optional[Callable[[], None]] = None,
        deadline_s: Optional[float] = None,
        parent=None,
    ) -> Optional[Callable[[], List[bool]]]:
        n = len(keys)
        if n == 0:
            return list
        # chaos seam: an injected submit fault fails the CALLER before
        # any batcher state is touched (no lanes to leak); unkeyed — a
        # per-site seeded stream, not all-or-nothing per request size
        fault_point("batcher.submit")
        # bounded admission: lanes are taken atomically (all or nothing)
        # and released at dispatch. An oversized request is capped so it
        # can't demand more lanes than exist.
        req = _Request(
            list(keys), list(signatures), list(digests),
            on_dispatch=on_dispatch, deadline_s=deadline_s,
            parent=parent if parent is not None else fabobs.current_span(),
        )
        req.permits = min(n, self._max_pending_lanes)
        with self._lanes_cv:
            while self._lanes_free < req.permits:
                # stop() notifies this cv: an admission-blocked submitter
                # must not wait forever on permits a wedged dispatcher
                # will never release
                if self._stopped:
                    raise RuntimeError("batcher stopped")
                if not block:
                    fabobs.obs_count("fabric_batcher_busy_rejects_total")
                    return None
                self._lanes_cv.wait()  # fablife: disable=blocking-unbudgeted  # released by dispatch (lane permits freed) and by stop(), which sets _stopped and notify_all()s this cv — the loop re-checks _stopped every wake, so the wait is bounded by batcher teardown
            self._lanes_free -= req.permits
            pending = self._max_pending_lanes - self._lanes_free
        fabobs.obs_gauge("fabric_batcher_pending_lanes", pending)
        # the stop lock orders every put against the stop sentinel: no
        # request can land behind the None the dispatcher exits on
        with self._stop_lock:
            if self._stopped:
                with self._lanes_cv:
                    self._lanes_free += req.permits
                    self._lanes_cv.notify_all()
                raise RuntimeError("batcher stopped")
            with self._req_lock:
                self._inflight.add(req)
            self._q.put(req)
        return req.resolve

    def verify_batch(self, keys, signatures, digests) -> List[bool]:
        return self.submit(keys, signatures, digests)()

    # -- dispatcher ------------------------------------------------------
    def _take_batch(self) -> Optional[List[_Request]]:
        first, self._held = self._held, None
        if first is None:
            first = self._q.get()  # fablife: disable=blocking-unbudgeted  # the dispatcher's idle park, not a request hop: stop() posts the None sentinel this get() returns on, after settling in-flight work fail-closed
        if first is None:
            return None
        batch = [first]
        lanes = len(first.keys)
        if self.mode == "passthrough":
            # high-RTT regime: dispatch immediately, one launch per
            # request, overlapping in flight (admission control already
            # happened at submit)
            return batch
        t_first = time.perf_counter()
        waiter = (
            threading.Event()
        )  # fresh event as a precise, interruptible sleep
        while lanes < self.max_batch:
            try:
                nxt = self._q.get_nowait()
            except queue.Empty:
                if lanes >= self.max_batch // 2:
                    break  # big enough: don't trade latency for lanes
                # the linger window respects the TIGHTEST wire deadline
                # in the batch: a budgeted request is dispatched, never
                # lingered past the moment its client walks away
                linger = self.linger_s
                tightest = min(
                    (r.deadline_s for r in batch
                     if r.deadline_s is not None),
                    default=None,
                )
                if tightest is not None:
                    linger = min(linger, tightest - time.monotonic())
                if linger > 0:
                    waiter.wait(linger)
                try:
                    nxt = self._q.get_nowait()
                except queue.Empty:
                    if linger > 0 and self._backlogged(lanes):
                        continue  # linger on while the device is busy
                    break
            if nxt is None:
                self._q.put(None)  # re-post the stop token
                break
            if not self._fits(lanes, len(nxt.keys)):
                self._held = nxt  # first of the next launch
                break
            batch.append(nxt)
            lanes += len(nxt.keys)
        # the wait for company after the batch's first request
        fabobs.obs_record_span(
            "batcher.linger", t_first, time.perf_counter(),
            parent=first.parent, joined=len(batch) - 1,
        )
        return batch

    def _run(self) -> None:
        while True:
            batch = self._take_batch()
            if batch is None:
                break
            keys: List = []
            sigs: List[bytes] = []
            digests: List[bytes] = []
            for r in batch:
                keys.extend(r.keys)
                sigs.extend(r.sigs)
                digests.extend(r.digests)
            with self._lanes_cv:
                self._lanes_free += sum(r.permits for r in batch)
                self._lanes_cv.notify_all()
                released = self._max_pending_lanes - self._lanes_free
            fabobs.obs_gauge("fabric_batcher_pending_lanes", released)
            for r in batch:
                if r.on_dispatch is not None:
                    try:
                        r.on_dispatch()
                    except Exception as exc:  # noqa: BLE001 - a ledger hook must never kill the dispatcher
                        logger.warning("on_dispatch hook failed: %s", exc)
            # admission, the dispatcher's pick-up and the linger window
            t_launch = time.perf_counter()
            for r in batch:
                fabobs.obs_record_span(
                    "batcher.queue_wait", r.t_submit, t_launch,
                    parent=r.parent,
                )
            with self._pending_cv:
                inflight = len(self._pending)
            launch_span = fabobs.span(
                "batcher.launch",
                parent=batch[0].parent if len(batch) == 1 else None,
                lanes=len(keys), requests=len(batch), inflight=inflight,
            )
            if launch_span.span_id and len(batch) > 1:
                launch_span.set(req_ids=[
                    r.parent.attrs.get("req_id") for r in batch
                    if r.parent is not None
                ])
            try:
                with launch_span:
                    resolver = self._launch(keys, sigs, digests)
            except BaseException as exc:  # fablint: disable=broad-except  # error propagated to every waiting caller via r.error
                for r in batch:
                    self._settle_error(r, exc)
                continue
            self.launches += 1
            self.lanes += len(keys)
            fabobs.obs_count("fabric_batcher_launches_total", mode=self.mode)
            fabobs.obs_count(
                "fabric_batcher_launches_by_bucket_total",
                bucket=str(self._bucket_of(len(keys))),
            )
            fabobs.obs_observe("fabric_batcher_batch_lanes", len(keys))
            with self._pending_cv:
                self._pending.append(
                    (batch, resolver, time.perf_counter(), len(keys),
                     launch_span)
                )
                self._pending_cv.notify_all()
                # depth-4 pipeline: up to four launches in flight; the
                # dispatcher takes its next batch once the settler has
                # brought them back to three
                while len(self._pending) > 3:
                    self._pending_cv.wait()  # fablife: disable=blocking-unbudgeted  # the settler pops and notifies after every settle; a hung resolver holds it, and stop() then settles the stragglers fail-closed after its join timeout
        # stop: the settler settles what is in flight, then exits (a
        # hung resolver holds it past the join; stop() then settles the
        # stragglers fail-closed)
        with self._pending_cv:
            self._pending.append(None)
            self._pending_cv.notify_all()
        self._settler.join(timeout=self.join_timeout_s)

    def _settle_loop(self) -> None:
        """Settle the launches in flight in dispatch order, each as soon
        as its resolver returns, until the dispatcher's exit token."""
        while True:
            with self._pending_cv:
                while not self._pending:
                    self._pending_cv.wait()  # fablife: disable=blocking-unbudgeted  # the settler's idle park: every dispatch, and the dispatcher's exit on stop(), appends and notifies
                entry = self._pending[0]
            if entry is None:
                return
            self._settle(*entry)
            with self._pending_cv:
                self._pending.popleft()
                self._pending_cv.notify_all()

    def _backlogged(self, lanes: int) -> bool:
        """Whether the launches in flight are at least twice as wide as a
        launch of ``lanes``: the device runs launches one after another,
        so it has that work ahead of this batch, which may wait for
        company at no cost to it.  A provider that pads to no buckets
        (a host pool) runs launches side by side: no batch waits there."""
        if getattr(self.provider, "bucket_for", None) is None:
            return False
        with self._pending_cv:
            ahead = [e[3] for e in self._pending if e is not None]
        return sum(map(self._bucket_of, ahead)) >= 2 * self._bucket_of(lanes)

    def _fits(self, lanes: int, more: int) -> bool:
        """Whether a launch of ``lanes`` lanes may take a request of
        ``more``: it stays within ``max_launch_lanes``, and the width
        the provider pads it to grows by no more than the request's own
        launch would take.  The program's time is linear in its width
        (the kernel on TPU v5e, 2,048 to 8,192 lanes), so past that the
        request verifies in less device time leading the next launch."""
        if (
            self.max_launch_lanes is not None
            and lanes + more > self.max_launch_lanes
        ):
            return False
        grown = self._bucket_of(lanes + more) - self._bucket_of(lanes)
        return grown <= self._bucket_of(more)

    def _bucket_of(self, lanes: int) -> int:
        """The width the provider pads a launch of ``lanes`` to; a
        provider that pads nothing launches at the width it is given."""
        bucket_for = getattr(self.provider, "bucket_for", None)
        return lanes if bucket_for is None else bucket_for(lanes)

    def _launch(self, keys: List, sigs: List[bytes], digests: List[bytes]):
        """One device/provider launch with bounded transient retry: a
        flapping backend (pool hiccup, injected fault) gets
        dispatch_retry's capped-backoff attempts before the failure fans
        out to every waiting resolver.  The fault site is unkeyed: the
        per-site seeded stream re-rolls the decision on every attempt,
        so a probabilistic plan models a flap the retry can ride out
        (a batch-content key would re-fire identically per attempt)."""
        dispatch = getattr(self.provider, "batch_verify_async", None)

        def attempt(n: int):
            fault_point("batcher.dispatch")
            if dispatch is None:
                # provider without an async seam: compute now, hand back
                # a trivial resolver (SoftwareProvider HAS
                # batch_verify_async — on the hostec_np/hostec tiers it
                # shards across the process pool and resolves later)
                verdicts = self.provider.batch_verify(keys, sigs, digests)
                return lambda v=verdicts: v
            return dispatch(keys, sigs, digests)

        def on_retry(exc: BaseException, attempt_n: int) -> None:
            fabobs.obs_count("fabric_batcher_dispatch_retries_total")
            fabobs.obs_event(
                "batcher.dispatch_retry",
                attempt=attempt_n, error=type(exc).__name__,
            )

        return call_with_retry(
            attempt, policy=self.dispatch_retry, on_retry=on_retry
        )

    def _settle_error(self, r: _Request, exc: BaseException) -> None:
        if not r.event.is_set():
            r.error = exc
            r.event.set()
        with self._req_lock:
            self._inflight.discard(r)

    def _settle(
        self,
        reqs: List[_Request],
        resolver: Callable,
        t0: float = 0.0,
        lanes: int = 0,
        launch_span=None,
    ) -> None:
        try:
            with fabobs.span("batcher.settle", parent=launch_span, lanes=lanes):
                out = list(resolver())
            if t0:
                self._observe_rtt(lanes, time.perf_counter() - t0)
        except BaseException as exc:  # fablint: disable=broad-except  # error propagated to every waiting caller via r.error
            for r in reqs:
                self._settle_error(r, exc)
            return
        now = time.perf_counter()
        off = 0
        for r in reqs:
            n = len(r.keys)
            if not r.event.is_set():  # stop() may have settled fail-closed
                r.result = out[off : off + n]
                r.event.set()
                fabobs.obs_observe(
                    "fabric_batcher_submit_wait_seconds", now - r.t_submit
                )
            off += n
            with self._req_lock:
                self._inflight.discard(r)

    def stop(self) -> None:
        """Idempotent shutdown.  After the dispatcher exits (or the join
        times out on a hung resolver), every still-unsettled request is
        settled fail-closed (all-False verdicts) so no resolve() caller
        blocks forever and no lane is ever guessed VALID."""
        with self._stop_lock:
            first = not self._stopped
            self._stopped = True
            if first:
                self._q.put(None)
        # wake submitters blocked on lane admission so they observe the
        # stop instead of waiting for permits that will never come back
        with self._lanes_cv:
            self._lanes_cv.notify_all()
        self._thread.join(timeout=self.join_timeout_s)
        with self._req_lock:
            leftovers = list(self._inflight)
            self._inflight.clear()
        for r in leftovers:
            r.fail_closed()
        if leftovers:
            # a fail-closed settlement is exactly the moment worth a
            # flight-recorder snapshot: what led up to the hang is in
            # the ring right now
            fabobs.obs_count(
                "fabric_batcher_fail_closed_total", len(leftovers)
            )
            fabobs.obs_trigger(
                "batcher.fail_closed", requests=len(leftovers)
            )


class BatchingProvider:
    """BCCSP-provider adapter over a shared VerifyBatcher: every channel
    validator on the node funnels its batch_verify through ONE batcher
    (and thus one device-launch queue), while single verify/sign/hash
    calls pass straight through to the wrapped provider."""

    def __init__(self, provider, **batcher_kwargs):
        self._provider = provider
        self.batcher = VerifyBatcher(provider, **batcher_kwargs)

    def batch_verify(self, keys, signatures, digests):
        return self.batcher.verify_batch(keys, signatures, digests)

    def batch_verify_async(self, keys, signatures, digests):
        return self.batcher.submit(keys, signatures, digests)

    def stop(self) -> None:
        self.batcher.stop()

    def __getattr__(self, name):
        return getattr(self._provider, name)
