"""Two-stage commit pipeline (SURVEY.md §2.13 P4: deliver -> payload
buffer -> validate -> commit stages overlap across blocks; reference
gossip/state.go:542 + kv_ledger.go:596 run block N's delivery while
block N-1 commits).

Stage A (prepare): orderer-sig check + host parse + the DEVICE signature
batch for block N — runs while stage B finishes block N-1.
Stage B (commit): policy circuits, MVCC, stores — inherently sequential
per channel, one worker, in order.

The bounded queue between the stages is the backpressure discipline of
SURVEY §2.13 P7 (orderer WaitReady analog): a slow commit stage stalls
`submit`, which stalls the deliver client, which stops pulling."""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Optional

from fabric_tpu.common import fabobs
from fabric_tpu.common.faults import fault_point
from fabric_tpu.common.flogging import must_get_logger
from fabric_tpu.protos import common_pb2


class PipelineError(Exception):
    pass


class _Handoff:
    """What crosses the queue: the block and its number, stage A's
    result, stage A's span (the parent of everything stage B records for
    the block) and the moment the put succeeded.  The submitter stamps
    ``t_put`` after the put returns, so a committer that was already
    waiting may read it unset: the block then waited for nobody."""

    __slots__ = ("block", "number", "prepared", "span", "t_put")

    def __init__(self, block, number, prepared, span):
        self.block = block
        self.number = number
        self.prepared = prepared
        self.span = span
        self.t_put: Optional[float] = None


class CommitPipeline:
    def __init__(
        self,
        channel,  # peer.channel.Channel
        on_commit: Optional[Callable[[common_pb2.Block, object], None]] = None,
        on_error: Optional[Callable[[common_pb2.Block, Exception], None]] = None,
        depth: int = 2,
    ):
        self.channel = channel
        self.on_commit = on_commit
        self.on_error = on_error
        self._prepared: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stopped = threading.Event()
        self._idle = threading.Event()
        self._idle.set()
        self._pending = 0
        self._pending_lock = threading.Lock()
        # terminal triage for soak runs: drain() returning False means
        # "not yet idle" — last_error (most recent commit exception,
        # guarded by _pending_lock) and dead (committer thread gone
        # without stop()) distinguish slow from dead
        self.last_error: Optional[BaseException] = None
        self._crashed = False
        self._committer = threading.Thread(
            target=self._commit_loop,
            name=f"commit-{channel.channel_id}",
            daemon=True,
        )
        self._committer.start()

    # -- producer side (the deliver loop) ----------------------------------
    def submit(self, block: common_pb2.Block) -> None:
        """Prepare block and hand it to the committer. Runs stage A on
        the CALLING thread (the deliver loop), so while the committer
        drains block N-1 this thread already parses + device-verifies
        block N. Blocks when the queue is full (P7 backpressure)."""
        if self._stopped.is_set():
            raise PipelineError("pipeline stopped")
        with self._pending_lock:
            self._pending += 1
            self._idle.clear()
        number = int(getattr(block.header, "number", 0))
        try:
            t0 = time.perf_counter()
            with fabobs.span("pipeline.prepare", block=number) as prep_span:
                prepared = self.channel.prepare_block(block)
            t_offered = time.perf_counter()
            fabobs.obs_observe(
                "fabric_pipeline_stage_seconds", t_offered - t0,
                stage="prepare",
            )
            item = _Handoff(block, number, prepared, prep_span)
            # bounded put that watches _stopped: a plain blocking put on
            # a full queue after stop() would wait forever — the
            # committer has exited and will never drain it (pipeline
            # audit, PR 3)
            while True:
                if self._stopped.is_set():
                    raise PipelineError("pipeline stopped")
                try:
                    self._prepared.put(item, timeout=0.2)
                except queue.Full:
                    continue
                item.t_put = time.perf_counter()
                # the submitter held by the full queue (P7 backpressure)
                fabobs.obs_record_span(
                    "pipeline.backpressure", t_offered, item.t_put,
                    parent=prep_span, block=number,
                )
                if self._stopped.is_set() and not self._committer.is_alive():
                    # stop() landed between our check and the put: the
                    # committer will never consume this item. Reclaim it
                    # (one submitter per pipeline, so the reclaimed item
                    # is ours) so _pending/_idle stay balanced.
                    try:
                        self._prepared.get_nowait()
                    except queue.Empty:
                        return  # consumed before the committer exited
                    raise PipelineError("pipeline stopped")
                return
        except Exception:
            with self._pending_lock:
                self._pending -= 1
                if self._pending == 0:
                    self._idle.set()
            raise

    # -- consumer side -----------------------------------------------------
    def _commit_loop(self) -> None:
        try:
            self._commit_loop_inner()
        except BaseException as exc:
            # the loop only exits this way on a non-Exception escape
            # (interpreter teardown, injected BaseException): latch the
            # crash so dead stays True even after a cleanup stop()
            with self._pending_lock:
                self.last_error = exc
            self._crashed = True
            raise

    def _commit_loop_inner(self) -> None:
        while not self._stopped.is_set():
            try:
                item = self._prepared.get(timeout=0.2)
            except queue.Empty:
                continue
            t_taken = time.perf_counter()
            block, number = item.block, item.number
            # a prepared block waiting for the committer
            fabobs.obs_record_span(
                "pipeline.queue_wait", item.t_put or t_taken, t_taken,
                parent=item.span, block=number,
            )
            try:
                # chaos seam: keyed by block number, so a seeded plan
                # fails a deterministic subset of commits
                fault_point("pipeline.commit", key=number)
                t0 = time.perf_counter()
                with fabobs.span(
                    "pipeline.commit", parent=item.span, block=number
                ):
                    flags = self.channel.store_block(
                        block, prepared=item.prepared
                    )
                fabobs.obs_observe(
                    "fabric_pipeline_stage_seconds",
                    time.perf_counter() - t0, stage="commit",
                )
                if self.on_commit is not None:
                    self.on_commit(block, flags)
            except Exception as exc:  # noqa: BLE001 - surfaced to the owner
                fabobs.obs_count("fabric_pipeline_commit_failures_total")
                with self._pending_lock:
                    self.last_error = exc
                if self.on_error is not None:
                    self.on_error(block, exc)
                else:
                    # no owner callback installed: a silently dropped
                    # block would stall the channel with no trace
                    # (fabflow mask-fail-open audit) — log loudly
                    must_get_logger("pipeline").error(
                        "commit of block %s failed with no on_error "
                        "handler installed: %s",
                        getattr(block.header, "number", "?"), exc,
                    )
            finally:
                with self._pending_lock:
                    self._pending -= 1
                    if self._pending == 0:
                        self._idle.set()

    def drain(self, timeout: float = 30.0) -> bool:
        """Wait until every submitted block has committed.  Returns
        False on timeout — check ``last_error`` (the loop's most recent
        commit exception) and ``dead`` to tell a slow pipeline from a
        wedged or crashed one."""
        return self._idle.wait(timeout)

    @property
    def dead(self) -> bool:
        """True when the committer thread crashed or exited without
        stop() — the pipeline will never drain (vs. merely slow).  The
        crashed state is latched, so a cleanup stop() after the fact
        does not mask it."""
        return self._crashed or (
            not self._committer.is_alive() and not self._stopped.is_set()
        )

    def stop(self) -> None:
        self._stopped.set()
        self._committer.join(timeout=5)
        # release the pending counts of any items the committer never
        # consumed, so a post-stop drain() returns instead of hanging
        while True:
            try:
                self._prepared.get_nowait()
            except queue.Empty:
                break
            with self._pending_lock:
                self._pending -= 1
                if self._pending == 0:
                    self._idle.set()
