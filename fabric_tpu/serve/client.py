"""Client shim: the sidecar as a BCCSP provider rung.

``SidecarProvider`` speaks the serve protocol to a resident sidecar and
presents the standard Provider SPI, so ``peer/pipeline``, the
VerifyBatcher and the chaos harness route through the sidecar without
knowing it exists.  Select it like any other rung::

    provider_from_config({"Default": "SERVE", "SERVE": {"Address": addr}})
    FABRIC_TPU_SERVE_ADDR=/tmp/fabserve.sock   # default_provider() routes

Degrade contract (the mask discipline this file is in the fabflow MASK
tier for):

- ``ST_BUSY`` is admission control, not failure: the client retries on
  the shared ``common.retry`` pacing, honoring the sidecar's
  ``retry_after_ms`` hint, until the policy budget is spent.
- A dead/stopping sidecar (connect failure, mid-batch socket death,
  ST_STOPPING, budget exhausted) degrades to IN-PROCESS verification
  through the local probe ladder (device if present, else SW) — masks
  stay bit-exact, requests never fail just because the sidecar died.
- If even the in-process fallback throws, the batch's mask is all-False
  (fail-closed) — a lane is never guessed VALID on any failure path.
"""

from __future__ import annotations

import os
import select
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from fabric_tpu.common import fabobs
from fabric_tpu.common.flogging import must_get_logger
from fabric_tpu.common.retry import Backoff, CooldownGate, RetryPolicy
from fabric_tpu.serve import protocol as proto
from fabric_tpu.serve.protocol import parse_address

logger = must_get_logger("serve.client")

#: Admission-control pacing: capped exponential between BUSY retries,
#: bounded total wait before the client degrades to in-process verify.
BUSY_POLICY = RetryPolicy(
    base_s=0.01, multiplier=2.0, cap_s=0.5, deadline_s=10.0, max_attempts=16
)


class SidecarUnavailable(Exception):
    """The sidecar cannot serve this request (dead socket, stopping,
    protocol violation).  The provider degrades to in-process verify."""


class SidecarClient:
    """One pipelined connection to a sidecar.

    ``submit_verify`` writes the request frame and returns a token;
    ``await_verify`` demultiplexes response frames until the token's
    reply arrives — concurrent callers cooperate under the receive lock,
    and replies may arrive in ANY order (the server settles verify
    requests concurrently): each frame is matched to its waiter by
    request id.  Any socket failure fails every pending token with
    :class:`SidecarUnavailable`: the waiters' provider degrades
    in-process, so a sidecar killed mid-batch still yields bit-exact
    masks.
    """

    def __init__(
        self,
        address: str,
        connect_timeout_s: float = 5.0,
        request_timeout_s: float = 120.0,
    ):
        self.address = address
        self.connect_timeout_s = connect_timeout_s
        self.request_timeout_s = request_timeout_s
        # negotiated protocol revision: optimistic v2, latched down to
        # v1 when the connect-time hello learns the server refuses v2
        # frames (an old sidecar kills the stream on an unknown
        # version) — old servers keep serving new clients, minus QoS
        self.version = proto.PROTOCOL_VERSION
        self._sock = None
        self._send_lock = threading.Lock()
        self._recv_lock = threading.Lock()
        self._state_lock = threading.Lock()
        self._next_id = 0
        # token -> {"event": Event, "reply": payload|None, "error": exc|None}
        self._pending: Dict[int, Dict] = {}
        # failure-driven dial circuit: a permanently-dead TCP endpoint
        # (SYN blackholed) costs connect_timeout_s PER BATCH without it
        # — every commit would stall ~5s before degrading.  CooldownGate
        # carries its own leaf lock, so it is safe both under
        # _state_lock (ready) and outside it (record_* after a dial).
        self._dial_gate = CooldownGate()

    # -- connection --------------------------------------------------------
    def _connect(self):
        import socket as _socket

        family, target = parse_address(self.address)
        sock = _socket.socket(family, _socket.SOCK_STREAM)
        sock.settimeout(self.connect_timeout_s)
        sock.connect(target)
        # the hello stays on the CONNECT budget: it is one tiny
        # round-trip, and a gray endpoint that accepts but never
        # answers must stall a dialer (and the router's probe path)
        # for seconds, not the full request timeout
        return self._hello(sock, family, target)

    def _hello(self, sock, family, target):
        """Connect-time version negotiation: one PING at the preferred
        revision, raw on the fresh socket (nothing else is in flight
        yet).  The downgrade to v1 is EVIDENCE-BASED: only a reply that
        is not a PING ST_OK (the old server answers one ST_ERROR frame
        before closing) latches v1 — a silent EOF or reset (a sidecar
        restarting under the dial) is a transport failure that raises,
        so a transient crash window can never permanently strip the
        QoS class off a long-lived client.  A server refusing v1 too
        is genuinely unusable."""
        import socket as _socket

        while True:
            refusal = False
            try:
                proto.send_frame(sock, proto.OP_PING, 0, b"",
                                 version=self.version)
                reply = proto.recv_frame(sock)
                if reply is not None:
                    opcode, _rid, payload = reply
                    if opcode == proto.OP_PING:
                        status, _, _, _ = proto.decode_verify_response(
                            payload
                        )
                        if status == proto.ST_OK:
                            # negotiated: switch to the request budget
                            sock.settimeout(self.request_timeout_s)
                            return sock
                    # it answered SOMETHING that is not an acceptance:
                    # the refusing server's one error frame
                    refusal = True
            except proto.ProtocolError:
                refusal = True  # unparseable reply: not our revision
            except OSError as exc:
                try:
                    sock.close()
                except OSError:
                    pass
                raise SidecarUnavailable(f"hello transport: {exc}") from exc
            try:
                sock.close()
            except OSError:
                pass
            if not refusal:
                # clean EOF, no refusal frame: the server went away
                # mid-hello — retry later at the SAME revision
                raise SidecarUnavailable("hello: stream closed")
            if self.version <= proto.MIN_PROTOCOL_VERSION:
                raise SidecarUnavailable(
                    f"hello refused at protocol v{self.version}"
                )
            # step DOWN one revision per refusal (v3 -> v2 -> v1): a
            # v2 server costs a v3 client only the deadline/cancel
            # fields, never the QoS class it still understands
            with self._state_lock:
                self.version -= 1
            sock = _socket.socket(family, _socket.SOCK_STREAM)
            sock.settimeout(self.connect_timeout_s)
            sock.connect(target)

    def _ensure_sock(self):
        with self._state_lock:
            if self._sock is not None:
                return self._sock
            if not self._dial_gate.ready():
                raise SidecarUnavailable(
                    f"connect {self.address}: cooling down after "
                    "dial failure"
                )
        # dial OUTSIDE the state lock: a blackholed endpoint blocks in
        # connect() for connect_timeout_s, and close()/_fail_all/the
        # await_reply loop must not stall behind the dialer
        try:
            sock = self._connect()
        except (OSError, SidecarUnavailable) as exc:
            self._dial_gate.record_failure()
            raise SidecarUnavailable(
                f"connect {self.address}: {exc}"
            ) from exc
        self._dial_gate.record_success()
        with self._state_lock:
            if self._sock is None:
                self._sock = sock
                return sock
            winner = self._sock
        # a concurrent dialer won the install race: use its socket
        try:
            sock.close()
        except OSError:
            pass
        return winner

    def _fail_all(self, exc: Exception) -> None:
        """Socket death: every pending waiter learns, the connection is
        torn down (the next call reconnects)."""
        with self._state_lock:
            sock, self._sock = self._sock, None
            pending = list(self._pending.values())
            self._pending.clear()
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass
        for entry in pending:
            entry["error"] = SidecarUnavailable(str(exc))
            entry["event"].set()

    def close(self) -> None:
        self._fail_all(SidecarUnavailable("client closed"))

    # -- request plumbing --------------------------------------------------
    def submit(self, opcode: int, payload: bytes) -> int:
        """Send one frame; returns the token to await.  Raises
        SidecarUnavailable on any transport failure."""
        sock = self._ensure_sock()
        with self._send_lock:
            with self._state_lock:
                self._next_id = (self._next_id + 1) & 0xFFFFFFFF
                token = self._next_id
                self._pending[token] = {
                    "event": threading.Event(), "reply": None, "error": None,
                }
            try:
                proto.send_frame(sock, opcode, token, payload,
                                 version=self.version)
            except OSError as exc:
                self._fail_all(exc)
                raise SidecarUnavailable(f"send: {exc}") from exc
        return token

    def await_reply(
        self, token: int, timeout_s: Optional[float] = None
    ) -> bytes:
        """Block until the token's response payload arrives (cooperative
        demux: whichever waiter holds the recv lock reads frames and
        settles the tokens they answer).  ``timeout_s`` overrides the
        connection default — the wire-deadline discipline derives every
        per-hop wait from the request's remaining budget instead of one
        static constant."""
        if timeout_s is None:
            timeout_s = self.request_timeout_s
        out = self._demux_wait(
            token, time.monotonic() + max(0.0, timeout_s), give_up=True
        )
        assert out is not None  # give_up=True raises instead
        return out

    def poll_reply(self, token: int, wait_s: float) -> Optional[bytes]:
        """Bounded, NON-consuming wait: the token's payload if it
        settles within ``wait_s``, else None with the token still
        pending — the hedged-verification primitive (the router polls
        the primary for one hedge delay, then keeps both the primary
        and the hedge in flight, first verdict wins).  Raises
        SidecarUnavailable only on real transport failure."""
        return self._demux_wait(
            token, time.monotonic() + max(0.0, wait_s), give_up=False
        )

    def _demux_wait(
        self, token: int, deadline: float, give_up: bool
    ) -> Optional[bytes]:
        while True:
            with self._state_lock:
                entry = self._pending.get(token)
            if entry is None:
                raise SidecarUnavailable("reply already consumed or failed")
            if entry["event"].is_set():
                with self._state_lock:
                    self._pending.pop(token, None)
                if entry["error"] is not None:
                    raise entry["error"]
                return entry["reply"]
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                if not give_up:
                    return None  # token stays pending (hedge polling)
                # give up on THIS token only: the connection may be
                # healthy and another waiter mid-demux — tearing it
                # down would discard that waiter's nearly-done
                # server-side work.  A late reply for this token is
                # dropped by the demux's gave-up branch below.
                with self._state_lock:
                    self._pending.pop(token, None)
                raise SidecarUnavailable("reply timeout")
            got_lock = self._recv_lock.acquire(timeout=min(remaining, 0.1))
            if not got_lock:
                continue
            try:
                if entry["event"].is_set():
                    continue  # settled while we waited for the lock
                sock = self._sock
                if sock is None:
                    raise SidecarUnavailable("connection lost")
                # select before recv: the demux holder must honor ITS
                # deadline without consuming partial frames — a recv
                # timeout mid-frame would desync the stream, a select
                # timeout touches nothing (how a tight budget walks
                # away from a dead-slow socket instead of parking on it)
                readable, _, _ = select.select(
                    [sock], [], [], min(remaining, 0.1)
                )
                if not readable:
                    continue
                try:
                    frame = proto.recv_frame(sock)
                except (OSError, proto.ProtocolError) as exc:
                    self._fail_all(exc)
                    raise SidecarUnavailable(f"recv: {exc}") from exc
                if frame is None:
                    self._fail_all(ConnectionError("sidecar closed stream"))
                    raise SidecarUnavailable("sidecar closed the stream")
                _opcode, rid, payload = frame
                with self._state_lock:
                    settled = self._pending.get(rid)
                if settled is not None:
                    settled["reply"] = payload
                    settled["event"].set()
                # else: reply for a token whose waiter gave up — drop
            finally:
                self._recv_lock.release()

    def cancel(self, token: int) -> None:
        """Best-effort abandon of an in-flight request: the local waiter
        state is dropped NOW (a late reply falls into the demux's
        gave-up branch), and on a rev-3 connection an OP_CANCEL frame
        tells the server to shed or stop replying.  The frame goes out
        even when the token is no longer pending — a reply-timeout
        give-up already popped it, and THAT is exactly the abandonment
        the server should hear about.  Never raises — a cancel races
        the settlement by design, and both orders are correct (the
        reply is either suppressed server-side or dropped
        client-side)."""
        with self._state_lock:
            self._pending.pop(token, None)
            sock = self._sock
        if sock is None or self.version < 3:
            return
        try:
            with self._send_lock:
                proto.send_frame(
                    sock, proto.OP_CANCEL, token, b"", version=self.version
                )
        except OSError as exc:
            logger.debug("cancel frame for token %d failed: %s", token, exc)

    def request(
        self, opcode: int, payload: bytes = b"",
        timeout_s: Optional[float] = None,
    ) -> bytes:
        return self.await_reply(self.submit(opcode, payload), timeout_s)

    def ensure_connected(self) -> None:
        """Dial (and version-hello) now if not connected.  Callers that
        encode version-dependent payloads use this to latch the
        negotiated revision BEFORE building the request body."""
        self._ensure_sock()

    # -- typed helpers -----------------------------------------------------
    def ping(self, timeout_s: Optional[float] = None) -> bool:
        """Liveness probe.  ``timeout_s`` matters: a health probe that
        rides the full request timeout lets one gray endpoint stall the
        whole probe path — the router passes its own short budget."""
        status, _, _, _ = proto.decode_verify_response(
            self.request(proto.OP_PING, timeout_s=timeout_s)
        )
        return status == proto.ST_OK

    def stats(self) -> Dict:
        import json

        return json.loads(self.request(proto.OP_STATS).decode())

    def shutdown(self) -> None:
        self.request(proto.OP_SHUTDOWN)


def deadline_ms_from_env() -> int:
    """``FABRIC_TPU_SERVE_DEADLINE_MS`` -> per-batch latency budget in
    milliseconds (0/unset = no deadline; the shared env read
    discipline: malformed values warn and disable the knob, never break
    a verify path)."""
    raw = os.environ.get("FABRIC_TPU_SERVE_DEADLINE_MS", "")
    if not raw:
        return 0
    try:
        return max(0, int(raw))
    except ValueError:
        logger.warning(
            "FABRIC_TPU_SERVE_DEADLINE_MS=%r ignored (not an int)", raw
        )
        return 0


def encode_lanes(
    keys: Sequence, signatures: Sequence[bytes], digests: Sequence[bytes],
    qos_class: Optional[int] = proto.DEFAULT_QOS, channel: str = "",
    deadline_ms: Optional[int] = None,
    version: int = proto.PROTOCOL_VERSION,
) -> bytes:
    """Provider lanes -> wire payload, deduplicating repeated key
    objects (the MSP cache reuses them) into the frame's key table.  A
    key that cannot serialize maps to NO_KEY — the server verifies that
    lane False, same as the in-process parse path.  ``version`` picks
    the body layout, which MUST match the frame revision the payload
    rides on: the default is the current revision (deadline_ms 0 = no
    budget), and ``qos_class=None`` forces the v1 body a v1-latched
    connection must send."""
    from fabric_tpu.common import p256

    table: List[bytes] = []
    index_of: Dict[int, int] = {}
    lanes: List[Tuple[int, bytes, bytes]] = []
    for key, sig, digest in zip(keys, signatures, digests, strict=True):
        idx = proto.NO_KEY
        if key is not None:
            idx = index_of.get(id(key), -1)
            if idx < 0:
                try:
                    raw = p256.pubkey_to_bytes(key.point)
                except Exception as exc:  # noqa: BLE001 - bad key: dead lane
                    logger.debug("unserializable key (%s); lane fails", exc)
                    raw = None
                if raw is None:
                    idx = proto.NO_KEY
                else:
                    idx = len(table)
                    table.append(raw)
                    index_of[id(key)] = idx
        lanes.append((idx, bytes(sig), bytes(digest)))
    if qos_class is None:
        version = 1  # explicit v1-body request (legacy calling style)
    return proto.encode_verify_request(
        table, lanes,
        qos_class=qos_class if version >= 2 else None,
        channel=channel,
        deadline_ms=(
            (deadline_ms if deadline_ms is not None else 0)
            if version >= 3 else None
        ),
    )


class SidecarProvider:
    """BCCSP rung routing batch verification through a resident sidecar,
    degrading to the in-process SW provider when the sidecar cannot
    serve.  Single verify/sign/hash/key ops run in-process always — the
    sidecar exists for the batch plane, and interactive single calls
    must not inherit its failure modes."""

    def __init__(
        self,
        address: Optional[str] = None,
        fallback=None,
        busy_policy: RetryPolicy = BUSY_POLICY,
        sleeper: Callable[[float], None] = time.sleep,
        qos_class: Optional[int] = None,
        channel: str = "",
        deadline_ms: Optional[int] = None,
    ):
        address = address or os.environ.get("FABRIC_TPU_SERVE_ADDR", "")
        if not address:
            raise ValueError(
                "sidecar address required (FABRIC_TPU_SERVE_ADDR or "
                "BCCSP.SERVE.Address)"
            )
        self.client = SidecarClient(address)
        self.busy_policy = busy_policy
        self._sleeper = sleeper
        self._fallback = fallback
        self._fallback_lock = threading.Lock()
        self.degraded = False  # latched: any request served in-process
        self.busy_rejects = 0  # admission rejections observed
        self.deadline_expired = 0  # budgets that ran out before a verdict
        # per-batch latency budget (wire deadline, protocol rev 3):
        # every per-hop wait — reply wait, busy-retry pacing — derives
        # from the remaining budget; 0 = no deadline (legacy behavior)
        self.deadline_ms = (
            deadline_ms if deadline_ms is not None else deadline_ms_from_env()
        )
        # admission class for protocol rev 2: explicit class wins, else
        # the FABRIC_TPU_SERVE_QOS channel map, else the wire default
        self.channel = channel
        if qos_class is None:
            from fabric_tpu.serve.qos import class_for_channel, qos_map_from_env

            qos_class = class_for_channel(channel, qos_map_from_env())
        self.qos_class = qos_class

    def _encode(
        self, keys, signatures, digests,
        remaining_s: Optional[float] = None,
    ) -> bytes:
        """Lane payload at the negotiated revision: the QoS prefix is
        only emitted once the client knows the server speaks v2, the
        deadline field once it speaks v3 (carrying the budget REMAINING
        at encode time — floored at 1ms so a nearly-spent budget never
        decodes as 'no deadline' — or 0 when no budget is set)."""
        return encode_lanes(
            keys, signatures, digests,
            qos_class=self.qos_class, channel=self.channel,
            deadline_ms=(
                max(1, int(remaining_s * 1000.0))
                if remaining_s is not None else 0
            ),
            version=self.client.version,
        )

    def _deadline(self) -> Optional[float]:
        """Absolute monotonic deadline for a batch entering now, or
        None when no budget is configured."""
        if not self.deadline_ms:
            return None
        return time.monotonic() + self.deadline_ms / 1000.0

    def _expire(self, keys, signatures, digests, why: str) -> List[bool]:
        """Budget ran out: hand the batch back to the in-process ladder
        NOW instead of parking on a dead-slow socket (the mask stays
        bit-exact through the same degrade path)."""
        self.deadline_expired += 1  # GIL-atomic add, stats only
        fabobs.obs_count(
            "fabric_serve_deadline_expired_total", seam="serve.client"
        )
        return self._degrade(keys, signatures, digests, why)

    # -- in-process fallback ----------------------------------------------
    def fallback_provider(self):
        with self._fallback_lock:
            if self._fallback is None:
                # the device-probe ladder, not a hardcoded SW rung: an
                # accelerator-attached node whose sidecar dies (or whose
                # FABRIC_TPU_SERVE_ADDR went stale) keeps its device
                from fabric_tpu.crypto.bccsp import probe_provider

                self._fallback = probe_provider()
            return self._fallback

    def _degrade(self, keys, signatures, digests, why) -> List[bool]:
        """In-process verification when the sidecar cannot serve.  The
        mask stays bit-exact (same ladder semantics); only if the local
        path ALSO fails is the batch failed closed as all-False."""
        if not self.degraded:
            logger.warning(
                "sidecar %s unavailable (%s); degrading to in-process "
                "verification", self.client.address, why,
            )
            # the first degrade is the flight-recorder moment: dump what
            # led here (obs failures swallow; the mask path continues).
            # The counter sits in the same transition gate — the family
            # counts degrade TRANSITIONS like every other seam, not one
            # tick per batch served by a latched-degraded provider.
            fabobs.obs_count("fabric_degrade_total", seam="serve.client")
            fabobs.obs_trigger("serve.client_degraded")
        self.degraded = True
        try:
            mask = self.fallback_provider().batch_verify(
                keys, signatures, digests
            )
            return list(mask)
        except Exception as exc:  # noqa: BLE001 - double fault: fail closed
            logger.error(
                "in-process fallback failed too (%s): batch fails closed",
                exc,
            )
            return [False] * len(keys)

    # -- the remote verify loop -------------------------------------------
    def _verify_once(
        self, payload: bytes, timeout_s: Optional[float], encode_span
    ) -> Tuple[int, int, Optional[List[bool]], str]:
        """One request on the wire.  Its id is the connection's token,
        known only once the frame is out, so the spans that opened before
        that (``encode_span``, ``client.roundtrip``) are given it late."""
        with fabobs.span("client.roundtrip") as roundtrip_span:
            token = self.client.submit(proto.OP_VERIFY, payload)
            roundtrip_span.set(req_id=token)
            encode_span.set(req_id=token)
            try:
                reply = self.client.await_reply(token, timeout_s)
            except SidecarUnavailable:
                # abandoning the wait (budget/timeout) must TELL the
                # server: an uncancelled tight-deadline batch would make
                # the slow sidecar compute a verdict nobody will read —
                # exactly the capacity OP_CANCEL exists to reclaim
                self.client.cancel(token)
                raise
        with fabobs.span("client.decode", req_id=token):
            return proto.decode_verify_response(reply)

    def batch_verify(
        self, keys, signatures, digests
    ) -> List[bool]:
        return self._batch_verify(keys, signatures, digests,  # fabdet: disable=wallclock-in-det  # wire deadline budget: deadline_ms carries the budget REMAINING at encode time — a semantically time-derived protocol field (masks, not deadlines, are the replay contract)
                                  self._deadline())

    def _batch_verify(
        self, keys, signatures, digests, deadline: Optional[float]
    ) -> List[bool]:
        """The verify loop against an ALREADY-STARTED budget: the async
        resolver re-enters here with its original deadline, so a
        busy/error resolve can never restart the per-batch clock."""
        n = len(keys)
        if n == 0:
            return []
        t0 = time.perf_counter()
        bo = Backoff(self.busy_policy, sleeper=self._sleeper)
        while True:
            remaining: Optional[float] = None
            if deadline is not None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return self._expire(
                        keys, signatures, digests, "deadline budget expired"
                    )
            try:
                # connect (and hello) BEFORE encoding: the QoS prefix
                # is only valid at the negotiated revision, and a retry
                # after a reconnect may have latched a different one
                self.client.ensure_connected()
                if deadline is not None:
                    # re-derive AFTER the dial: a reconnect can eat
                    # seconds, and both the reply wait and the budget
                    # advertised on the wire must reflect what is
                    # genuinely left, not the loop-top snapshot
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        return self._expire(
                            keys, signatures, digests,
                            "deadline expired during connect",
                        )
                with fabobs.span("client.encode") as encode_span:
                    payload = self._encode(keys, signatures, digests, remaining)  # fabdet: disable=wallclock-in-det  # remaining-budget recompute before re-encode: the deadline_ms wire field is semantically time-derived by contract (masks are the det surface)
                status, retry_ms, mask, message = self._verify_once(
                    payload, remaining, encode_span
                )
            except (SidecarUnavailable, proto.ProtocolError) as exc:
                if deadline is not None and time.monotonic() >= deadline:
                    # the BUDGET, not the transport, gave out: the
                    # reply wait was derived from the remaining budget,
                    # and its expiry hands the batch back (failover/
                    # degrade) instead of parking on a dead-slow socket
                    return self._expire(keys, signatures, digests, exc)
                # a reply body that decodes to garbage (version skew,
                # truncation) is as unusable as a dead socket: degrade,
                # never let the exception escape past the mask contract
                return self._degrade(keys, signatures, digests, exc)
            if status == proto.ST_OK:
                if mask is None or len(mask) != n:
                    # a length-skewed mask is a protocol violation; never
                    # stretch or truncate verdicts to fit
                    return self._degrade(
                        keys, signatures, digests,
                        f"mask length {0 if mask is None else len(mask)} != {n}",
                    )
                fabobs.obs_count("fabric_verify_lanes_total", n, rung="serve")
                fabobs.obs_observe(
                    "fabric_verify_seconds",
                    time.perf_counter() - t0, rung="serve",
                )
                return mask
            if status == proto.ST_BUSY:
                self.busy_rejects += 1  # GIL-atomic add, stats only
                delay = bo.next_delay()
                if delay is None:
                    return self._degrade(
                        keys, signatures, digests, "admission budget spent"
                    )
                if deadline is not None:
                    # the BUSY pacing budget is capped by the request's
                    # REMAINING wire deadline: a tight-deadline batch
                    # fails over to the in-process ladder instead of
                    # sleeping its whole budget away in admission retry
                    remaining = deadline - time.monotonic()
                    if delay >= remaining:
                        return self._expire(
                            keys, signatures, digests,
                            "deadline expired during admission backoff",
                        )
                bo.sleep()
                # honor the sidecar's patience hint, but clamp it to our
                # own policy cap: retry_after_ms is a u32 off the wire and
                # must never buy a server-controlled unbounded sleep —
                # and never more of the remaining deadline than exists
                hint_s = min(retry_ms / 1000.0, self.busy_policy.cap_s)
                if deadline is not None:
                    hint_s = min(
                        hint_s, max(0.0, deadline - time.monotonic())
                    )
                if hint_s > delay:
                    self._sleeper(hint_s - delay)
                continue
            if status == proto.ST_ERROR:
                # transient per-request failure (injected fault, launch
                # error): bounded retry like BUSY, then degrade — the
                # same remaining-budget cap as the BUSY leg
                delay = bo.next_delay()
                if delay is not None and deadline is not None:
                    remaining = deadline - time.monotonic()
                    if delay >= remaining:
                        return self._expire(
                            keys, signatures, digests,
                            "deadline expired during error backoff",
                        )
                if bo.sleep():
                    continue
                return self._degrade(keys, signatures, digests, message)
            # ST_STOPPING or unknown status: the sidecar is going away
            return self._degrade(
                keys, signatures, digests, message or f"status {status}"
            )

    def batch_verify_async(self, keys, signatures, digests):
        """Pipelined dispatch: the request frame goes out NOW; the
        resolver demuxes the reply later (stage-A/B overlap through the
        socket).  Any failure at either end resolves through the same
        degrade ladder as the sync path."""
        n = len(keys)
        if n == 0:
            return list
        t0 = time.perf_counter()
        deadline = self._deadline()
        try:
            self.client.ensure_connected()
            payload = self._encode(  # fabdet: disable=wallclock-in-det  # async-submit remaining budget: deadline_ms is a semantically time-derived wire field by contract (masks are the det surface)
                keys, signatures, digests,
                None if deadline is None else deadline - time.monotonic(),
            )
            token = self.client.submit(proto.OP_VERIFY, payload)
        except (proto.ProtocolError, SidecarUnavailable) as exc:
            why = exc

            def degraded_resolve() -> List[bool]:
                return self._degrade(keys, signatures, digests, why)

            return degraded_resolve

        def resolve() -> List[bool]:
            timeout_s: Optional[float] = None
            if deadline is not None:
                timeout_s = deadline - time.monotonic()
                if timeout_s <= 0:
                    self.client.cancel(token)
                    return self._expire(
                        keys, signatures, digests,
                        "deadline expired before resolve",
                    )
            try:
                status, _, mask, _ = proto.decode_verify_response(
                    self.client.await_reply(token, timeout_s)
                )
            except (SidecarUnavailable, proto.ProtocolError) as exc:
                if deadline is not None and time.monotonic() >= deadline:
                    # the budget, not the transport, gave out: the
                    # batch is handed back to the in-process ladder
                    # and a late reply is dropped by the demux
                    return self._expire(keys, signatures, digests, exc)
                return self._degrade(keys, signatures, digests, exc)
            if status == proto.ST_OK and mask is not None and len(mask) == n:
                fabobs.obs_count("fabric_verify_lanes_total", n, rung="serve")
                fabobs.obs_observe(
                    "fabric_verify_seconds",
                    time.perf_counter() - t0, rung="serve",
                )
                return mask
            # BUSY/ERROR/STOPPING at resolve time: fall into the sync
            # path, which owns the retry/degrade ladder — on the
            # ORIGINAL budget, never a fresh one
            return self._batch_verify(keys, signatures, digests, deadline)

        return resolve

    # -- pass-through SPI --------------------------------------------------
    def verify(self, key, signature: bytes, digest: bytes) -> bool:
        return self.fallback_provider().verify(key, signature, digest)

    def batch_hash(self, msgs):
        return self.fallback_provider().batch_hash(msgs)

    def hash(self, msg: bytes) -> bytes:
        return self.fallback_provider().hash(msg)

    def key_import(self, raw: bytes):
        return self.fallback_provider().key_import(raw)

    def key_gen(self):
        return self.fallback_provider().key_gen()

    def sign(self, key, digest: bytes) -> bytes:
        return self.fallback_provider().sign(key, digest)

    def for_channel(self, channel_id: str) -> "SidecarProvider":
        """A channel-bound view of this provider: SAME pipelined
        connection and fallback, the CHANNEL's admission class (from
        the FABRIC_TPU_SERVE_QOS map) stamped on every batch — how a
        peer's per-channel validators become per-class traffic on a
        shared sidecar without a socket per channel."""
        import copy

        from fabric_tpu.serve.qos import class_for_channel, qos_map_from_env

        cls = class_for_channel(channel_id, qos_map_from_env())
        if channel_id == self.channel and cls == self.qos_class:
            return self
        bound = copy.copy(self)
        bound.channel = channel_id
        bound.qos_class = cls
        return bound

    def describe_backend(self) -> str:
        if self.degraded:
            return (
                f"serve-degraded({self.fallback_provider().describe_backend()})"
            )
        return f"serve:{self.client.address}"

    def stop(self) -> None:
        self.client.close()


def _provider_from_config(cfg: dict):
    """BCCSP factory hook: Default: SERVE -> SidecarProvider, or the
    multi-endpoint SidecarRouter when a fleet is configured
    (``SERVE.Endpoints`` or ``FABRIC_TPU_SERVE_ENDPOINTS``).  The SW
    sub-config's tier pins were already applied by the factory, so the
    in-process fallback rides the operator's chosen ladder."""
    serve_cfg = (cfg or {}).get("SERVE") or {}
    channel = serve_cfg.get("Channel") or ""
    qos_class = None
    qos_name = serve_cfg.get("QoS")
    if qos_name in proto.QOS_NAMES:
        qos_class = proto.QOS_NAMES.index(qos_name)
    endpoints = serve_cfg.get("Endpoints")
    if not endpoints:
        from fabric_tpu.serve.router import endpoints_from_env

        endpoints = endpoints_from_env() or None
    if endpoints:
        from fabric_tpu.serve.router import SidecarRouter

        return SidecarRouter(
            endpoints=endpoints, qos_class=qos_class, channel=channel
        )
    return SidecarProvider(
        address=serve_cfg.get("Address"), qos_class=qos_class, channel=channel
    )


# Dependency inversion keeps the layer map acyclic: serve (layer 6) may
# import crypto (layer 2), so the RUNG registers itself with the factory
# instead of the factory importing upward.
from fabric_tpu.crypto import factory as _factory  # noqa: E402

_factory.register_provider_factory("SERVE", _provider_from_config)
