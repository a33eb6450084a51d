"""VerifyBatcher (SURVEY P7): cross-channel coalescing into bucketed
device launches with bounded-queue backpressure."""

import threading
import time


from conftest import requires_crypto

from fabric_tpu.parallel.batcher import VerifyBatcher


class FakeProvider:
    """Verdict = (key == b"ok"); records launch sizes."""

    def __init__(self, gate=None):
        self.launch_sizes = []
        self.gate = gate

    def batch_verify_async(self, keys, sigs, digests):
        if self.gate is not None:
            self.gate.wait()
        self.launch_sizes.append(len(keys))
        out = [k == b"ok" for k in keys]
        return lambda: out


def test_slicing_returns_each_requests_own_lanes():
    prov = FakeProvider()
    b = VerifyBatcher(prov, linger_s=0.001)
    try:
        r1 = b.submit([b"ok", b"bad"], [b"s"] * 2, [b"d"] * 2)
        r2 = b.submit([b"bad", b"ok", b"ok"], [b"s"] * 3, [b"d"] * 3)
        assert r1() == [True, False]
        assert r2() == [False, True, True]
        assert b.lanes == 5
    finally:
        b.stop()


def test_concurrent_submissions_coalesce():
    prov = FakeProvider()
    b = VerifyBatcher(prov, linger_s=0.02)
    results = {}
    try:

        def worker(i):
            n = 1 + (i % 4)
            keys = [b"ok" if (i + j) % 2 == 0 else b"no" for j in range(n)]
            results[i] = (
                keys,
                b.submit(keys, [b"s"] * n, [b"d"] * n)(),
            )

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(40)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        b.stop()

    for keys, out in results.values():
        assert out == [k == b"ok" for k in keys]
    assert len(results) == 40
    # 40 requests from 8+ racing threads must NOT mean 40 device launches
    assert b.launches < 40, prov.launch_sizes
    assert sum(prov.launch_sizes) == b.lanes


def test_backpressure_bounds_pending_lanes():
    gate = threading.Event()
    prov = FakeProvider(gate=gate)
    b = VerifyBatcher(prov, linger_s=0.0, max_pending_lanes=4)
    try:
        # dispatcher picks this up and stalls inside the provider; its
        # permits were released at dispatch
        first = b.submit([b"ok"], [b"s"], [b"d"])
        time.sleep(0.05)
        # these 4 hold every permit while queued behind the stalled launch
        second = b.submit([b"ok"] * 4, [b"s"] * 4, [b"d"] * 4)

        blocked = threading.Event()
        unblocked = threading.Event()

        def overflow():
            blocked.set()
            r = b.submit([b"ok"], [b"s"], [b"d"])
            unblocked.set()
            r()

        t = threading.Thread(target=overflow, daemon=True)
        t.start()
        assert blocked.wait(1.0)
        time.sleep(0.1)
        assert not unblocked.is_set()  # backpressured while device stalled
        gate.set()
        assert unblocked.wait(2.0)
        assert first() == [True]
        assert second() == [True] * 4
        t.join(timeout=2.0)
    finally:
        gate.set()
        b.stop()


def test_oversized_request_does_not_deadlock():
    prov = FakeProvider()
    b = VerifyBatcher(prov, linger_s=0.0, max_pending_lanes=4)
    try:
        out = b.submit([b"ok"] * 10, [b"s"] * 10, [b"d"] * 10)()
        assert out == [True] * 10
    finally:
        b.stop()


def test_stop_settles_outstanding_requests():
    prov = FakeProvider()
    b = VerifyBatcher(prov, linger_s=0.001)
    r = b.submit([b"ok"], [b"s"], [b"d"])
    b.stop()
    assert r() == [True]


@requires_crypto
def test_with_real_tpu_provider():
    """End-to-end through the device kernel: mixed-size concurrent
    requests, one verdict per lane, bit-exact vs expectations."""
    import hashlib

    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec
    from cryptography.hazmat.primitives.asymmetric.utils import (
        decode_dss_signature,
    )

    from fabric_tpu.crypto import der, p256
    from fabric_tpu.crypto.bccsp import ECDSAPublicKey
    from fabric_tpu.crypto.tpu_provider import TPUProvider

    sk = ec.generate_private_key(ec.SECP256R1())
    nums = sk.public_key().public_numbers()
    pub = ECDSAPublicKey(nums.x, nums.y)
    triples = []
    for i in range(6):
        msg = b"batcher %d" % i
        digest = hashlib.sha256(msg).digest()
        r, s = decode_dss_signature(sk.sign(msg, ec.ECDSA(hashes.SHA256())))
        if not p256.is_low_s(s):
            s = p256.N - s
        triples.append((pub, der.marshal_signature(r, s), digest))

    b = VerifyBatcher(TPUProvider(), linger_s=0.01)
    try:
        good = b.submit(
            [t[0] for t in triples],
            [t[1] for t in triples],
            [t[2] for t in triples],
        )
        bad_digest = hashlib.sha256(b"tampered").digest()
        bad = b.submit([pub], [triples[0][1]], [bad_digest])
        assert good() == [True] * 6
        assert bad() == [False]
    finally:
        b.stop()


def test_batching_provider_adapter():
    """BatchingProvider: batch paths route through the shared batcher,
    everything else passes through to the wrapped provider."""
    from fabric_tpu.parallel.batcher import BatchingProvider

    prov = FakeProvider()
    bp = BatchingProvider(prov, linger_s=0.001)
    try:
        assert bp.batch_verify([b"ok", b"no"], [b"s"] * 2, [b"d"] * 2) == [
            True,
            False,
        ]
        resolver = bp.batch_verify_async([b"ok"], [b"s"], [b"d"])
        assert resolver() == [True]
        # passthrough of non-batch attributes
        assert bp.launch_sizes == prov.launch_sizes
        assert bp.batcher.lanes == 3
    finally:
        bp.stop()


class SlowResolveProvider:
    """Fixed per-launch 'RTT' in the resolver (a high-latency launch path)."""

    def __init__(self, rtt_s):
        self.rtt_s = rtt_s
        self.launch_sizes = []

    def batch_verify_async(self, keys, sigs, digests):
        self.launch_sizes.append(len(keys))
        out = [k == b"ok" for k in keys]

        def resolve():
            time.sleep(self.rtt_s)
            return out

        return resolve


def test_rtt_autodetect_switches_to_passthrough():
    """High per-launch RTT flips the batcher to passthrough: each small
    request becomes its own launch instead of coalescing."""
    prov = SlowResolveProvider(rtt_s=0.08)  # 80ms >> 25ms threshold
    b = VerifyBatcher(prov, linger_s=0.005)
    try:
        assert b.mode == "coalesce"  # no signal yet: default
        for _ in range(4):
            b.submit([b"ok"] * 8, [b""] * 8, [b""] * 8)()
        assert b.rtt_ema_ms is not None and b.rtt_ema_ms > 30
        assert b.mode == "passthrough"
        # in passthrough, concurrent submissions do NOT merge
        prov.launch_sizes.clear()
        rs = [b.submit([b"ok"] * 8, [b""] * 8, [b""] * 8) for _ in range(3)]
        for r in rs:
            r()
        assert all(s == 8 for s in prov.launch_sizes)
    finally:
        b.stop()


def test_rtt_autodetect_stays_coalescing_when_fast():
    prov = SlowResolveProvider(rtt_s=0.0)
    b = VerifyBatcher(prov, linger_s=0.005)
    try:
        for _ in range(6):
            b.submit([b"ok"] * 8, [b""] * 8, [b""] * 8)()
        assert b.rtt_ema_ms is not None and b.rtt_ema_ms < 20
        assert b.mode == "coalesce"
    finally:
        b.stop()


def test_forced_mode_env(monkeypatch):
    monkeypatch.setenv("FABRIC_TPU_BATCHER_MODE", "passthrough")
    prov = SlowResolveProvider(rtt_s=0.0)
    b = VerifyBatcher(prov, linger_s=0.005)
    try:
        assert b.mode == "passthrough"
    finally:
        b.stop()


class HangingResolveProvider:
    """Resolver blocks until released — a wedged device."""

    def __init__(self):
        self.release = threading.Event()

    def batch_verify_async(self, keys, sigs, digests):
        def resolve():
            self.release.wait(30)
            return [True] * len(keys)

        return resolve


def test_stop_settles_hung_resolver_fail_closed():
    """stop() must not leave resolve() callers blocked behind a hung
    resolver: after the join times out, in-flight requests settle with
    all-False verdicts (fail-closed, never a guessed True)."""
    prov = HangingResolveProvider()
    b = VerifyBatcher(prov, linger_s=0.0, join_timeout_s=0.2)
    r = b.submit([b"ok", b"ok"], [b"s"] * 2, [b"d"] * 2)
    time.sleep(0.05)  # let the dispatcher pick it up and hang
    t0 = time.monotonic()
    try:
        b.stop()
        out = r()
    finally:
        prov.release.set()
    assert out == [False, False]
    assert time.monotonic() - t0 < 5


def test_stop_is_idempotent():
    prov = FakeProvider()
    b = VerifyBatcher(prov, linger_s=0.001)
    r = b.submit([b"ok"], [b"s"], [b"d"])
    b.stop()
    b.stop()  # second stop: no deadlock, no double sentinel trouble
    assert r() == [True]


def test_stop_then_submit_raises_and_leaks_nothing():
    prov = FakeProvider()
    b = VerifyBatcher(prov, linger_s=0.001)
    b.stop()
    try:
        b.submit([b"ok"], [b"s"], [b"d"])
        raised = False
    except RuntimeError:
        raised = True
    assert raised
    assert b._lanes_free == b._max_pending_lanes  # admission released
    assert not b._inflight


class FlakyDispatchProvider:
    """First dispatch attempts raise ConnectionError, then succeed —
    exercises the bounded transient retry in the dispatcher."""

    def __init__(self, failures):
        self.failures = failures
        self.attempts = 0

    def batch_verify_async(self, keys, sigs, digests):
        self.attempts += 1
        if self.attempts <= self.failures:
            raise ConnectionError("transient flap")
        out = [k == b"ok" for k in keys]
        return lambda: out


def test_dispatch_retries_transient_then_succeeds():
    from fabric_tpu.common.retry import RetryPolicy

    prov = FlakyDispatchProvider(failures=2)
    b = VerifyBatcher(
        prov,
        linger_s=0.0,
        dispatch_retry=RetryPolicy(
            base_s=0.001, multiplier=2, cap_s=0.01, deadline_s=1,
            max_attempts=3,
        ),
    )
    try:
        assert b.submit([b"ok", b"no"], [b"s"] * 2, [b"d"] * 2)() == [
            True,
            False,
        ]
        assert prov.attempts == 3
    finally:
        b.stop()


def test_dispatch_retry_budget_exhausted_propagates():
    from fabric_tpu.common.retry import RetryPolicy

    prov = FlakyDispatchProvider(failures=100)
    b = VerifyBatcher(
        prov,
        linger_s=0.0,
        dispatch_retry=RetryPolicy(
            base_s=0.001, multiplier=2, cap_s=0.01, deadline_s=1,
            max_attempts=2,
        ),
    )
    try:
        r = b.submit([b"ok"], [b"s"], [b"d"])
        try:
            r()
            raised = False
        except ConnectionError:
            raised = True
        assert raised
        assert prov.attempts == 3  # 1 try + 2 retries
    finally:
        b.stop()


def test_injected_submit_fault_fails_caller_without_leaking_lanes():
    from fabric_tpu.common.faults import FaultPlan, InjectedFault, plan_installed

    prov = FakeProvider()
    b = VerifyBatcher(prov, linger_s=0.001, max_pending_lanes=8)
    try:
        with plan_installed(FaultPlan.parse("batcher.submit=raise:1.0")):
            try:
                b.submit([b"ok"], [b"s"], [b"d"])
                raised = False
            except InjectedFault:
                raised = True
        assert raised
        assert b._lanes_free == 8  # nothing admitted, nothing leaked
        # the batcher still works after the plan is gone
        assert b.submit([b"ok"], [b"s"], [b"d"])() == [True]
    finally:
        b.stop()


def test_stop_wakes_admission_blocked_submitter():
    """A submitter blocked on lane admission (permits held by requests
    queued behind a hung dispatcher) must be released by stop() with an
    error — not wait forever on permits that will never come back."""
    prov = HangingResolveProvider()
    b = VerifyBatcher(
        prov, linger_s=0.0, max_pending_lanes=2, join_timeout_s=0.2
    )
    # dispatched immediately (permits released at dispatch), then the
    # dispatcher wedges inside the resolver
    b.submit([b"ok", b"ok"], [b"s"] * 2, [b"d"] * 2)
    time.sleep(0.05)
    # queued behind the wedge: holds both permits
    b.submit([b"ok", b"ok"], [b"s"] * 2, [b"d"] * 2)

    outcome = []

    def blocked_submit():
        try:
            b.submit([b"ok"], [b"s"], [b"d"])
            outcome.append("admitted")
        except RuntimeError:
            outcome.append("stopped")

    t = threading.Thread(target=blocked_submit, daemon=True)
    t.start()
    time.sleep(0.1)
    assert not outcome  # genuinely blocked in admission
    try:
        b.stop()
        t.join(timeout=2.0)
    finally:
        prov.release.set()
    assert outcome == ["stopped"]


class HoldFirstThenFailProvider:
    """Launch 1 blocks until released (so launch 2 queues behind it),
    launch 2 raises a hard error — the steady-state launch-failure
    path must still drain launch 1's pending resolver."""

    def __init__(self):
        self.n = 0
        self.release = threading.Event()

    def batch_verify_async(self, keys, sigs, digests):
        self.n += 1
        if self.n == 1:
            self.release.wait(5)
            out = [k == b"ok" for k in keys]
            return lambda: out
        raise ValueError("hard provider error")


def test_launch_failure_drains_pending_resolvers():
    prov = HoldFirstThenFailProvider()
    b = VerifyBatcher(prov, linger_s=0.0)
    try:
        ra = b.submit([b"ok"], [b"s"], [b"d"])
        time.sleep(0.05)  # dispatcher takes A and blocks in its launch
        rb = b.submit([b"ok"], [b"s"], [b"d"])
        prov.release.set()  # A launches; B's launch then hard-fails
        done = []
        t = threading.Thread(target=lambda: done.append(ra()), daemon=True)
        t.start()
        t.join(timeout=3.0)
        # pre-fix: A's resolver stayed pending behind the blocking
        # q.get() and this join timed out
        assert done == [[True]]
        try:
            rb()
            raised = False
        except ValueError:
            raised = True
        assert raised
    finally:
        b.stop()
