"""TPUProvider bytes-path (device-side unpack + key gather) differential
vs the software oracle, including the distinct-key-bucket fallback."""

import hashlib

import pytest

from fabric_tpu.crypto import p256
from fabric_tpu.crypto.bccsp import ECDSAPublicKey, SoftwareProvider, VerifyError
from fabric_tpu.crypto.der import marshal_signature
from fabric_tpu.crypto.tpu_provider import TPUProvider

SW = SoftwareProvider()


def _cases(n, num_keys):
    keys = []
    for k in range(num_keys):
        priv = (k * 0x9E3779B97F4A7C15 + 77) % (p256.N - 1) + 1
        pub = p256.scalar_mult(priv, p256.GENERATOR)
        keys.append((priv, ECDSAPublicKey(pub[0], pub[1])))
    out = []
    for i in range(n):
        priv, key = keys[i % num_keys]
        digest = hashlib.sha256(f"bytes {i}".encode()).digest()
        kk = (i * 0xD6E8FEB86659FD93 + 3) % (p256.N - 1) + 1
        r, s = p256.sign_digest(priv, digest, k=kk)
        kind = i % 4
        if kind == 1:
            digest = hashlib.sha256(b"other").digest()
        elif kind == 2:
            sig = b"\x30\x01\x00"
            out.append((key, sig, digest))
            continue
        elif kind == 3:
            s = p256.N - s  # high-S
        out.append((key, marshal_signature(r, s), digest))
    return out


# 40 > KEY_BUCKET exercises the fallback; the in-bucket 5-key case is
# ~90s of warm device execution on the 2-vCPU gate box (NOTES_BUILD
# tier-1 budget forensics), so it is slow-marked and tier-1 keeps the
# fallback case (which loads the same programs and the same mixed-lane
# parity assertion).
@pytest.mark.parametrize(
    "num_keys", [pytest.param(5, marks=pytest.mark.slow), 40]
)
def test_bytes_path_matches_software(num_keys):
    cases = _cases(48, num_keys)
    expected = []
    for key, sig, dig in cases:
        try:
            expected.append(SW.verify(key, sig, dig))
        except VerifyError:
            expected.append(False)
    prov = TPUProvider()
    got = prov.batch_verify(
        [c[0] for c in cases], [c[1] for c in cases], [c[2] for c in cases]
    )
    assert got == expected
    assert any(expected) and not all(expected)


@pytest.mark.slow  # ~2min of warm bytes-path execution on the gate box
# (NOTES_BUILD tier-1 budget forensics); async resolver ordering stays
# covered in tier-1 by test_pipeline's channel-level async tests
def test_async_resolver_order():
    cases = _cases(40, 4)
    prov = TPUProvider()
    r1 = prov.batch_verify_async(
        [c[0] for c in cases], [c[1] for c in cases], [c[2] for c in cases]
    )
    r2 = prov.batch_verify_async(
        [c[0] for c in cases], [c[1] for c in cases], [c[2] for c in cases]
    )
    assert r1() == r2()


def test_key_columns_vectorized_matches_per_key_reference():
    """PR 18 regression (fabtrace transfer-in-loop): the key-column
    dedup now converts cache-miss keys with one vectorized
    be_bytes_to_limbs call per coordinate instead of a per-key
    int_to_limbs loop.  Columns, on-curve flags, lane indices and the
    SKI cache must match the per-key reference exactly — including an
    off-curve key, id()-deduped repeats, and a pure cache-hit pass."""
    import numpy as np

    from fabric_tpu.ops import bignum as bn

    pts = []
    acc = None
    for _ in range(4):
        acc = p256.point_add(acc, p256.GENERATOR)
        pts.append(acc)
    keys = [ECDSAPublicKey(x, y) for x, y in pts]
    keys.append(ECDSAPublicKey(12345, 67890))  # off-curve

    prov = TPUProvider.__new__(TPUProvider)  # no device/jax needed
    prov._key_limb_cache = {}
    seq = [keys[0], keys[1], keys[0], keys[4], keys[2], keys[1], keys[3]]
    kx, ky, on_curve, idx = prov._dedup_key_columns(seq)
    assert list(idx) == [0, 1, 0, 2, 3, 1, 4]
    order = [keys[0], keys[1], keys[4], keys[2], keys[3]]
    for col, key in enumerate(order):
        assert np.array_equal(kx[col], bn.int_to_limbs(key.x))
        assert np.array_equal(ky[col], bn.int_to_limbs(key.y))
        assert on_curve[col] == p256.is_on_curve((key.x, key.y))
    assert list(on_curve) == [True, True, False, True, True]
    # second pass is pure cache hits and must return identical columns
    kx2, ky2, on_curve2, idx2 = prov._dedup_key_columns(seq)
    assert list(idx2) == list(idx) and list(on_curve2) == list(on_curve)
    assert all(np.array_equal(a, b) for a, b in zip(kx, kx2))
    assert all(np.array_equal(a, b) for a, b in zip(ky, ky2))


@pytest.mark.parametrize("tier", ["fastec", "hostec_np", "hostec", "p256"])
def test_no_key_lane_is_false_on_every_ec_tier(tier):
    """A lane whose key is None (identity or SEC1 import failed upstream,
    serve NO_KEY) is a False lane — never an error that fails the
    batch's good lanes.  PR 22: on the fastec tier Provider.batch_verify
    raised AttributeError here, which failed the sidecar client's
    degrade target (and chip_smoke's oracle) closed."""
    from fabric_tpu.crypto.bccsp import (
        available_ec_backends,
        ec_backend_name,
        select_ec_backend,
    )

    if not available_ec_backends()[tier]:
        pytest.skip(f"EC tier {tier} not importable here")
    (key, sig, dig), (_, bad_sig, bad_dig) = _cases(2, 1)
    before = ec_backend_name()
    select_ec_backend(tier)
    try:
        sw = SoftwareProvider()
        keys, sigs, digs = [key, None, key], [sig, sig, bad_sig], [dig, dig, bad_dig]
        assert sw.batch_verify(keys, sigs, digs) == [True, False, False]
        assert sw.batch_verify_async(keys, sigs, digs)() == [True, False, False]
    finally:
        select_ec_backend(before)


def test_no_key_lane_is_dead_in_device_prep():
    """TPUProvider's host prep maps a None key to an off-curve column, so
    the host mask kills the lane before the kernel (PR 22: it raised
    AttributeError, and the batch degraded to software)."""
    (key, sig, dig), _ = _cases(2, 1)
    prov = TPUProvider.__new__(TPUProvider)  # no device/jax needed
    prov._key_limb_cache = {}
    prep, limbs = prov.prep_bytes([key, None, key], [sig] * 3, [dig] * 3)
    assert limbs is None
    assert list(prep[-1]) == [True, False, True]
    *_, ok = prov.prep_limbs([None, key], [sig] * 2, [dig] * 2)
    assert list(ok) == [False, True]


# ---- the first dispatch of a shape runs on a fresh stack (PR 28) ----


def _python_depth():
    import sys

    depth, frame = 0, sys._getframe()
    while frame is not None:
        depth, frame = depth + 1, frame.f_back
    return depth


def test_on_fresh_stack_runs_shallow_on_another_thread_and_hands_back():
    import threading

    from fabric_tpu.crypto.tpu_provider import _on_fresh_stack

    def deep(n):
        if n:
            return deep(n - 1)
        return _on_fresh_stack(
            lambda a, b: (a + b, _python_depth(), threading.get_ident()), 2, 3
        )

    total, depth, ident = deep(40)
    assert total == 5
    assert ident != threading.get_ident()
    # thread bootstrap, Thread.run, the wrapper, the lambda, this helper: a
    # handful of frames whatever the caller's 40
    assert depth < 10


@pytest.mark.parametrize("exc", [ValueError("lowering refused"), KeyboardInterrupt()])
def test_on_fresh_stack_raises_what_the_call_raised(exc):
    from fabric_tpu.crypto.tpu_provider import _on_fresh_stack

    def boom():
        raise exc

    with pytest.raises(type(exc)):
        _on_fresh_stack(boom)


def test_a_shape_is_lowered_once_on_another_thread_and_called_on_the_callers():
    import threading
    import types

    from fabric_tpu.crypto import tpu_provider

    provider = types.SimpleNamespace(_lowered=set())
    dispatch = tpu_provider.TPUProvider._lower_on_fresh_stack_then_call
    me = threading.get_ident()
    lowered, called = [], []

    class Program:  # what the provider uses of a jitted function
        def lower(self, x):
            lowered.append((x, threading.get_ident()))
            if x < 0:
                raise RuntimeError("lowering refused")

        def __call__(self, x):
            called.append((x, threading.get_ident()))
            return x * 2

    program = Program()
    assert dispatch(provider, program, ("bytes", 2048), 21) == 42
    assert dispatch(provider, program, ("bytes", 2048), 4) == 8
    # lowered once, for the first arguments, elsewhere; both calls here
    assert [x for x, _ in lowered] == [21] and lowered[0][1] != me
    assert called == [(21, me), (4, me)]
    # another shape is another program to trace and lower
    assert dispatch(provider, program, ("bytes", 4096), 1) == 2
    assert [x for x, _ in lowered] == [21, 1]
    # a lowering that failed raises here, lowered nothing, and is tried again
    with pytest.raises(RuntimeError):
        dispatch(provider, program, ("limbs", 128), -1)
    assert ("limbs", 128) not in provider._lowered
    assert dispatch(provider, program, ("limbs", 128), 3) == 6
    assert [x for x, _ in lowered] == [21, 1, -1, 3]
    assert all(ident != me for _, ident in lowered)
