"""C++ host runtime (native/fabric_native.cc via ctypes): batched
SHA-256 and strict-DER signature parsing, differential against the
pure-Python implementations (which are also the fallback path)."""

import hashlib
import secrets

import numpy as np
import pytest

from fabric_tpu.crypto import der, p256
from fabric_tpu.utils import native


def test_native_library_builds_and_loads():
    # the toolchain is part of the environment contract; if this fails
    # the fallbacks still work but we want to know
    assert native.available()


def test_batch_sha256_differential():
    msgs = [secrets.token_bytes(n) for n in (0, 1, 31, 55, 56, 63, 64, 65, 1000, 10000)]
    got = native.batch_sha256(msgs)
    assert got.shape == (len(msgs), 32)
    for m, g in zip(msgs, got):
        assert bytes(g) == hashlib.sha256(m).digest()
    assert native.batch_sha256([]).shape == (0, 32)


def test_batch_der_parse_valid_signatures():
    sigs, want = [], []
    for _ in range(100):
        r = secrets.randbelow(p256.N - 1) + 1
        s = secrets.randbelow(p256.N - 1) + 1
        sigs.append(der.marshal_signature(r, s))
        want.append((r, s, p256.is_low_s(s)))
    r_arr, s_arr, ok, low = native.batch_der_parse(sigs)
    for i, (r, s, lows) in enumerate(want):
        assert ok[i] == 1
        assert int.from_bytes(bytes(r_arr[i]), "big") == r
        assert int.from_bytes(bytes(s_arr[i]), "big") == s
        assert bool(low[i]) == lows


@pytest.mark.parametrize(
    "bad",
    [
        b"",
        b"\x30\x02\x02\x00",
        b"\xff" * 16,
        der.marshal_signature(5, 7)[:-1],  # truncated
        # non-minimal integer: leading zero before a low byte
        b"\x30\x08\x02\x02\x00\x05\x02\x02\x00\x07",
    ],
)
def test_batch_der_parse_rejects_malformed(bad):
    _, _, ok, _ = native.batch_der_parse([bad])
    assert ok[0] == 0


def test_batch_der_parse_tolerates_trailing_bytes():
    """The Go asn1 quirk der.py documents: extra bytes after the SEQUENCE
    are tolerated. BOTH parsers must accept, or peers diverge."""
    sig = der.marshal_signature(5, 7) + b"\x00\xff"
    _, _, ok, _ = native.batch_der_parse([sig])
    assert ok[0] == 1
    assert der.unmarshal_signature(sig) == (5, 7)


def test_der_fuzz_native_matches_python():
    """Random valid signatures with random byte mutations: the native
    parser's accept/reject + values must equal the Python reference."""
    import random

    rng = random.Random(1234)
    cases = []
    for _ in range(400):
        r = rng.randrange(1, p256.N)
        s = rng.randrange(1, p256.N)
        sig = bytearray(der.marshal_signature(r, s))
        mutations = rng.randrange(0, 3)
        for _ in range(mutations):
            kind = rng.randrange(3)
            if kind == 0 and sig:
                sig[rng.randrange(len(sig))] = rng.randrange(256)
            elif kind == 1:
                sig = sig[: rng.randrange(len(sig) + 1)]
            else:
                sig += bytes([rng.randrange(256)])
        cases.append(bytes(sig))

    r_arr, s_arr, ok, _ = native.batch_der_parse(cases)
    for i, sig in enumerate(cases):
        try:
            rr, ss = der.unmarshal_signature(sig)
            py_ok = 1 <= rr < p256.N and 1 <= ss < p256.N
        except der.DerError:
            py_ok = False
            rr = ss = None
        assert bool(ok[i]) == py_ok, (i, sig.hex())
        if py_ok:
            assert int.from_bytes(bytes(r_arr[i]), "big") == rr, sig.hex()
            assert int.from_bytes(bytes(s_arr[i]), "big") == ss, sig.hex()


def test_batch_der_parse_rejects_out_of_range():
    zero_s = der.marshal_signature(5, p256.N)  # s == n
    _, _, ok, _ = native.batch_der_parse([zero_s])
    assert ok[0] == 0


def test_der_parse_matches_python_fallback():
    """The C++ parser and the Python fallback must agree bit-for-bit on a
    mixed batch (the fallback is what runs without the toolchain)."""
    sigs = []
    for i in range(50):
        r = secrets.randbelow(p256.N - 1) + 1
        s = secrets.randbelow(p256.N - 1) + 1
        sigs.append(der.marshal_signature(r, s))
    sigs += [b"", b"\x30\x01\x00", secrets.token_bytes(20)]

    native_out = native.batch_der_parse(sigs)

    # force the fallback by simulating a missing library
    saved = native._lib, native._tried
    native._lib, native._tried = None, True
    try:
        fallback_out = native.batch_der_parse(sigs)
    finally:
        native._lib, native._tried = saved

    for a, b in zip(native_out, fallback_out):
        assert np.array_equal(a, b)


def test_stale_when_missing_or_older_than_any_source(tmp_path, monkeypatch):
    """PR 22: the .so is (re)built when it is missing or older than any
    of native/*.cc, native/*.h, native/Makefile — what runs is built
    from the files git would commit, not from a library that lingers on
    a disk."""
    import os

    so = tmp_path / "libfabric_native.so"
    monkeypatch.setattr(native, "_SO_PATH", str(so))
    assert native._stale()  # missing
    for name in ("a.cc", "b.h", "Makefile", "notes.txt"):
        (tmp_path / name).write_text("x")
        os.utime(tmp_path / name, (1000, 1000))
    so.write_text("so")
    os.utime(so, (2000, 2000))
    assert not native._stale()
    os.utime(tmp_path / "notes.txt", (3000, 3000))  # not a source
    assert not native._stale()
    for name in ("a.cc", "b.h", "Makefile"):
        os.utime(tmp_path / name, (3000, 3000))
        assert native._stale(), name
        os.utime(tmp_path / name, (1000, 1000))
    assert native.why_unavailable() is None or not native.available()
