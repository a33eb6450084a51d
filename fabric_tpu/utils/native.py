"""ctypes bindings for the C++ host runtime (native/fabric_native.cc).

The native library accelerates the irregular byte work feeding the TPU
kernels — batched SHA-256 and strict-DER ECDSA signature parsing — and
is optional: when the shared object cannot be built or loaded every
entry point falls back to the pure-Python implementation with identical
semantics, so nothing above this module needs to care. Built with
``make -C native`` automatically, once per process, when the shared
object is missing or older than its sources.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

from fabric_tpu.common.flogging import must_get_logger

logger = must_get_logger("native")

_REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_SO_PATH = os.path.join(_REPO, "native", "libfabric_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_why_unavailable: Optional[str] = None


def _stale() -> bool:
    """The .so is missing, or older than a file it is built from — so
    what runs is always built from the files git would commit (the .so
    itself is gitignored and may linger on a disk across checkouts)."""
    native_dir = os.path.dirname(_SO_PATH)
    try:
        built = os.path.getmtime(_SO_PATH)
        return any(
            os.path.getmtime(os.path.join(native_dir, name)) > built
            for name in os.listdir(native_dir)
            if name.endswith((".cc", ".h")) or name == "Makefile"
        )
    except OSError:
        return True


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _why_unavailable
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if _stale():
            # -B: make's own rule does not list the Makefile. Safe under
            # a process that has the old .so mapped: the Makefile
            # compiles to a temp file and renames, so the mapped inode
            # is never rewritten (no SIGBUS).
            try:
                subprocess.run(
                    ["make", "-C", os.path.dirname(_SO_PATH), "-B"],
                    capture_output=True,
                    timeout=120,
                    check=True,
                )
            except Exception as exc:
                _why_unavailable = f"build failed: {exc}"
                logger.warning(
                    "native library build failed (%s); using the Python "
                    "parsers", exc,
                )
                return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as exc:
            _why_unavailable = f"load failed: {exc}"
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.fn_batch_sha256.argtypes = [u8p, u64p, u64p, ctypes.c_int64, u8p]
        lib.fn_batch_sha256.restype = None
        lib.fn_batch_der_parse.argtypes = [
            u8p, u64p, u64p, ctypes.c_int64, u8p, u8p, u8p, u8p,
        ]
        lib.fn_batch_der_parse.restype = None
        try:
            i32p = ctypes.POINTER(ctypes.c_int32)
            i64p = ctypes.POINTER(ctypes.c_int64)
            lib.fn_block_parse.argtypes = [u8p, u64p, u64p, ctypes.c_int64]
            lib.fn_block_parse.restype = ctypes.c_void_p
            lib.fn_block_counts.argtypes = [ctypes.c_void_p, i64p]
            lib.fn_block_counts.restype = None
            lib.fn_block_pertx.argtypes = [
                ctypes.c_void_p, i32p, i32p, u8p, u64p,
            ]
            lib.fn_block_pertx.restype = None
            lib.fn_block_jobs.argtypes = [
                ctypes.c_void_p, i64p, i64p, u8p, u64p, u64p, u8p,
            ]
            lib.fn_block_jobs.restype = None
            lib.fn_block_uniq.argtypes = [ctypes.c_void_p, u64p]
            lib.fn_block_uniq.restype = None
            lib.fn_block_ns.argtypes = [ctypes.c_void_p, i64p, u8p, u64p]
            lib.fn_block_ns.restype = None
            lib.fn_block_wkeys.argtypes = [
                ctypes.c_void_p, i64p, i64p, u8p, u64p, u64p,
            ]
            lib.fn_block_wkeys.restype = None
            lib.fn_block_free.argtypes = [ctypes.c_void_p]
            lib.fn_block_free.restype = None
            lib.fn_sha256_backend.restype = ctypes.c_int
        except AttributeError:
            # a library without the block parser: serve der/sha only;
            # block parsing uses the Python fallback (consumers gate on
            # hasattr)
            pass
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def why_unavailable() -> Optional[str]:
    """Why available() is False (build or load error), else None."""
    _load()
    return _why_unavailable


def _pack(chunks: Sequence[bytes]):
    lens = np.array([len(c) for c in chunks], dtype=np.uint64)
    offsets = np.zeros(len(chunks), dtype=np.uint64)
    if len(chunks) > 1:
        offsets[1:] = np.cumsum(lens[:-1])
    blob = np.frombuffer(b"".join(chunks), dtype=np.uint8)
    if blob.size == 0:
        blob = np.zeros(1, dtype=np.uint8)
    return blob, offsets, lens


def _u8(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))


def _u64(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def batch_sha256(msgs: Sequence[bytes]) -> np.ndarray:
    """(N, 32) uint8 digests."""
    n = len(msgs)
    if n == 0:
        return np.zeros((0, 32), dtype=np.uint8)
    lib = _load()
    if lib is None:
        import hashlib

        return np.frombuffer(
            b"".join(hashlib.sha256(m).digest() for m in msgs), dtype=np.uint8
        ).reshape(n, 32)
    blob, offsets, lens = _pack(msgs)
    out = np.zeros((n, 32), dtype=np.uint8)
    lib.fn_batch_sha256(
        _u8(blob), _u64(offsets), _u64(lens), n, _u8(out)
    )
    return out


def batch_der_parse(
    sigs: Sequence[bytes],
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(r[N,32], s[N,32], ok[N], low_s[N]) — ok=0 for malformed DER or
    out-of-range values; low_s mirrors utils.IsLowS (s <= n/2)."""
    n = len(sigs)
    r = np.zeros((n, 32), dtype=np.uint8)
    s = np.zeros((n, 32), dtype=np.uint8)
    ok = np.zeros(n, dtype=np.uint8)
    low_s = np.zeros(n, dtype=np.uint8)
    if n == 0:
        return r, s, ok, low_s
    lib = _load()
    if lib is None:
        from fabric_tpu.common import der, p256

        for i, sig in enumerate(sigs):
            try:
                ri, si = der.unmarshal_signature(sig)
            except Exception:
                continue
            if not (1 <= ri < p256.N and 1 <= si < p256.N):
                continue
            ok[i] = 1
            low_s[i] = 1 if p256.is_low_s(si) else 0
            r[i] = np.frombuffer(ri.to_bytes(32, "big"), dtype=np.uint8)
            s[i] = np.frombuffer(si.to_bytes(32, "big"), dtype=np.uint8)
        return r, s, ok, low_s
    blob, offsets, lens = _pack(sigs)
    lib.fn_batch_der_parse(
        _u8(blob), _u64(offsets), _u64(lens), n,
        _u8(r), _u8(s), _u8(ok), _u8(low_s),
    )
    return r, s, ok, low_s
