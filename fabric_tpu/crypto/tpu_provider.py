"""The TPU-backed BCCSP provider.

Occupies the same architectural slot as the reference's out-of-process
PKCS#11 HSM provider (reference bccsp/pkcs11, SURVEY.md §2.12: "the
bccsp/tpu-equivalent provider is the analog"): single-verify API preserved,
batches collected under the hood.

Host/device split (SURVEY.md §7 Stage 1): DER parsing, the low-S rule,
range checks and key deserialization are irregular byte-twiddling and stay
on host; the double-scalar multiplication runs as one fixed-shape XLA
program per batch-size bucket. Scalars are converted bytes->limbs with
vectorized numpy (np.unpackbits), not per-int Python loops, so the host
feed path keeps up with the device.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Sequence, Set, Tuple

import numpy as np

from fabric_tpu.common.flogging import must_get_logger
from fabric_tpu.common import fabobs, p256
from fabric_tpu.crypto.bccsp import (
    ECDSAPublicKey,
    Provider,
    VerifyError,
)
from fabric_tpu.ops import bignum as bn

logger = must_get_logger("tpu_provider")

_BUCKETS = [128, 256, 512, 1024, 2048, 4096, 8192, 16384]

# stand-in for a lane with no key: (0, 0) is not on P-256
_NO_KEY = ECDSAPublicKey(0, 0)


def _on_fresh_stack(fn, *args):
    """``fn(*args)`` on a new thread's empty Python stack: its result, or
    its exception raised here.

    Tracing and lowering the verify program is deep Python recursion
    over ~10^5 equations, and CPython (3.11 on) keeps frames in 16 KiB
    data-stack chunks: where the caller's own frames put a chunk
    boundary inside that recursion's hot depth, every call across it
    allocates and frees a chunk (a million page faults in one lowering).
    Lowering the one program took 36 s from the sidecar's stack and 59,
    83 and 93 s from three peer-side stacks that differ by a few local
    variables and one frame, against 7 s from an empty one (chip host,
    PR 28) — which is what refused PR 27."""
    with ThreadPoolExecutor(
        max_workers=1, thread_name_prefix="tpu-first-dispatch"
    ) as fresh:
        return fresh.submit(fn, *args).result()


def _bucket(n: int) -> int:
    for b in _BUCKETS:
        if n <= b:
            return b
    return ((n + _BUCKETS[-1] - 1) // _BUCKETS[-1]) * _BUCKETS[-1]


def be_bytes_to_limbs(rows: np.ndarray) -> np.ndarray:
    """(B, 32) uint8 big-endian byte rows -> (20, B) uint32 13-bit limbs.

    Vectorized: unpack to bits, regroup in 13-bit windows.
    """
    b = rows.shape[0]
    # bit i (LSB-first) of the 256-bit integer
    bits = np.unpackbits(rows[:, ::-1], axis=1, bitorder="little")  # (B, 256)
    pad = np.zeros((b, bn.NLIMBS * bn.LIMB_BITS - 256), dtype=bits.dtype)
    bits = np.concatenate([bits, pad], axis=1).reshape(b, bn.NLIMBS, bn.LIMB_BITS)
    weights = (1 << np.arange(bn.LIMB_BITS, dtype=np.uint32)).astype(np.uint32)
    limbs = (bits.astype(np.uint32) * weights).sum(axis=2, dtype=np.uint32)
    return np.ascontiguousarray(limbs.T)


class TPUProvider(Provider):
    """Batched device verification with the reference's decision semantics."""

    def __init__(self):
        import jax

        from fabric_tpu.crypto.bccsp import SoftwareProvider
        from fabric_tpu.ops import p256_kernel as pk
        from fabric_tpu.utils.jaxcache import enable_compile_cache

        # every consumer of the device provider (peer/orderer processes
        # included) must hit the persistent XLA cache — a subprocess peer
        # without it recompiles the verify kernel for minutes
        enable_compile_cache()
        self._jax = jax
        self._pk = pk
        self._software = SoftwareProvider()
        self._key_limb_cache: Dict[
            bytes, Tuple[np.ndarray, np.ndarray, bool]
        ] = {}

    def _key_columns(self, distinct: Sequence[ECDSAPublicKey]):
        """(x limbs, y limbs, on_curve) per DISTINCT key, cached by SKI —
        mirrors the MSP identity cache the reference leans on (msp/cache,
        SURVEY.md §2.2). Cache misses convert in ONE vectorized
        be_bytes_to_limbs call per coordinate instead of a per-key
        int_to_limbs loop (PR 18, fabtrace transfer-in-loop). The
        on-curve gate matters: the complete-addition formulas are only
        defined for curve points, so off-curve keys must fail in the
        host mask, exactly as SoftwareProvider fails them."""
        skis = [key.ski() for key in distinct]
        missing = [
            i for i, ski in enumerate(skis)
            if ski not in self._key_limb_cache
        ]
        if missing:
            xb = np.frombuffer(
                b"".join(distinct[i].x.to_bytes(32, "big") for i in missing),
                dtype=np.uint8,
            ).reshape(len(missing), 32)
            yb = np.frombuffer(
                b"".join(distinct[i].y.to_bytes(32, "big") for i in missing),
                dtype=np.uint8,
            ).reshape(len(missing), 32)
            xl = be_bytes_to_limbs(xb)
            yl = be_bytes_to_limbs(yb)
            if len(self._key_limb_cache) > 65536:
                self._key_limb_cache.clear()
            for j, i in enumerate(missing):
                key = distinct[i]
                self._key_limb_cache[skis[i]] = (
                    np.ascontiguousarray(xl[:, j]),
                    np.ascontiguousarray(yl[:, j]),
                    p256.is_on_curve((key.x, key.y)),
                )
        return [self._key_limb_cache[ski] for ski in skis]

    # Below this count the device round-trip (and worse, a first-time XLA
    # compile) costs more than host verification; interactive paths (MSP
    # identity checks, orderer SigFilter, CLI clients) hit the single API
    # and must never wait on a kernel compile. The per-block validator
    # calls batch_verify with hundreds-to-thousands of lanes.
    MIN_DEVICE_BATCH = 32

    def verify(self, key: ECDSAPublicKey, signature: bytes, digest: bytes) -> bool:
        # SoftwareProvider already does the DER parse + low-S precheck and
        # raises VerifyError with the reference's (bool, error) semantics.
        return self._software.verify(key, signature, digest)

    def describe_backend(self) -> str:
        """"tpu", or "tpu-degraded(<host tier>)" once any dispatch has been
        served by the software fallback — so a degraded run can never be
        mistaken for a device number downstream."""
        if type(self).degraded:
            return f"tpu-degraded({self._software.describe_backend()})"
        return "tpu"

    # distinct keys are padded to a fixed column bucket so the jitted
    # program's K dimension does not recompile per block (few orgs in
    # practice; overflow falls back to full limb matrices)
    KEY_BUCKET = 32

    def batch_verify(
        self,
        keys: Sequence[ECDSAPublicKey],
        signatures: Sequence[bytes],
        digests: Sequence[bytes],
    ) -> List[bool]:
        if len(signatures) < self.MIN_DEVICE_BATCH:
            return Provider.batch_verify(
                self._software, keys, signatures, digests
            )
        return self.batch_verify_async(keys, signatures, digests)()

    # flips to True the first time a device dispatch exhausts its
    # retries and the batch is served by the software path instead —
    # consumers (bench labeling, ops /healthz) read it to tell "device
    # result" from "degraded-but-alive result"
    degraded = False

    def _sw_verify_all(
        self,
        keys: Sequence[ECDSAPublicKey],
        signatures: Sequence[bytes],
        digests: Sequence[bytes],
    ) -> List[bool]:
        if not type(self).degraded:
            fabobs.obs_count("fabric_degrade_total", seam="tpu.dispatch")
            fabobs.obs_trigger("tpu.degraded")
        type(self).degraded = True
        return Provider.batch_verify(self._software, keys, signatures, digests)

    def batch_verify_async(
        self,
        keys: Sequence[ECDSAPublicKey],
        signatures: Sequence[bytes],
        digests: Sequence[bytes],
    ):
        """Dispatch the device batch WITHOUT waiting: returns a resolver
        () -> List[bool]. Lets a pipelined caller (peer CommitPipeline,
        bench double-buffering) prep block N+1 on the single host core
        while the accelerator chews block N.

        Flake armor: dispatch errors are retried with backoff
        (FABRIC_TPU_DISPATCH_RETRIES attempts), and a batch whose
        retries exhaust is verified by the software path instead of
        raising — `degraded` latches so the result is never mistaken
        for a device result. Committers never stop committing because
        the accelerator went away."""
        n = len(signatures)
        t0 = time.perf_counter()
        with fabobs.span("tpu.prep", lanes=n) as prep_span:
            prep, limbs = self.prep_bytes(keys, signatures, digests)
            if prep_span.span_id and prep is not None and n:
                prep_span.set(distinct_keys=int(prep[5].max()) + 1)
        attempts = max(int(os.environ.get("FABRIC_TPU_DISPATCH_RETRIES", "3")), 1)
        delay = 1.0
        out = None
        for attempt in range(attempts):
            try:
                with fabobs.span("tpu.dispatch", lanes=n, bucket=_bucket(n)):
                    if prep is None:  # key-bucket overflow: limb-matrix path
                        out = self._dispatch_limbs(limbs)
                    else:
                        out = self._dispatch_bytes_or_fallback(prep)
                break
            except Exception as exc:  # noqa: BLE001 - backend init/dispatch flake
                if attempt == attempts - 1:
                    logger.warning(
                        "device dispatch failed %d time(s) (%s); "
                        "falling back to software verify", attempts, exc,
                    )
                    return lambda: self._sw_verify_all(keys, signatures, digests)
                time.sleep(delay)
                delay *= 3.0
        host_s = time.perf_counter() - t0

        def resolve() -> List[bool]:
            r0 = time.perf_counter()
            try:
                with fabobs.span("tpu.resolve", lanes=n):
                    verdicts = [bool(v) for v in np.asarray(out)[:n]]
            except Exception as exc:  # noqa: BLE001 - async error surfaces here
                logger.warning(
                    "async device result failed (%s); "
                    "falling back to software verify", exc,
                )
                return self._sw_verify_all(keys, signatures, digests)
            fabobs.obs_count("fabric_verify_lanes_total", n, rung="device")
            # the provider's own time: host prep + dispatch + this wait and
            # copy back, not the time the resolver sat waiting to be called
            fabobs.obs_observe(
                "fabric_verify_seconds",
                host_s + time.perf_counter() - r0, rung="device",
            )
            return verdicts

        return resolve

    _bytes_path_broken = False

    def _dispatch_bytes_or_fallback(self, prep):
        """The bytes kernel is the fast path; if its compile or dispatch
        fails, the limb-matrix kernel (host-side unpack and key gather)
        serves the batch. One hard failure of the bytes program itself
        disables the bytes path for the process (`_bytes_path_broken`)."""
        bytes_failed = False
        if not self._bytes_path_broken:
            try:
                return self._dispatch_bytes(prep)
            except Exception as exc:  # noqa: BLE001 - compile/dispatch failure
                logger.warning(
                    "bytes kernel failed (%s); trying the limb-matrix "
                    "fallback", exc,
                )
                bytes_failed = True
        e_bytes, r_bytes, s_bytes, kx, ky, idx, ok = prep
        qx = np.ascontiguousarray(kx[:, idx])
        qy = np.ascontiguousarray(ky[:, idx])
        out = self._dispatch_limbs(
            (
                be_bytes_to_limbs(e_bytes),
                be_bytes_to_limbs(r_bytes),
                be_bytes_to_limbs(s_bytes),
                qx,
                qy,
                ok,
            )
        )
        if bytes_failed:
            # the limb program dispatched fine, so the failure was the
            # bytes program itself (e.g. a compile refusal), not a
            # backend outage — only then is disabling it for the process
            # justified (an outage must not cost the fast path after the
            # backend recovers; the caller's retry loop handles outages)
            type(self)._bytes_path_broken = True
        return out

    def _dedup_key_columns(self, keys: Sequence[ECDSAPublicKey]):
        """One limb conversion + curve check per DISTINCT key object (the
        MSP cache reuses key objects for repeated identities), plus the
        per-lane column index. Shared by the bytes and limb paths."""
        columns: Dict[int, int] = {}
        distinct: List[ECDSAPublicKey] = []
        idx = np.zeros(len(keys), dtype=np.int32)
        for i, key in enumerate(keys):
            if key is None:
                # no key (identity/SEC1 import failed upstream, serve
                # NO_KEY): an off-curve column, so the host mask kills
                # the lane like every other provider tier does
                key = _NO_KEY
            col = columns.get(id(key))
            if col is None:
                col = len(distinct)
                columns[id(key)] = col
                distinct.append(key)
            idx[i] = col
        cols = self._key_columns(distinct)
        kx_cols = [c[0] for c in cols]
        ky_cols = [c[1] for c in cols]
        on_curve = np.asarray([c[2] for c in cols], dtype=bool)
        return kx_cols, ky_cols, on_curve, idx

    def prep_bytes(
        self,
        keys: Sequence[ECDSAPublicKey],
        signatures: Sequence[bytes],
        digests: Sequence[bytes],
    ):
        """Bytes-path host prep: DER parse + key-column dedup only; the
        byte->limb unpack and the per-lane key gather happen on device
        (p256_kernel.verify_batch_bytes_device). Returns None when the
        distinct-key count exceeds KEY_BUCKET (caller pivots to the
        limb-matrix path WITHOUT repeating this prep — see
        batch_verify_async)."""
        from fabric_tpu.utils import native

        n = len(signatures)
        r_bytes, s_bytes, ok_u8, low_s = native.batch_der_parse(signatures)
        ok = (ok_u8 & low_s).astype(bool)
        if any(len(d) != 32 for d in digests):
            raise VerifyError("digests must be 32-byte SHA-256 outputs")
        e_bytes = np.frombuffer(b"".join(digests), dtype=np.uint8).reshape(
            n, 32
        )
        kx_cols, ky_cols, on_curve, idx = self._dedup_key_columns(keys)
        if kx_cols:
            ok &= on_curve[idx]
        if len(kx_cols) > self.KEY_BUCKET:
            # too many distinct keys for the fixed column bucket: hand the
            # already-built columns to the limb-matrix path
            qx = np.stack(kx_cols, axis=1)[:, idx]
            qy = np.stack(ky_cols, axis=1)[:, idx]
            return None, (
                be_bytes_to_limbs(e_bytes),
                be_bytes_to_limbs(r_bytes),
                be_bytes_to_limbs(s_bytes),
                qx,
                qy,
                ok,
            )
        k = self.KEY_BUCKET
        kx_mat = np.zeros((bn.NLIMBS, k), dtype=np.uint32)
        ky_mat = np.zeros((bn.NLIMBS, k), dtype=np.uint32)
        if kx_cols:
            kx_mat[:, : len(kx_cols)] = np.stack(kx_cols, axis=1)
            ky_mat[:, : len(ky_cols)] = np.stack(ky_cols, axis=1)
        return (e_bytes, r_bytes, s_bytes, kx_mat, ky_mat, idx, ok), None

    def _dispatch_bytes(self, prep):
        e_bytes, r_bytes, s_bytes, kx, ky, idx, ok = prep
        n = ok.shape[0]
        size = _bucket(n)
        pad = size - n

        def padded(a):
            if pad == 0:
                return a
            widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
            return np.pad(a, widths)

        return self._lower_on_fresh_stack_then_call(
            self._pk.verify_batch_bytes_jit, ("bytes", size),
            padded(e_bytes),
            padded(r_bytes),
            padded(s_bytes),
            kx,
            ky,
            padded(idx),
            padded(ok.astype(bool)),
        )

    def _dispatch_limbs(self, limbs: Sequence[np.ndarray]):
        size = _bucket(limbs[-1].shape[0])
        return self._lower_on_fresh_stack_then_call(
            self._pk.verify_batch_jit, ("limbs", size),
            *self.pad_limbs(limbs, size),
        )

    # (program, bucket) shapes this process has traced and lowered: jit's
    # cache is process-wide, so the set is the class's
    _lowered: Set[Tuple[str, int]] = set()

    def _lower_on_fresh_stack_then_call(self, program, shape, *args):
        """Dispatch the jitted `program`.  Before the first call of a
        shape, the program is traced and lowered for it through
        :func:`_on_fresh_stack`; the call itself, here, finds both cached
        and only compiles or loads (which took five times as long on
        that thread as from a caller's stack: chip host, PR 28)."""
        if shape not in self._lowered:
            _on_fresh_stack(program.lower, *args)
            self._lowered.add(shape)
        return program(*args)

    def prep_limbs(
        self,
        keys: Sequence[ECDSAPublicKey],
        signatures: Sequence[bytes],
        digests: Sequence[bytes],
    ) -> Tuple[np.ndarray, ...]:
        """Vectorized host prep for the limb-matrix kernel (mesh and
        multi-channel paths): DER parse, byte->limb conversion and the
        deduped key-column gather, all on host. Returns the kernel-ready
        (e, r, s, qx, qy) (20, n) limb arrays + (n,) mask."""
        from fabric_tpu.utils import native

        n = len(signatures)
        r_bytes, s_bytes, ok_u8, low_s = native.batch_der_parse(signatures)
        # high-S rejected like utils.IsLowS (bccsp/sw/ecdsa.go:41-57)
        ok = (ok_u8 & low_s).astype(bool)

        if any(len(d) != 32 for d in digests):
            raise VerifyError("digests must be 32-byte SHA-256 outputs")
        e_bytes = np.frombuffer(b"".join(digests), dtype=np.uint8).reshape(
            n, 32
        )
        kx_cols, ky_cols, on_curve, idx = self._dedup_key_columns(keys)
        if kx_cols:
            qx = np.stack(kx_cols, axis=1)[:, idx]
            qy = np.stack(ky_cols, axis=1)[:, idx]
            ok &= on_curve[idx]
        else:
            qx = np.zeros((bn.NLIMBS, n), dtype=np.uint32)
            qy = np.zeros((bn.NLIMBS, n), dtype=np.uint32)
        return (
            be_bytes_to_limbs(e_bytes),
            be_bytes_to_limbs(r_bytes),
            be_bytes_to_limbs(s_bytes),
            qx,
            qy,
            ok,
        )

    @staticmethod
    def pad_limbs(
        limbs: Sequence[np.ndarray], size: int
    ) -> Tuple[np.ndarray, ...]:
        """Pad (e, r, s, qx, qy, ok) from n lanes to `size` dead lanes."""
        *arrays, ok = limbs
        pad = size - ok.shape[0]
        if pad == 0:
            return (*arrays, ok.astype(bool))
        return tuple(
            np.pad(a, [(0, 0), (0, pad)]) for a in arrays
        ) + (np.pad(ok.astype(bool), (0, pad)),)

