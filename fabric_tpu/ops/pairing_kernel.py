"""Batched Ate2 pairing check on device (Idemix BBS+ structure check).

Reference semantics: idemix/signature.go:288-296 —
    Fexp( Ate(W, APrime) * Inverse(Ate(GenG2, ABar)) ).Isunity()
with W (issuer key) and GenG2 FIXED G2 points; only the G1 arguments
(A', ABar) vary per signature.

Device design (NOT a port of amcl's pairing):

- Both G2 points are fixed, so the entire Miller-loop point chain runs
  ON THE HOST once per issuer key, emitting per-step LINE COEFFICIENTS:
  l(P) = A + B·px + py with A = λ·x_T − y_T, B = −λ (Fp12 constants;
  host `_line`).  The device never touches G2/Fp12 point arithmetic —
  each Miller step is one Fp12 squaring, a 12-row scalar multiply (the
  line evaluated at P), and an Fp12 multiply, batched over signatures.
- The ISSUER key's line schedule enters the program as RUNTIME INPUTS
  (a few hundred KB of (steps, 12, NLIMBS) arrays), so ONE compiled
  program serves every issuer key per lane bucket — a fresh issuer
  costs a ~1s host schedule build, not a ~230s TPU recompile.  Only
  the generator-G2 schedule and the add-step bit mask (properties of
  the curve, not the key) stay baked as constants.  Lane buckets are
  capped (8 or 16); larger batches chunk over the cached program.
- Both pairings run in ONE lax.scan (they share the |6u+2| bit
  schedule); add-steps are selected per step by a static mask.
- The final exponentiation mirrors the host oracle op-for-op
  (conj·inv easy part, frobenius², ~1020-bit hard-part power as a
  scan), so every intermediate is differential-testable.
- The Fp12 layer is the row-stacked fabric_tpu.ops.fp12: one gather +
  one stacked Montgomery multiply per tower op, keeping the graph
  small enough for the remote TPU compiler.

Differential contract (tests/test_pairing_kernel.py): device Miller
values equal host `miller_loop` bit-for-bit; unity verdicts equal the
host oracle's on valid, corrupted, and absent inputs.
"""

from __future__ import annotations

from functools import lru_cache
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fabric_tpu.common import fp256bn as host
from fabric_tpu.ops import bignum as bn
from fabric_tpu.ops import fp12 as f12

# ---------------------------------------------------------------------------
# Host-side line precomputation (per fixed G2 point)
# ---------------------------------------------------------------------------

_SIX_U_TWO = 6 * host.U + 2
_N_BITS = bin(abs(_SIX_U_TWO))[3:]  # loop bits after the implicit MSB


# (A, B) with l(P) = A + B·px + py — shared with crypto/hostbn, which
# precomputes the same per-issuer schedules for its numpy lanes
_line_coeffs = host.line_coeffs


def _fp12_to_mont_rows(v: host.Fp12) -> np.ndarray:
    """(12, NLIMBS) uint32 Montgomery rows, order [c0.re, c0.im, ...]."""
    rows = []
    for c in v:
        rows.append(f12.to_mont_int(c[0]))  # fabtrace: disable=transfer-in-loop  # tower-bounded: 12 Fp12 coefficients per value, a trace-time constant, not lane-bounded
        rows.append(f12.to_mont_int(c[1]))  # fabtrace: disable=transfer-in-loop  # tower-bounded: 12 Fp12 coefficients per value, a trace-time constant, not lane-bounded
    return np.stack(rows).astype(np.uint32)


class LineSchedule:
    """Per-G2-point precomputed Miller lines: arrays over the scan
    steps (doubling line always; addition line + has_add for '1' bits),
    plus the two post-conjugation frobenius correction lines."""

    def __init__(self, q: host.G2Point):
        qe = host._untwist(q)
        t = qe
        dbl_a, dbl_b, add_a, add_b, has_add = [], [], [], [], []
        zero12 = _fp12_to_mont_rows(host.FP12_ZERO)
        for bit in _N_BITS:
            a, b = _line_coeffs(t, t)
            dbl_a.append(_fp12_to_mont_rows(a))  # fabtrace: disable=transfer-in-loop  # one-time per-issuer schedule precompute (scan-step bounded, cached on the pool for the key's lifetime), never per lane
            dbl_b.append(_fp12_to_mont_rows(b))  # fabtrace: disable=transfer-in-loop  # one-time per-issuer schedule precompute (scan-step bounded, cached on the pool for the key's lifetime), never per lane
            t = host._e12_add(t, t)
            if bit == "1":
                a, b = _line_coeffs(t, qe)
                add_a.append(_fp12_to_mont_rows(a))  # fabtrace: disable=transfer-in-loop  # one-time per-issuer schedule precompute (scan-step bounded, cached on the pool for the key's lifetime), never per lane
                add_b.append(_fp12_to_mont_rows(b))  # fabtrace: disable=transfer-in-loop  # one-time per-issuer schedule precompute (scan-step bounded, cached on the pool for the key's lifetime), never per lane
                has_add.append(1)
                t = host._e12_add(t, qe)
            else:
                add_a.append(zero12)
                add_b.append(zero12)
                has_add.append(0)
        assert _SIX_U_TWO < 0  # FP256BN: u negative (SIGN_OF_X)
        t = (t[0], host.fp12_neg(t[1]))
        q1 = (
            host.fp12_frobenius(qe[0], 1),
            host.fp12_frobenius(qe[1], 1),
        )
        q2 = (
            host.fp12_frobenius(qe[0], 2),
            host.fp12_neg(host.fp12_frobenius(qe[1], 2)),
        )
        corr = []
        a, b = _line_coeffs(t, q1)
        corr.append((_fp12_to_mont_rows(a), _fp12_to_mont_rows(b)))
        t = host._e12_add(t, q1)
        a, b = _line_coeffs(t, q2)
        corr.append((_fp12_to_mont_rows(a), _fp12_to_mont_rows(b)))

        self.dbl_a = np.stack(dbl_a)  # (S, 12, NLIMBS)
        self.dbl_b = np.stack(dbl_b)
        self.add_a = np.stack(add_a)
        self.add_b = np.stack(add_b)
        self.has_add = np.array(has_add, dtype=np.uint32)
        self.corr = corr


# ---------------------------------------------------------------------------
# Device evaluation
# ---------------------------------------------------------------------------


def _bcast12(p: f12.Rows) -> f12.Rows:
    """(1-row or (B,)) G1 coordinate -> (12, B) rows."""
    return tuple(
        jnp.broadcast_to(l, (12,) + l.shape[-1:]) for l in p
    )


def _line_eval(a_mat, b_mat, px12: f12.Rows, py_rows: f12.Rows, like):
    """A + B·px + py, canonical output.  a_mat/b_mat are (12, NLIMBS)
    constants (traced scan slices); px12 is the G1 x broadcast to 12
    rows; py_rows has py at row 0 and zeros elsewhere."""
    a = f12.rows_of(a_mat, like)
    b = f12.rows_of(b_mat, like)
    bp = f12.rmul(b, px12)
    out = f12.radd(f12.radd(a, bp), py_rows)  # bound 3
    return f12.rreduce(out, 2)


def _miller2(
    w_arrs,
    sched_g: LineSchedule,
    p1x: f12.Rows,
    p1y: f12.Rows,
    p2x: f12.Rows,
    p2y: f12.Rows,
    like,
):
    """Both Miller loops in one scan (shared bit schedule); returns the
    host-bit-exact Miller values for (W,P1) and (g2,P2).

    `w_arrs` is the issuer schedule as TRACED arrays (dbl_a, dbl_b,
    add_a, add_b, corr_a, corr_b) so one program serves every issuer;
    the generator schedule and the add-step mask are compile-time
    constants (the mask is a property of |6u+2|'s bits, identical for
    every schedule)."""
    w_dbl_a, w_dbl_b, w_add_a, w_add_b, w_corr_a, w_corr_b = w_arrs
    p1x12, p2x12 = _bcast12(p1x), _bcast12(p2x)
    z11 = f12.rzero(11, like)
    p1y_rows = f12.rcat(tuple(l[None] for l in p1y), z11)
    p2y_rows = f12.rcat(tuple(l[None] for l in p2y), z11)

    xs = (
        w_dbl_a,
        w_dbl_b,
        w_add_a,
        w_add_b,
        jnp.asarray(sched_g.dbl_a),
        jnp.asarray(sched_g.dbl_b),
        jnp.asarray(sched_g.add_a),
        jnp.asarray(sched_g.add_b),
        jnp.asarray(sched_g.has_add),
    )

    def body(carry, step):
        f1_st, f2_st = carry
        (wda, wdb, waa, wab, gda, gdb, gaa, gab, has_add) = step
        f1 = f12.unpack(f1_st)
        f2 = f12.unpack(f2_st)
        f1 = f12.fp12_mul(
            f12.fp12_sqr(f1),
            _line_eval(wda, wdb, p1x12, p1y_rows, like),
        )
        f2 = f12.fp12_mul(
            f12.fp12_sqr(f2),
            _line_eval(gda, gdb, p2x12, p2y_rows, like),
        )
        f1a = f12.fp12_mul(
            f1, _line_eval(waa, wab, p1x12, p1y_rows, like)
        )
        f2a = f12.fp12_mul(
            f2, _line_eval(gaa, gab, p2x12, p2y_rows, like)
        )
        cond = has_add.astype(bool)
        f1 = f12.fp12_select(cond, f1a, f1)
        f2 = f12.fp12_select(cond, f2a, f2)
        return (f12.pack(f1), f12.pack(f2)), None

    one = f12.fp12_one(like)
    one = tuple(
        jnp.broadcast_to(l, (12,) + like.shape) for l in one
    )
    (f1_st, f2_st), _ = lax.scan(
        body, (f12.pack(one), f12.pack(one)), xs
    )
    f1 = f12.fp12_conj(f12.unpack(f1_st))
    f2 = f12.fp12_conj(f12.unpack(f2_st))
    for step, (ga, gb) in enumerate(sched_g.corr):
        f1 = f12.fp12_mul(
            f1,
            _line_eval(w_corr_a[step], w_corr_b[step], p1x12, p1y_rows, like),
        )
        f2 = f12.fp12_mul(
            f2,
            _line_eval(jnp.asarray(ga), jnp.asarray(gb), p2x12, p2y_rows, like),
        )
    return f1, f2


def _final_exp(f: f12.Rows) -> f12.Rows:
    """Bit-exact mirror of host final_exp."""
    easy = f12.fp12_mul(f12.fp12_conj(f), f12.fp12_inv(f))
    easy = f12.fp12_mul(f12.fp12_frobenius(easy, 2), easy)
    return f12.fp12_pow_const(easy, host._HARD_EXP)


def _unity_check(w_arrs, sched_g, p1x, p1y, p2x, p2y, ok):
    """The jitted core: (NLIMBS, B) stacked coords -> per-lane unity
    mask of Fexp(m1 · inv(m2))."""
    like = p1x[0]

    def tup(st):
        return tuple(st[i] for i in range(bn.NLIMBS))

    f1, f2 = _miller2(
        w_arrs, sched_g, tup(p1x), tup(p1y), tup(p2x), tup(p2y), like
    )
    m = f12.fp12_mul(f1, f12.fp12_inv(f2))
    out = _final_exp(m)
    one = f12.fp12_one(like)
    one = tuple(
        jnp.broadcast_to(l, (12,) + like.shape) for l in one
    )
    return f12.fp12_equal(out, one) & ok


# lane buckets: 8 / 16 / 64; bigger batches CHUNK over the cached
# 64-lane program instead of compiling ever-larger programs (each fresh
# bucket shape is a multi-minute TPU compile). The Miller loop is a
# fixed-length scan of lane-WIDE Fp12 ops, so widening lanes raises VPU
# utilization at near-constant step count — 64 lanes amortize the
# per-launch cost vs the old 8/16 buckets (goal: device ms/sig beats an
# honest CPU column at batch >= 64; not measured on the attached chip).
_BUCKETS = (8, 16, 64)
_BUCKET_SMALL = _BUCKETS[0]
_BUCKET_MAX = _BUCKETS[-1]


@lru_cache(maxsize=1)
def _shared_fn():
    """THE pairing program (per lane-bucket shape, cached by jax): issuer
    schedule arrays are runtime inputs, so every issuer key shares it."""
    sched_g = _g2_schedule()

    def run(w_arrs, p1x, p1y, p2x, p2y, ok):
        return _unity_check(w_arrs, sched_g, p1x, p1y, p2x, p2y, ok)

    return jax.jit(run)


class Ate2Kernel:
    """Batched device evaluator of the Idemix pairing structure check
    for one issuer key W.  Construction costs one host schedule build
    (~1s of host Fp12 arithmetic); the compiled program is shared across
    ALL issuer keys per lane bucket."""

    def __init__(self, w: host.G2Point):
        self.sched_w = LineSchedule(w)
        self.sched_g = _g2_schedule()
        sw = self.sched_w
        # device-resident schedule inputs, shipped once per kernel
        self._w_arrs = tuple(
            jax.device_put(np.asarray(a))  # fabtrace: disable=transfer-in-loop  # one-time schedule shipping: 6 fixed arrays placed at pool construction, reused by every later launch
            for a in (
                sw.dbl_a,
                sw.dbl_b,
                sw.add_a,
                sw.add_b,
                np.stack([c[0] for c in sw.corr]),
                np.stack([c[1] for c in sw.corr]),
            )
        )
        self._fn = _shared_fn()
        self._sharded_fns = {}

    def check(
        self,
        pairs: Sequence[
            Optional[Tuple[host.G1Point, host.G1Point]]
        ],  # (A', ABar)
    ) -> List[bool]:
        n = len(pairs)
        if n == 0:
            return []
        # software pipeline across chunks: dispatch EVERY chunk's launch
        # before materializing any mask, so host Montgomery prep of
        # chunk k+1 overlaps device execution of chunk k and the
        # launches queue back-to-back on the accelerator
        dispatched = []
        # multi-chunk batches pad the tail to the SAME max-bucket shape
        # — a second bucket would mean a second multi-minute TPU compile
        # for lanes a few padded slots cover for free
        force = _BUCKET_MAX if n > _BUCKET_MAX else None
        for start in range(0, n, _BUCKET_MAX):
            chunk = pairs[start : start + _BUCKET_MAX]
            dispatched.append(
                (len(chunk), self._dispatch_chunk(chunk, force))
            )
        out: List[bool] = []
        for chunk_n, mask in dispatched:
            out.extend(bool(v) for v in np.asarray(mask)[:chunk_n])  # fabtrace: disable=transfer-in-loop  # chunk-granular drain (one materialization per _BUCKET_MAX-lane launch, not per lane) AFTER every launch is queued — the sync here is the pipeline's join point
        return out

    def check_sharded(self, pairs, mesh, axis: str = "data") -> List[bool]:
        """Lane-sharded pairing over a jax.sharding.Mesh (SURVEY P6):
        the per-lane Miller loop + final exponentiation have no cross-
        lane ops, so GSPMD splits the batch across the mesh's data axis
        — the multi-chip scale-out of the idemix verify column. Line
        schedules replicate (they are per-ISSUER, tiny next to the lane
        tensors); lanes pad to a bucket divisible by the axis size."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = len(pairs)
        if n == 0:
            return []
        ndev = mesh.shape[axis]
        bucket = next(
            (b for b in _BUCKETS if b >= n and b % ndev == 0),
            ((n + ndev - 1) // ndev) * ndev,
        )
        fn = self._sharded_fns.get((id(mesh), axis, bucket))
        if fn is None:
            sched_g = self.sched_g
            rep = NamedSharding(mesh, P())
            lane = NamedSharding(mesh, P(None, axis))  # (NLIMBS, B)
            mask = NamedSharding(mesh, P(axis))  # (B,)
            w_spec = tuple(rep for _ in self._w_arrs)

            def run(w_arrs, p1x, p1y, p2x, p2y, ok):
                return _unity_check(
                    w_arrs, sched_g, p1x, p1y, p2x, p2y, ok
                )

            fn = jax.jit(
                run,
                in_shardings=(w_spec, lane, lane, lane, lane, mask),
                out_shardings=rep,  # all-gather the per-shard verdicts
            )
            self._sharded_fns[(id(mesh), axis, bucket)] = fn
        cols = self._mont_cols(list(pairs), bucket)
        mask_out = fn(self._w_arrs, *cols)
        return [bool(v) for v in np.asarray(mask_out)[:n]]

    def _mont_cols(self, pairs, bucket):
        """(p1x, p1y, p2x, p2y, ok) kernel columns for `bucket` lanes."""
        gx, gy = host.G1_GEN
        cols = {"p1x": [], "p1y": [], "p2x": [], "p2y": [], "ok": []}
        for i in range(bucket):
            pair = pairs[i] if i < len(pairs) else None
            if pair is None or pair[0] is None or pair[1] is None:
                p1, p2, ok = (gx, gy), (gx, gy), False
            else:
                p1, p2, ok = pair[0], pair[1], True
            cols["p1x"].append(p1[0])
            cols["p1y"].append(p1[1])
            cols["p2x"].append(p2[0])
            cols["p2y"].append(p2[1])
            cols["ok"].append(ok)

        def mont(vals):
            return jnp.asarray(
                np.stack(
                    [f12.to_mont_int(v) for v in vals], axis=1  # fabtrace: disable=transfer-in-loop  # pairing-ingest worklist row (NOTES_BUILD PR 18): per-lane host Montgomery encode on the dispatch path — THE ingest tax the 2104.06968-style columnar refactor removes
                ).astype(np.uint32)
            )

        return (
            mont(cols["p1x"]),
            mont(cols["p1y"]),
            mont(cols["p2x"]),
            mont(cols["p2y"]),
            jnp.asarray(np.array(cols["ok"], dtype=bool)),
        )

    def _dispatch_chunk(self, pairs, force_bucket=None):
        n = len(pairs)
        bucket = force_bucket or next(b for b in _BUCKETS if n <= b)
        cols = self._mont_cols(pairs, bucket)
        # async dispatch: the mask materializes in check()'s drain
        return self._fn(self._w_arrs, *cols)


@lru_cache(maxsize=1)
def _g2_schedule() -> LineSchedule:
    return LineSchedule(host.G2_GEN)


@lru_cache(maxsize=8)
def kernel_for_issuer(w_bytes: bytes) -> Ate2Kernel:
    """Cached per-issuer kernel (W from its 128-byte amcl encoding)."""
    return Ate2Kernel(host.g2_from_bytes(w_bytes))


def miller2_host_values(
    w: host.G2Point, p1: host.G1Point, p2: host.G1Point
):
    """Test hook: device Miller values decoded to host ints (single
    lane), for bit-exact comparison with host.miller_loop."""
    k = Ate2Kernel(w)
    like = jnp.zeros((1,), dtype=jnp.uint32)

    def col(v):
        return tuple(
            jnp.asarray(np.full((1,), x, dtype=np.uint32))
            for x in f12.to_mont_int(v)
        )

    @jax.jit
    def run():
        return tuple(
            f12.pack(f)
            for f in _miller2(
                k._w_arrs, k.sched_g,
                col(p1[0]), col(p1[1]), col(p2[0]), col(p2[1]),
                like,
            )
        )

    f1_st, f2_st = run()
    return (
        f12.fp12_to_host(f12.unpack(f1_st)),
        f12.fp12_to_host(f12.unpack(f2_st)),
    )
