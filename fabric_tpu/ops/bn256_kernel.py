"""Batched G1 arithmetic on the FP256BN pairing curve (the Idemix curve)
— the device half of SURVEY.md §7 Stage 5.

Reference semantics: fabric-amcl's FP256BN G1 (idemix/signature.go Ver
recomputes t1/t2/t3 via ~10 G1 scalar muls per signature). This kernel
evaluates batched multi-scalar multiplications Σ_k e_k·B_k with complete
a=0 projective formulas (Renes–Costello–Batina 2016, algorithms 7 and 9;
FP256BN has a=0, b=3), vmapped over the signature lanes, reusing the
13-bit-limb Montgomery machinery from fabric_tpu.ops.bignum with the BN
base-field modulus.

On the commit path (peer/idemix_device.py) this is the MSM program
``idemix_msm``: a block's t1/t2/t3 and nym commitments in one launch of
COMMIT_LANES lanes of MSM_K terms, beside the pairing program
(ops/pairing_kernel.py).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from fabric_tpu.common import fp256bn as host
from fabric_tpu.ops import bignum as bn
from fabric_tpu.ops import fieldops as fo

CTX_Q = bn.MontCtx(host.P)

_R = 1 << bn.RADIX_BITS
B3_MONT = bn.int_to_limbs((3 * host.B_COEFF * _R) % host.P)
ONE_MONT_Q = bn.int_to_limbs(_R % host.P)

WINDOW_BITS = 2
NUM_WINDOWS = 128  # 256 bits / 2


# Shared lazy-reduction machinery bound to the BN base-field modulus.
FIELD = fo.Field(CTX_Q)
FE = fo.FE
fe = fo.Field.fe
fe_mul = FIELD.mul
fe_mul_many = FIELD.mul_many
fe_add = FIELD.add
fe_sub = FIELD.sub
fe_norm = FIELD.norm
Point = fo.Point
point_identity_like = FIELD.identity_like

_B3_FE = FE(bn.const_l(B3_MONT), 1)


def point_add(p: Point, q: Point) -> Point:
    """Complete addition, RCB 2016 algorithm 7 (a = 0).  Its 14 products
    fall into three levels of 6, 2 and 6 independent ones; each level is
    one `fe_mul_many`, so the addition is 3 looped-CIOS calls, not 14."""
    x1, y1, z1 = p
    x2, y2, z2 = q
    b3 = _B3_FE

    t0, t1, t2, t3, t4, x3 = fe_mul_many([
        (x1, x2),
        (y1, y2),
        (z1, z2),
        (fe_add(x1, y1), fe_add(x2, y2)),
        (fe_add(y1, z1), fe_add(y2, z2)),
        (fe_add(x1, z1), fe_add(x2, z2)),
    ])
    t3 = fe_sub(t3, fe_add(t0, t1))
    t4 = fe_sub(t4, fe_add(t1, t2))
    y3 = fe_sub(x3, fe_add(t0, t2))
    x3 = fe_add(t0, t0)
    t0 = fe_add(x3, t0)  # bound 3

    t2, y3 = fe_mul_many([(b3, t2), (b3, y3)])
    z3 = fe_add(t1, t2)  # bound 2
    t1 = fe_sub(t1, t2)

    x3, t2, y3, t1, t0, z3 = fe_mul_many([
        (t4, y3), (t3, t1), (y3, t0), (t1, z3), (t0, t3), (z3, t4),
    ])
    x3 = fe_sub(t2, x3)
    y3 = fe_add(t1, y3)  # bound 2
    z3 = fe_add(z3, t0)  # bound 2
    return Point(x3, fe_norm(y3), fe_norm(z3))


def point_double(p: Point) -> Point:
    """Complete doubling, RCB 2016 algorithm 9 (a = 0): 9 products in
    three levels of 4, 1 and 4."""
    x, y, z = p
    b3 = _B3_FE

    t0, yz, t2, xy = fe_mul_many([(y, y), (y, z), (z, z), (x, y)])
    z3 = fe_add(t0, t0)
    z3 = fe_add(z3, z3)
    z3 = fe_add(z3, z3)  # bound 8

    t2 = fe_mul(b3, t2)
    y3 = fe_add(t0, t2)  # bound 2
    t1 = fe_add(t2, t2)
    t0 = fe_sub(t0, fe_add(t1, t2))

    x3, z3, y3, t1 = fe_mul_many([(t2, z3), (yz, z3), (t0, y3), (t0, xy)])
    y3 = fe_add(x3, y3)
    x3 = fe_add(t1, t1)
    return Point(fe_norm(x3), fe_norm(y3), z3)


_pack = fo.pack_point


def _unpack(c: Sequence[Sequence[jax.Array]], bound: int = 4) -> Point:
    return Point(
        fe_norm(FE(tuple(c[0]), bound)), fe(c[1]), fe(c[2])
    )


# ---------------------------------------------------------------------------
# Host <-> device packing
# ---------------------------------------------------------------------------


def to_mont_int(v: int) -> int:
    return (v * _R) % host.P


def pack_points(pts: Sequence[host.G1Point]) -> np.ndarray:
    """Affine host points (or None = identity) -> (3, NLIMBS, B) uint32
    Montgomery projective."""
    xs, ys, zs = [], [], []
    for pt in pts:
        if pt is None:
            xs.append(0)
            ys.append(to_mont_int(1))  # fabtrace: disable=transfer-in-loop  # MSM point-ingest worklist row (NOTES_BUILD PR 18): per-point host Montgomery encode pending a columnar pack over the whole batch
            zs.append(0)
        else:
            xs.append(to_mont_int(pt[0]))  # fabtrace: disable=transfer-in-loop  # MSM point-ingest worklist row (NOTES_BUILD PR 18): per-point host Montgomery encode pending a columnar pack over the whole batch
            ys.append(to_mont_int(pt[1]))  # fabtrace: disable=transfer-in-loop  # MSM point-ingest worklist row (NOTES_BUILD PR 18): per-point host Montgomery encode pending a columnar pack over the whole batch
            zs.append(to_mont_int(1))  # fabtrace: disable=transfer-in-loop  # MSM point-ingest worklist row (NOTES_BUILD PR 18): per-point host Montgomery encode pending a columnar pack over the whole batch
    return np.stack(
        [bn.ints_to_limbs(xs), bn.ints_to_limbs(ys), bn.ints_to_limbs(zs)]
    )


_R_INV = pow(_R, -1, host.P)


def unpack_points(arr: np.ndarray):
    """(3, NLIMBS, B) device output -> list of affine host points/None.
    Out of the Montgomery domain on the host: no device op, so nothing
    to compile or launch after the program itself."""
    arr = np.asarray(arr)
    xs, ys, zs = (
        [v * _R_INV % host.P for v in bn.limbs_to_ints(arr[c])]
        for c in range(3)
    )
    out = []
    for x, y, z in zip(xs, ys, zs):
        if z == 0:
            out.append(None)
        else:
            zi = pow(z, -1, host.P)
            out.append(((x * zi) % host.P, (y * zi) % host.P))
    return out


# ---------------------------------------------------------------------------
# Batched multi-scalar multiplication
# ---------------------------------------------------------------------------


def scalar_digits_msb(scalars: jax.Array) -> jax.Array:
    """(NLIMBS, B) limb scalars -> (NUM_WINDOWS, B) 2-bit digits MSB-first."""
    digits = []
    for w in range(NUM_WINDOWS):
        bit = 256 - WINDOW_BITS * (w + 1)
        limb, off = divmod(bit, bn.LIMB_BITS)
        # a 2-bit window never straddles >2 limbs with 13-bit limbs
        lo = scalars[limb] >> np.uint32(off)
        if off + WINDOW_BITS > bn.LIMB_BITS and limb + 1 < bn.NLIMBS:
            hi = scalars[limb + 1] << np.uint32(bn.LIMB_BITS - off)
            lo = lo | hi
        digits.append(lo & np.uint32((1 << WINDOW_BITS) - 1))
    return jnp.stack(digits)


def msm_batch_device(bases: jax.Array, scalars: jax.Array) -> tuple:
    """bases (K, 3, NLIMBS, B) Montgomery projective; scalars
    (K, NLIMBS, B) plain integers < R. Returns packed (3, NLIMBS, B)
    accumulator Σ_k scalars[k]·bases[k] per lane."""
    k_count, _, _, lanes = bases.shape
    lanes_like = bases[0, 0, 0]

    # per-base tables {identity, B, 2B, 3B} built as ONE flattened
    # (K*B)-lane batch (a vmapped build compiles far slower):
    flat = jnp.moveaxis(bases, 0, 2).reshape(3, bn.NLIMBS, k_count * lanes)
    p1 = Point(fe(bn.split(flat[0])), fe(bn.split(flat[1])), fe(bn.split(flat[2])))
    p2 = point_double(p1)
    p3 = point_add(p2, p1)
    ident = point_identity_like(flat[0, 0])
    rows = []
    for pt in (ident, p1, p2, p3):
        rows.append(
            jnp.stack(
                [
                    bn.restack(pt.x.limbs),
                    bn.restack(fe_norm(pt.y).limbs),
                    bn.restack(fe_norm(pt.z).limbs),
                ]
            )
        )
    # (4, 3, NLIMBS, K*B) -> (K, 4, 3, NLIMBS, B)
    tables = jnp.moveaxis(
        jnp.stack(rows).reshape(4, 3, bn.NLIMBS, k_count, lanes), 3, 0
    )
    flat_scalars = jnp.moveaxis(scalars, 0, 1).reshape(
        bn.NLIMBS, k_count * lanes
    )
    digits = scalar_digits_msb(flat_scalars).reshape(
        NUM_WINDOWS, k_count, lanes
    )

    def select(table, idx):
        return fo.one_hot_select(table, idx, 4)

    def window_body(carry, window_digits):
        acc = _unpack(carry)
        for _ in range(WINDOW_BITS):
            acc = point_double(acc)

        def base_body(j, packed):
            a = _unpack(packed)
            a = point_add(a, select(tables[j], window_digits[j]))
            return _pack(a)

        packed = lax.fori_loop(0, k_count, base_body, _pack(acc))
        return packed, None

    carry, _ = lax.scan(
        window_body, _pack(point_identity_like(lanes_like)), digits
    )
    final = _unpack(carry)
    return (
        jnp.stack([bn.restack(final.x.limbs), bn.restack(fe_norm(final.y).limbs), bn.restack(fe_norm(final.z).limbs)])
    )


def idemix_msm(bases: jax.Array, scalars: jax.Array) -> jax.Array:
    """THE MSM program: :func:`msm_batch_device` under the name its XLA
    module carries (``jit_idemix_msm``), which the benchmark's trace
    readers find it by."""
    return msm_batch_device(bases, scalars)


msm_batch_jit = jax.jit(idemix_msm)

#: Lane buckets and the term bucket: a batch is padded up to the smallest
#: lane bucket that holds it and its terms to a multiple of MSM_K, so
#: batches of different sizes land on a few shapes.  An Idemix signature's t2 has 3 + (attributes) +
#: 1 terms, 8 for the MSP's four attributes; t1, t3 and a nym signature's
#: commitment have 3.  COMMIT_LANES holds one block's 32 creators x 4.
MSM_BUCKETS = (8, 128)
MSM_K = 8
COMMIT_LANES = 128


def msm_bucket(lanes: int) -> int:
    """The smallest lane bucket that holds `lanes`; past the largest, a
    multiple of it."""
    top = MSM_BUCKETS[-1]
    return next((b for b in MSM_BUCKETS if lanes <= b), -(-lanes // top) * top)


def msm_k(terms: int) -> int:
    return max(MSM_K, -(-terms // MSM_K) * MSM_K)


def msm_program():
    """(fn, shapes_for) for ``serve/registry.py``'s
    ``BucketProgramRegistry.for_jax_program``: the MSM program at a lane
    bucket, MSM_K terms a lane."""

    def shapes_for(bucket: int):
        u32 = jnp.uint32
        return (
            jax.ShapeDtypeStruct((MSM_K, 3, bn.NLIMBS, bucket), u32),
            jax.ShapeDtypeStruct((MSM_K, bn.NLIMBS, bucket), u32),
        )

    return idemix_msm, shapes_for


def msm_pack(
    bases_per_lane: Sequence[Sequence], scalars_per_lane: Sequence[Sequence[int]],
    bucket: int, k_count: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Host arrays for `bucket` lanes of `k_count` terms: missing lanes and
    terms are identity bases with scalar 0."""
    def base(i, k):
        if i < len(bases_per_lane) and k < len(bases_per_lane[i]):
            return bases_per_lane[i][k]
        return None

    def scalar(i, k):
        if i < len(scalars_per_lane) and k < len(scalars_per_lane[i]):
            return scalars_per_lane[i][k] % host.R
        return 0

    bases = np.stack(
        [
            pack_points([base(i, k) for i in range(bucket)])  # fabtrace: disable=transfer-in-loop  # rides the pack_points MSM ingest worklist row: the per-K-column loop vectorizes together with the point encode it wraps
            for k in range(k_count)
        ]
    )
    scalars = np.stack(
        [
            bn.ints_to_limbs([scalar(i, k) for i in range(bucket)])
            for k in range(k_count)
        ]
    )
    return bases, scalars


def msm_host_batch(
    bases_per_lane: Sequence[Sequence], scalars_per_lane: Sequence[Sequence[int]]
) -> list:
    """Convenience host API: per-lane lists of (affine point | None) bases
    and int scalars. Returns affine points.  Lanes and terms are padded to
    the bucket shapes above, so a batch of another size finds the program
    already traced."""
    n = len(bases_per_lane)
    bases, scalars = msm_pack(
        bases_per_lane, scalars_per_lane, msm_bucket(n),
        msm_k(max(len(b) for b in bases_per_lane)),
    )
    out = msm_batch_jit(jnp.asarray(bases), jnp.asarray(scalars))
    return unpack_points(out)[:n]
