"""Shared lazy-reduction field-element machinery for the EC kernels.

Both device curves (P-256 for ECDSA, FP256BN for Idemix) use the same
13-bit-limb Montgomery representation and the same RCB lazy-reduction
discipline; only the modulus context differs. `Field(ctx)` binds the FE
ops to one MontCtx so the bound bookkeeping, the one-hot table select
and the point pack/unpack helpers exist exactly once
(fabric_tpu/ops/{p256_kernel,bn256_kernel} instantiate it).
"""

from __future__ import annotations

from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fabric_tpu.ops import bignum as bn


class FE(NamedTuple):
    """A field element (unpacked limbs) with a static value bound
    (value < bound * p), tracked at trace time so the lazy-reduction
    rules of the RCB formulas are machine-checked."""

    limbs: tuple
    bound: int


class Point(NamedTuple):
    x: FE
    y: FE
    z: FE


def _lane_shape(elems: Sequence[FE]) -> Tuple[int, ...]:
    return jnp.broadcast_shapes(*(jnp.shape(e.limbs[0]) for e in elems))


# A "stacked" FE holds k elements: every limb is (k, *lanes) instead of
# (*lanes).  The limb arithmetic of bignum is elementwise over the batch
# shape, so it takes stacked limbs as they are.
def _stack(elems: Sequence[FE], lanes: Tuple[int, ...]) -> FE:
    """k FEs of lane shape `lanes` -> one stacked FE whose bound is the
    largest of theirs.  A constant's limbs (numpy scalars, e.g. the
    curve's b) are broadcast to `lanes`."""
    bound = max(e.bound for e in elems)
    if isinstance(elems[0].limbs[0], np.generic) and all(
        e is elems[0] for e in elems
    ):
        # one constant k times over: its scalar limbs broadcast against
        # the other operand's rows as they are
        return FE(elems[0].limbs, bound)
    # ONE concatenation of all NLIMBS * k rows: the TPU compiler leaves
    # a concatenate unfused, so a stack per limb is NLIMBS device ops
    # (point_add compiled for a v5e: 648 executed ops against 493)
    whole = jnp.stack(
        [
            jnp.broadcast_to(jnp.asarray(e.limbs[j], jnp.uint32), lanes)
            for j in range(bn.NLIMBS)
            for e in elems
        ]
    ).reshape((bn.NLIMBS, len(elems)) + tuple(lanes))
    return FE(tuple(whole[j] for j in range(bn.NLIMBS)), bound)


def _unstack(a: FE) -> List[FE]:
    k = a.limbs[0].shape[0]
    return [FE(tuple(l[i] for l in a.limbs), a.bound) for i in range(k)]


class Field:
    def __init__(self, ctx: bn.MontCtx):
        self.ctx = ctx
        self.one_mont = bn.int_to_limbs((1 << bn.RADIX_BITS) % ctx.m)

    @staticmethod
    def fe(limbs: Sequence[jax.Array], bound: int = 1) -> FE:
        return FE(tuple(limbs), bound)

    def mul(self, a: FE, b: FE) -> FE:
        assert a.bound * b.bound <= 16, (a.bound, b.bound)
        return FE(tuple(bn.mont_mul_l(self.ctx, a.limbs, b.limbs, nreduce=1)), 1)

    def mul_many(self, pairs: Sequence[Tuple[FE, FE]]) -> List[FE]:
        """The k independent products a_i * b_i through ONE looped-CIOS
        call over a (NLIMBS, k, *lanes) accumulator: k loops of NLIMBS
        steps one after the other become one.  Every product is what
        `mul(a_i, b_i)` gives, limb for limb (same canonical operands,
        same single conditional subtraction)."""
        for a, b in pairs:
            assert a.bound * b.bound <= 16, (a.bound, b.bound)
        lanes = _lane_shape([e for pair in pairs for e in pair])
        left = _stack([a for a, _ in pairs], lanes)
        right = _stack([b for _, b in pairs], lanes)
        prod = bn.mont_mul_l(self.ctx, left.limbs, right.limbs, nreduce=1)
        return _unstack(FE(tuple(prod), 1))

    def add(self, a: FE, b: FE) -> FE:
        assert a.bound + b.bound <= 8, (a.bound, b.bound)
        return FE(tuple(bn.add_raw_l(a.limbs, b.limbs)), a.bound + b.bound)

    def sub(self, a: FE, b: FE) -> FE:
        # a - b + bound(b)*p, then conditional subtracts back to canonical.
        return FE(
            tuple(
                bn.sub_mod_l(
                    self.ctx, a.limbs, b.limbs, b.bound,
                    nreduce=a.bound + b.bound - 1,
                )
            ),
            1,
        )

    def norm(self, a: FE) -> FE:
        if a.bound == 1:
            return a
        return FE(tuple(bn.reduce_canonical_l(self.ctx, a.limbs, a.bound - 1)), 1)

    # -- points -----------------------------------------------------------
    def identity_like(self, like: jax.Array) -> Point:
        return Point(
            FE(tuple(bn.bcast_l(bn.int_to_limbs(0), like)), 1),
            FE(tuple(bn.bcast_l(self.one_mont, like)), 1),
            FE(tuple(bn.bcast_l(bn.int_to_limbs(0), like)), 1),
        )


def pack_point(p: Point):
    return (p.x.limbs, p.y.limbs, p.z.limbs)


def unpack_point(
    c: Sequence[Sequence[jax.Array]], x_bound: int = 4
) -> Point:
    # c carries canonical 13-bit limbs (the pack_point contract fabflow
    # assumes and re-proves per kernel)
    return Point(FE(tuple(c[0]), x_bound), FE(tuple(c[1]), 1), FE(tuple(c[2]), 1))


def one_hot_select(table: jax.Array, idx: jax.Array, width: int) -> Point:
    """table (width, 3, NLIMBS, B) or (width, 3, NLIMBS); idx (B,) ->
    Point. One-hot contraction — gathers lower poorly on TPU;
    multiply-accumulate over the rows fuses."""
    oh = (
        jnp.arange(width, dtype=jnp.uint32)[:, None] == idx[None, :]
    ).astype(jnp.uint32)
    if table.ndim == 4:
        sel = (table * oh[:, None, None, :]).sum(axis=0)  # (3, NLIMBS, B)
    else:
        sel = jnp.einsum("kcl,kb->clb", table, oh)
    return Point(
        FE(tuple(sel[0, i] for i in range(bn.NLIMBS)), 1),
        FE(tuple(sel[1, i] for i in range(bn.NLIMBS)), 1),
        FE(tuple(sel[2, i] for i in range(bn.NLIMBS)), 1),
    )


def stack_point_rows(p: Point) -> jax.Array:
    """Point -> (3, NLIMBS, B) stacked array (for tables/outputs)."""
    return jnp.stack(
        [bn.restack(p.x.limbs), bn.restack(p.y.limbs), bn.restack(p.z.limbs)]
    )
