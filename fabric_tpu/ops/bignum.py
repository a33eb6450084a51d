"""Multi-limb modular arithmetic for JAX/TPU.

Replaces the Go-stdlib constant-time P-256 assembly the reference leans on
(SURVEY.md §2.12: crypto/elliptic P-256 under bccsp/sw) with batched,
compiler-friendly integer math. Design notes:

- **Radix 2^13, 20 limbs** (260 bits for 256-bit fields). 13-bit limbs make
  products fit comfortably in 32 bits (26-bit products), so a full CIOS
  Montgomery multiplication runs with *lazy carries* entirely in uint32:
  each of the 20 outer iterations adds two <2^27 products per limb, for a
  worst-case accumulator below 20 * 2^27 * (1 + eps) < 2^32.
- **Limb-unpacked representation**: between multiplications a big number
  is a *tuple of 20 arrays*, each shaped (*batch) — plain SSA values, so
  additions, subtractions and carries are one elementwise DAG XLA fuses
  freely and a carry is an ordinary data dependency. The batch dimension
  rides the VPU lanes. A multiplication stacks its operands to
  (20, *batch) for the CIOS loop (`mont_mul_l`).
- **No constant-time requirement**: verification consumes public data
  (signatures, public keys, digests), so data-dependent selects are fine —
  but never data-dependent *shapes* or control flow; everything is one
  fixed XLA program.

Stacked (NLIMBS, *batch) arrays remain the interface at kernel boundaries
(`split`/`restack` convert). Values are canonical (every limb < 2^13,
value < modulus) unless a caller tracks a laxer bound (see
fabric_tpu.ops.p256_kernel.FE).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Canonical limb parameters live in the jax-free common tier
# (fabric_tpu/common/limbparams.py) so host code and tools can use them
# without pulling in jax; re-exported here under the historical names.
from fabric_tpu.common.limbparams import (  # noqa: F401
    LIMB_BITS,
    LIMB_MASK,
    NLIMBS,
    RADIX_BITS,
)

# A big number inside a kernel: tuple of NLIMBS arrays, each (*batch).
LimbVec = Tuple[jax.Array, ...]


# ---------------------------------------------------------------------------
# Host conversions
# ---------------------------------------------------------------------------


def int_to_limbs(x: int, nlimbs: int = NLIMBS) -> np.ndarray:
    """Python int -> little-endian 13-bit limbs, shape (nlimbs,) uint32."""
    if x < 0:
        raise ValueError("negative")
    out = np.zeros(nlimbs, dtype=np.uint32)
    for i in range(nlimbs):
        out[i] = x & LIMB_MASK
        x >>= LIMB_BITS
    if x:
        raise ValueError("value does not fit in limbs")
    return out


def ints_to_limbs(xs, nlimbs: int = NLIMBS) -> np.ndarray:
    """Batch of ints -> (nlimbs, B) uint32 (limb-major)."""
    out = np.zeros((nlimbs, len(xs)), dtype=np.uint32)
    for j, x in enumerate(xs):
        out[:, j] = int_to_limbs(x, nlimbs)
    return out


def limbs_to_int(a) -> int:
    """(nlimbs,) limbs -> Python int."""
    a = np.asarray(a)
    val = 0
    for i in range(a.shape[0] - 1, -1, -1):
        val = (val << LIMB_BITS) | int(a[i])
    return val


def limbs_to_ints(a) -> list:
    """(nlimbs, B) -> list of B Python ints."""
    a = np.asarray(a)
    return [limbs_to_int(a[:, j]) for j in range(a.shape[1])]


# ---------------------------------------------------------------------------
# Packing between stacked arrays and unpacked limb tuples
# ---------------------------------------------------------------------------


def split(x: jax.Array) -> LimbVec:
    """(NLIMBS, *batch) -> tuple of NLIMBS (*batch) arrays."""
    return tuple(x[i] for i in range(x.shape[0]))

def restack(xs: Sequence[jax.Array]) -> jax.Array:
    return jnp.stack(tuple(xs), axis=0)


# ---------------------------------------------------------------------------
# Carry propagation (pure data-dependency chains; fusion-friendly)
# ---------------------------------------------------------------------------


def carry_l(xs: Sequence[jax.Array]) -> Tuple[List[jax.Array], jax.Array]:
    """Carry-propagate a limb list (uint32 or int32; the arithmetic shift
    on int32 makes negative limbs borrow). Returns (canonical limbs,
    carry_out)."""
    out = []
    c = None
    for x in xs:
        t = x if c is None else x + c
        c = t >> LIMB_BITS
        out.append(t & LIMB_MASK)
    return out, c


def carry_u32(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    ys, c = carry_l(split(x))
    return restack(ys), c


carry_i32 = carry_u32  # dtype decides signedness; same chain


# ---------------------------------------------------------------------------
# Montgomery context
# ---------------------------------------------------------------------------


class MontCtx:
    """Precomputed Montgomery constants for an odd modulus m < 2^256.

    R = 2^260 (one limb-width above 256 bits). Per-limb constants are
    numpy uint32/int32 *scalars* so they enter traces as broadcastable
    XLA constants.
    """

    def __init__(self, modulus: int):
        if modulus % 2 == 0:
            raise ValueError("modulus must be odd")
        self.m = modulus
        r = 1 << RADIX_BITS
        self.m_limbs = int_to_limbs(modulus)
        self.m_scalars_i32 = tuple(np.int32(v) for v in self.m_limbs)
        self.r2_limbs = int_to_limbs((r * r) % modulus)
        self.one_mont = int_to_limbs(r % modulus)
        self.one = int_to_limbs(1)
        # m' = -m^-1 mod 2^13 for the REDC quotient digit.
        self.m0inv = np.uint32((-pow(modulus, -1, 1 << LIMB_BITS)) % (1 << LIMB_BITS))
        # k*m as int32 per-limb scalars, for borrow-free subtraction.
        self.km_scalars_i32 = {
            k: tuple(np.int32(v) for v in int_to_limbs(k * modulus))
            for k in range(1, 9)
        }

    def const(self, value_limbs: np.ndarray) -> Tuple[np.uint32, ...]:
        return tuple(np.uint32(v) for v in value_limbs)


def cond_sub_l(ctx: MontCtx, xs: Sequence[jax.Array]) -> List[jax.Array]:
    """One conditional subtract: x - m if x >= m else x (limbs canonical)."""
    d = [x.astype(jnp.int32) - mj for x, mj in zip(xs, ctx.m_scalars_i32)]
    limbs, c = carry_l(d)
    keep = c < 0  # borrow out -> x < m
    return [jnp.where(keep, x, l.astype(jnp.uint32)) for x, l in zip(xs, limbs)]


def reduce_canonical_l(ctx: MontCtx, xs: Sequence[jax.Array], times: int) -> List[jax.Array]:
    xs = list(xs)
    for _ in range(times):
        xs = cond_sub_l(ctx, xs)
    return xs


# ---------------------------------------------------------------------------
# Core multiply (CIOS Montgomery, lazy carries)
#
# The outer i-loop is a lax.fori_loop whose body is ~10 vector ops on a
# stacked (NLIMBS, B) accumulator; the inner j-loop is vectorized over
# the limb axis.  Compiled for a v5e the loop is 3-4 fusions, a pad and
# the counter a step, accumulator and operands in the on-chip vector
# memory (`S(1)`), the same program text at 2,048, 4,096 and 6 x 4,096
# lanes.  On the chip (PR 31) a step over 6 x 2,048 lanes costs what a
# step over 2,048 does (~1.4 us: the fixed cost of issuing its ops, not
# their width) and twice that over 6 x 4,096, so callers hand independent
# products to one call (fieldops.Field.mul_many) rather than loop once
# per product.
# ---------------------------------------------------------------------------


def mont_mul_l(
    ctx: MontCtx,
    a: Sequence[jax.Array],
    b: Sequence[jax.Array],
    nreduce: int = 1,
) -> List[jax.Array]:
    """Montgomery product a*b*R^-1 mod m on canonical-limb inputs.

    Values may be up to 4m; with inputs <= c1*m, c2*m the pre-reduction
    output is < m*(1 + c1*c2*m/2^260), so nreduce=1 suffices for
    c1*c2 <= 16.
    """
    from jax import lax

    batch = jnp.broadcast_shapes(
        *(jnp.shape(x) for x in a), *(jnp.shape(y) for y in b)
    )
    a_s = jnp.stack(tuple(jnp.broadcast_to(jnp.asarray(x), batch) for x in a))
    b_s = jnp.stack(tuple(jnp.broadcast_to(jnp.asarray(y), batch) for y in b))
    m_s = jnp.asarray(ctx.m_limbs, dtype=jnp.uint32).reshape(
        (NLIMBS,) + (1,) * len(batch)
    )
    m0inv = ctx.m0inv

    # Static headroom proof (mechanized by tools/fabflow, which unrolls
    # lax.fori_loop(0, NLIMBS) over this very body): with canonical
    # 13-bit limbs, each step adds at most ai*b[j] + q*m_j <= 2 * 8191^2
    # = 134184962 < 2^27 per limb, plus the shifted-down carry (<= 2^19).
    # fabflow keeps one interval for the whole stacked accumulator, so it
    # charges every limb the carry; its 20-step worst case is 2687141695
    # < 0.626 * 2^32 < 2^32 - 1, so the uint32 lazy-carry accumulator can
    # never wrap.  Adding ONE more accumulation term per step (e.g. a
    # third product) would push the bound to ~0.94 * 2^32, a second one
    # past 2^32 — the gate recomputes this on every change.
    def body(i, t):
        ai = a_s[i]
        t0 = t[0] + ai * b_s[0]
        q = ((t0 & LIMB_MASK) * m0inv) & LIMB_MASK
        carry0 = (t0 + q * m_s[0]) >> LIMB_BITS
        # u_j for j=1..19, shifted down one limb; u_0's low bits vanish.
        nt = t[1:] + ai * b_s[1:] + q * m_s[1:]
        nt = nt.at[0].add(carry0)
        return jnp.concatenate([nt, jnp.zeros_like(t[:1])])

    t = lax.fori_loop(
        0, NLIMBS, body, jnp.zeros_like(a_s), unroll=False
    )
    limbs, _ = carry_l(split(t))  # value < 2m for canonical inputs; carry_out 0
    return reduce_canonical_l(ctx, limbs, nreduce)


def add_raw_l(a: Sequence[jax.Array], b: Sequence[jax.Array]) -> List[jax.Array]:
    """Limb-canonical addition WITHOUT modular reduction (value = a+b)."""
    limbs, _ = carry_l([x + y for x, y in zip(a, b)])
    return limbs


def sub_mod_l(
    ctx: MontCtx,
    a: Sequence[jax.Array],
    b: Sequence[jax.Array],
    b_bound: int,
    nreduce: int,
) -> List[jax.Array]:
    """a - b + b_bound*m, carried in int32 (no borrow underflow), reduced
    with `nreduce` conditional subtracts."""
    kp = ctx.km_scalars_i32[b_bound]
    d = [
        x.astype(jnp.int32) + kpj - y.astype(jnp.int32)
        for x, y, kpj in zip(a, b, kp)
    ]
    limbs, _ = carry_l(d)
    return reduce_canonical_l(ctx, [l.astype(jnp.uint32) for l in limbs], nreduce)


def const_l(limbs: np.ndarray) -> Tuple[np.uint32, ...]:
    """A compile-time constant as broadcastable per-limb scalars."""
    return tuple(np.uint32(v) for v in limbs)


def bcast_l(limbs: np.ndarray, like: jax.Array) -> List[jax.Array]:
    """A constant materialized at `like`'s batch shape."""
    return [jnp.full(like.shape, np.uint32(v), dtype=jnp.uint32) for v in limbs]


def to_mont_l(ctx: MontCtx, xs: Sequence[jax.Array], nreduce: int = 1) -> List[jax.Array]:
    return mont_mul_l(ctx, xs, const_l(ctx.r2_limbs), nreduce=nreduce)


def from_mont_l(ctx: MontCtx, xs: Sequence[jax.Array]) -> List[jax.Array]:
    return mont_mul_l(ctx, xs, const_l(ctx.one))


def mont_pow_l(ctx: MontCtx, xs: Sequence[jax.Array], exponent: int) -> List[jax.Array]:
    """x^exponent in the Montgomery domain.

    Branch-free fixed-window form: scan over static 2-bit exponent digits
    (MSB-first); each step squares twice and multiplies by a selected
    entry of {1, x, x^2, x^3}. 384 multiplies for a 256-bit exponent —
    the same count as optimal square-and-multiply — while keeping the
    traced graph small (the scan body traces once).
    """
    from jax import lax

    nbits = exponent.bit_length()
    ndigits = (nbits + 1) // 2
    digits = np.array(
        [(exponent >> (2 * (ndigits - 1 - i))) & 3 for i in range(ndigits)],
        dtype=np.int32,
    )
    x1 = list(xs)
    x2 = mont_mul_l(ctx, x1, x1)
    x3 = mont_mul_l(ctx, x2, x1)
    one = const_l(ctx.one_mont)
    # table[d][j]: limb j of the digit-d multiplier, materialized (4, B)
    table = [jnp.stack([jnp.broadcast_to(one[j], x1[j].shape), x1[j], x2[j], x3[j]])
             for j in range(NLIMBS)]
    acc0 = [jnp.broadcast_to(jnp.asarray(one[j]), x1[j].shape) for j in range(NLIMBS)]

    def body(acc, d):
        acc = list(acc)
        acc = mont_mul_l(ctx, acc, acc)
        acc = mont_mul_l(ctx, acc, acc)
        mult = [t[d] for t in table]
        return tuple(mont_mul_l(ctx, acc, mult)), None

    acc, _ = lax.scan(body, tuple(acc0), jnp.asarray(digits))
    return list(acc)


def eq_l(a: Sequence[jax.Array], b: Sequence[jax.Array]) -> jax.Array:
    out = None
    for x, y in zip(a, b):
        e = x == y
        out = e if out is None else (out & e)
    return out


def is_zero_l(a: Sequence[jax.Array]) -> jax.Array:
    out = None
    for x in a:
        e = x == 0
        out = e if out is None else (out & e)
    return out


# ---------------------------------------------------------------------------
# Stacked-array wrappers (interface / test convenience)
# ---------------------------------------------------------------------------


def mont_mul(ctx: MontCtx, a: jax.Array, b: jax.Array, nreduce: int = 1) -> jax.Array:
    return restack(mont_mul_l(ctx, split(a), split(b), nreduce))


def add_raw(a: jax.Array, b: jax.Array) -> jax.Array:
    return restack(add_raw_l(split(a), split(b)))


def sub_mod(ctx: MontCtx, a: jax.Array, b: jax.Array, b_bound: int, nreduce: int) -> jax.Array:
    return restack(sub_mod_l(ctx, split(a), split(b), b_bound, nreduce))


def to_mont(ctx: MontCtx, x: jax.Array, nreduce: int = 1) -> jax.Array:
    return restack(to_mont_l(ctx, split(x), nreduce))


def from_mont(ctx: MontCtx, x: jax.Array) -> jax.Array:
    return restack(from_mont_l(ctx, split(x)))


def mont_pow(ctx: MontCtx, x: jax.Array, exponent: int) -> jax.Array:
    return restack(mont_pow_l(ctx, split(x), exponent))


def reduce_canonical(x: jax.Array, ctx: MontCtx, times: int) -> jax.Array:
    return restack(reduce_canonical_l(ctx, split(x), times))


def cond_sub(x: jax.Array, ctx: MontCtx) -> jax.Array:
    return restack(cond_sub_l(ctx, split(x)))


def eq_limbs(a: jax.Array, b: jax.Array) -> jax.Array:
    return eq_l(split(a), split(b))


def is_zero(a: jax.Array) -> jax.Array:
    return is_zero_l(split(a))
