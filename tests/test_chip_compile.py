"""The main path's pieces compile for a DESCRIBED TPU v5e at the real
lane width (4,096) — rehearsal 3 of the on-chip-measurement guide, kept
among the tests so every later PR is checked by the chip's own compiler
at no chip time.  Nothing runs: a pass says the TPU compiler accepts the
program, nothing about results or speed.  The whole verify program
takes minutes to compile and lives in scripts/chip_compile_rehearsal.py.

Only one process may load the TPU library, and it keeps it until exit:
the topology is described inside the module-scoped fixture (never at
import, in a skipif, in a parametrize argument or in conftest.py), all
of these tests live in this one file, and nothing here starts a child.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from fabric_tpu.ops import bignum as bn
from fabric_tpu.ops import fieldops as fo
from fabric_tpu.ops import p256_kernel as pk

LANES = 4096


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-device compile is written to the persistent cache but
    can never be read back without a chip (the next run warns and
    compiles again): keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


POINT_BYTES = 3 * bn.NLIMBS * LANES * 4
LIMBS_BYTES = bn.NLIMBS * LANES * 4


def _compile(fn, *args):
    # raises what the chip's compiler would raise
    return jax.jit(fn).lower(*args).compile()


def _output_bytes(compiled):
    return compiled.memory_analysis().output_size_in_bytes


def _limbs(sharding, lanes=LANES):
    return jax.ShapeDtypeStruct((bn.NLIMBS, lanes), jnp.uint32, sharding=sharding)


def _point(sharding):
    return jax.ShapeDtypeStruct(
        (3, bn.NLIMBS, LANES), jnp.uint32, sharding=sharding
    )


def _unstack_point(rows):
    return pk._unpack_point([bn.split(rows[0]), bn.split(rows[1]), bn.split(rows[2])])


_stack_point = fo.stack_point_rows


def test_bytes_to_limbs_compiles(one_chip, no_persistent_cache):
    rows = jax.ShapeDtypeStruct((LANES, 32), jnp.uint8, sharding=one_chip)
    assert _output_bytes(_compile(pk.bytes_to_limbs_device, rows)) >= LIMBS_BYTES


def test_montgomery_multiply_compiles_in_the_chip_form(
    one_chip, no_persistent_cache
):
    def mul(a, b):
        return bn.restack(bn.mont_mul_l(pk.CTX_P, bn.split(a), bn.split(b)))

    compiled = _compile(mul, _limbs(one_chip), _limbs(one_chip))
    # the CIOS is a fori_loop -> an HLO while
    assert "while" in compiled.as_text()


def test_stacked_multiply_compiles_as_one_loop(
    one_chip, no_persistent_cache
):
    """Six products side by side (a level of a point operation) at the real
    width: one looped CIOS over (20, 6, 4096), not six."""
    k = 6
    rows = jax.ShapeDtypeStruct(
        (k, bn.NLIMBS, LANES), jnp.uint32, sharding=one_chip
    )

    def mul6(a, b):
        pairs = [(pk.fe(bn.split(a[i])), pk.fe(bn.split(b[i]))) for i in range(k)]
        return jnp.stack([bn.restack(x.limbs) for x in pk.FIELD.mul_many(pairs)])

    compiled = _compile(mul6, rows, rows)
    text = compiled.as_text()
    assert _output_bytes(compiled) >= k * LIMBS_BYTES
    # one `while` instruction, and its accumulator carries all six
    loops = [l for l in text.splitlines() if " while(" in l]
    assert len(loops) == 1, len(loops)
    assert f"u32[{bn.NLIMBS},{k},{LANES}]" in loops[0]


@pytest.mark.parametrize("op", ["point_add", "point_double"])
def test_point_ops_compile(one_chip, no_persistent_cache, op):
    if op == "point_add":
        def fn(p, q):
            return _stack_point(pk.point_add(_unstack_point(p), _unstack_point(q)))
        args = (_point(one_chip), _point(one_chip))
    else:
        def fn(p):
            return _stack_point(pk.point_double(_unstack_point(p)))
        args = (_point(one_chip),)
    assert _output_bytes(_compile(fn, *args)) >= POINT_BYTES


@pytest.mark.parametrize("table", ["per_lane_q", "shared_g"])
def test_table_select_compiles(one_chip, no_persistent_cache, table):
    shape = (16, 3, bn.NLIMBS, LANES) if table == "per_lane_q" else (16, 3, bn.NLIMBS)
    tab = jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((LANES,), jnp.uint32, sharding=one_chip)

    def fn(t, i):
        return _stack_point(pk._select_point(t, i))

    assert _output_bytes(_compile(fn, tab, idx)) >= POINT_BYTES


def test_final_comparison_compiles(one_chip, no_persistent_cache):
    valid = jax.ShapeDtypeStruct((LANES,), jnp.bool_, sharding=one_chip)

    def fn(acc, r, ok):
        return pk._final_compare(_unstack_point(acc), bn.split(r), ok)

    compiled = _compile(fn, _point(one_chip), _limbs(one_chip), valid)
    assert _output_bytes(compiled) >= LANES


def test_key_gather_compiles(one_chip, no_persistent_cache):
    """The bytes program's on-device key gather: (20, K) columns of the
    distinct keys, one column index per lane."""
    cols = _limbs(one_chip, lanes=32)  # TPUProvider.KEY_BUCKET
    idx = jax.ShapeDtypeStruct((LANES,), jnp.int32, sharding=one_chip)
    compiled = _compile(lambda k, i: jnp.take(k, i, axis=1), cols, idx)
    assert _output_bytes(compiled) >= LIMBS_BYTES
