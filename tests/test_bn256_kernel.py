"""Differential tests: batched FP256BN G1 kernel vs the host oracle
(fabric_tpu.crypto.fp256bn)."""

import secrets

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fabric_tpu.crypto import fp256bn as host
from fabric_tpu.ops import bignum as bn
from fabric_tpu.ops import bn256_kernel as bk


def rand_scalar():
    return secrets.randbelow(host.R - 1) + 1


def rand_point():
    return host.g1_mul(host.G1_GEN, rand_scalar())


def _rows_to_point(rows):
    return bk.Point(
        bk.fe(bn.split(rows[0])), bk.fe(bn.split(rows[1])), bk.fe(bn.split(rows[2]))
    )


def _point_to_rows(r):
    return jnp.stack(
        [
            bn.restack(bk.fe_norm(r.x).limbs),
            bn.restack(bk.fe_norm(r.y).limbs),
            bn.restack(bk.fe_norm(r.z).limbs),
        ]
    )


class TestPointOps:
    """The complete formulas' edge cases ride in the lanes of one batch a
    formula (6 lanes for the addition, 3 for the doubling): one XLA:CPU
    compile each, whatever cases the lanes hold."""

    def test_add_matches_host(self):
        p, q = rand_point(), rand_point()
        cases = {
            "P+Q": (rand_point(), rand_point()),
            "P+P": (p, p),
            "P+(-P)": (q, host.g1_neg(q)),
            "O+O": (None, None),
            "O+P": (None, host.G1_GEN),
            "P+O": (p, None),
        }
        want = {
            "P+P": host.g1_mul(p, 2),
            "P+(-P)": None,
            "O+O": None,
            "O+P": host.G1_GEN,
            "P+O": p,
        }
        a = bk.pack_points([pq[0] for pq in cases.values()])
        b = bk.pack_points([pq[1] for pq in cases.values()])

        @jax.jit
        def add(a, b):
            return _point_to_rows(bk.point_add(_rows_to_point(a), _rows_to_point(b)))

        got = bk.unpack_points(add(jnp.asarray(a), jnp.asarray(b)))
        for (name, (p_, q_)), g in zip(cases.items(), got):
            assert g == host.g1_add(p_, q_), name
            if name in want:
                assert g == want[name], name

    def test_double_matches_host_incl_identity(self):
        p = rand_point()
        cases = {"2P": p, "2G": host.G1_GEN, "2O": None}
        want = {"2P": host.g1_mul(p, 2), "2G": host.g1_mul(host.G1_GEN, 2), "2O": None}
        a = bk.pack_points(list(cases.values()))

        @jax.jit
        def dbl(a):
            return _point_to_rows(bk.point_double(_rows_to_point(a)))

        got = bk.unpack_points(dbl(jnp.asarray(a)))
        for (name, p_), g in zip(cases.items(), got):
            assert g == host.g1_add(p_, p_) == want[name], name


def _montgomery_loops(jaxpr) -> int:
    """The looped-CIOS calls a run of `jaxpr` makes: every innermost loop
    once for each trip of the loops around it (a `fori_loop` with static
    bounds traces as a `scan` of that length)."""
    n = 0
    for eqn in jaxpr.eqns:
        subs = jax.core.jaxprs_in_params(eqn.params)
        inner = sum(_montgomery_loops(sub) for sub in subs)
        if eqn.primitive.name == "scan":
            n += eqn.params["length"] * inner if inner else 1
        elif eqn.primitive.name == "while":
            assert not inner, "a while loop around a loop has no static trip count"
            n += 1
        else:
            n += inner
    return n


class TestLoopCounts:
    """The engagement proof of the stacked multiplies (`Field.mul_many`),
    decided at trace time: a point operation traces 3 Montgomery loops (it
    traced 14 and 9), an MSM window of MSM_K terms 2 x 3 + 8 x 3 = 30 (it
    traced 130).  Trace only: nothing is compiled or run."""

    LANES = 8

    def _point(self):
        return jax.ShapeDtypeStruct((3, bn.NLIMBS, self.LANES), jnp.uint32)

    @pytest.mark.parametrize("op,want", [("point_add", 3), ("point_double", 3)])
    def test_point_op_traces_three_loops(self, op, want):
        if op == "point_add":
            fn = lambda p, q: _point_to_rows(bk.point_add(_rows_to_point(p), _rows_to_point(q)))
            args = (self._point(), self._point())
        else:
            fn = lambda p: _point_to_rows(bk.point_double(_rows_to_point(p)))
            args = (self._point(),)
        assert _montgomery_loops(jax.make_jaxpr(fn)(*args).jaxpr) == want

    def test_msm_window_traces_thirty_loops(self):
        bases = jax.ShapeDtypeStruct((bk.MSM_K, 3, bn.NLIMBS, self.LANES), jnp.uint32)
        scalars = jax.ShapeDtypeStruct((bk.MSM_K, bn.NLIMBS, self.LANES), jnp.uint32)
        jaxpr = jax.make_jaxpr(bk.msm_batch_device)(bases, scalars).jaxpr
        windows = [
            e for e in jaxpr.eqns
            if e.primitive.name == "scan" and e.params["length"] == bk.NUM_WINDOWS
        ]
        assert len(windows) == 1
        (body,) = jax.core.jaxprs_in_params(windows[0].params)
        assert bk.MSM_K == 8
        assert _montgomery_loops(body) == 30


class TestMSM:
    """All cases share ONE (K=4, B=4) shape — every distinct shape is a
    multi-minute XLA compile; identity bases with zero scalars pad the
    smaller cases."""

    K, B = 4, 4

    def _run(self, cases):
        """cases: list of (bases, scalars) with len <= K; padded to (K,B)."""
        while len(cases) < self.B:
            cases.append(([], []))
        bases, scalars = [], []
        for bs, es in cases:
            bs = list(bs) + [None] * (self.K - len(bs))
            es = list(es) + [0] * (self.K - len(es))
            bases.append(bs)
            scalars.append(es)
        got = bk.msm_host_batch(bases, scalars)
        want = []
        for bs, es in zip(bases, scalars):
            acc = None
            for b, e in zip(bs, es):
                acc = host.g1_add(acc, host.g1_mul(b, e % host.R))
            want.append(acc)
        assert got == want

    def test_single_base_matches_scalar_mul(self):
        self._run([([rand_point()], [rand_scalar()]) for _ in range(self.B)])

    def test_multi_base_matches_host_sum(self):
        self._run(
            [
                (
                    [rand_point() for _ in range(self.K)],
                    [rand_scalar() for _ in range(self.K)],
                )
                for _ in range(self.B)
            ]
        )

    def test_edge_scalars_and_identity_base(self):
        self._run(
            [
                ([host.G1_GEN, None], [0, 5]),
                ([host.G1_GEN, host.G1_GEN], [1, host.R - 1]),  # R·G = O
                ([None, None], [3, 7]),
                ([rand_point(), host.G1_GEN], [host.R - 1, 2]),
            ]
        )
