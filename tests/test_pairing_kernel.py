"""Device Ate2 pairing (ops/pairing_kernel.py) vs the host oracle
(crypto/fp256bn.py): tower ops bit-exact, Miller values bit-exact,
unity verdicts identical on valid/corrupt inputs, and the idemix batch
path equal with device_pairing on and off."""

import os
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fabric_tpu.crypto import fp256bn as host
from fabric_tpu.ops import fp12 as f12

RNG = random.Random(20260731)

# The pairing program is compiled ONCE for all issuer keys (line
# schedules are runtime inputs), and the persistent compile cache serves
# every later run — but a WARM run of the end-to-end differentials still
# costs minutes of pure XLA:CPU *execution* on the 2-vCPU gate box
# (measured with FABRIC_TPU_CACHE_DEBUG=1: test_ate2_unity hits the
# cache and still takes ~277s; test_ate2_sharded ~508s — see
# NOTES_BUILD).  That is execution cost no cache can amortize, so the
# heavy differentials carry @pytest.mark.slow and tier-1 (-m 'not
# slow') keeps the cheap fp12 tower rung only; full runs (no -m
# filter, CI-external soaks) still execute them.  FABRIC_TPU_PAIRING_TESTS=0
# opts out of the kernel tests entirely; the two deep-debug
# differentials (per-step Miller values, the idemix batch e2e) stay
# behind FABRIC_TPU_PAIRING_TESTS=1.
_mode = os.environ.get("FABRIC_TPU_PAIRING_TESTS", "")
full_kernel = pytest.mark.skipif(
    _mode == "0",
    reason="pairing kernel tests disabled (FABRIC_TPU_PAIRING_TESTS=0)",
)
deep_kernel = pytest.mark.skipif(
    _mode != "1",
    reason="deep pairing differentials are slow; "
    "set FABRIC_TPU_PAIRING_TESTS=1",
)


def rand_fp12():
    return tuple(
        (RNG.randrange(host.P), RNG.randrange(host.P)) for _ in range(6)
    )


def like2():
    return jnp.zeros((2,), dtype=jnp.uint32)


def test_tower_ops_bit_exact():
    x, y = rand_fp12(), rand_fp12()
    lk = like2()

    @jax.jit
    def fn(x_st, y_st):
        xx = f12.unpack(x_st)
        yy = f12.unpack(y_st)
        return (
            f12.pack(f12.fp12_mul(xx, yy)),
            f12.pack(f12.fp12_sqr(xx)),
            f12.pack(f12.fp12_frobenius(xx, 1)),
            f12.pack(f12.fp12_frobenius(xx, 2)),
            f12.pack(f12.fp12_conj(xx)),
        )

    outs = fn(
        f12.pack(f12.fp12_from_host(x, lk)),
        f12.pack(f12.fp12_from_host(y, lk)),
    )
    got = [f12.fp12_to_host(f12.unpack(np.asarray(o))) for o in outs]
    assert got[0] == host.fp12_mul(x, y)
    assert got[1] == host.fp12_sqr(x)
    assert got[2] == host.fp12_frobenius(x, 1)
    assert got[3] == host.fp12_frobenius(x, 2)
    assert got[4] == host.fp12_conj(x)


@pytest.mark.slow
def test_inv_and_pow_bit_exact():
    x = rand_fp12()
    e = 0xDEADBEEF12345
    lk = like2()

    @jax.jit
    def fn(x_st):
        xx = f12.unpack(x_st)
        return (
            f12.pack(f12.fp12_inv(xx)),
            f12.pack(f12.fp12_pow_const(xx, e)),
        )

    outs = fn(f12.pack(f12.fp12_from_host(x, lk)))
    got = [f12.fp12_to_host(f12.unpack(np.asarray(o))) for o in outs]
    assert got[0] == host.fp12_inv(x)
    assert got[1] == host.fp12_pow(x, e)


def _rand_g1():
    return host.g1_mul(host.G1_GEN, RNG.randrange(1, host.R))


def _rand_g2():
    return host.g2_mul(host.G2_GEN, RNG.randrange(1, host.R))


@deep_kernel
def test_miller_values_bit_exact():
    from fabric_tpu.ops.pairing_kernel import miller2_host_values

    w = _rand_g2()
    p1, p2 = _rand_g1(), _rand_g1()
    got1, got2 = miller2_host_values(w, p1, p2)
    assert got1 == host.miller_loop(w, p1)
    assert got2 == host.miller_loop(host.G2_GEN, p2)


@full_kernel
@pytest.mark.slow
def test_ate2_unity_matches_oracle():
    """e(W, A')·e(g2, ABar)^-1 == 1 holds iff ABar = A'^w-exponent
    structure matches; build a true pair from the BBS+ relation
    ABar = A'·sk-free scaling: use W = g2^gamma, A' random,
    ABar = A'^gamma — then e(W,A') == e(g2, ABar)."""
    from fabric_tpu.ops.pairing_kernel import Ate2Kernel

    gamma = RNG.randrange(1, host.R)
    w = host.g2_mul(host.G2_GEN, gamma)
    kernel = Ate2Kernel(w)

    a1 = _rand_g1()
    good = (a1, host.g1_mul(a1, gamma))
    a2 = _rand_g1()
    bad = (a2, host.g1_mul(a2, (gamma + 1) % host.R))

    def oracle(pair):
        t = host.fp12_mul(
            host.ate(w, pair[0]),
            host.fp12_inv(host.ate(host.G2_GEN, pair[1])),
        )
        return host.gt_is_unity(host.fexp(t))

    assert oracle(good) and not oracle(bad)
    got = kernel.check([good, bad, None])
    assert got == [True, False, False]


@deep_kernel
def test_idemix_batch_device_pairing_matches_host():
    from fabric_tpu import idemix
    from fabric_tpu.crypto import fp256bn as bncurve
    from fabric_tpu.idemix.batch import verify_signatures_batch

    rng = random.Random(1234)
    attrs = ["OU", "Role", "EnrollmentID", "RevocationHandle"]
    rh_index = 3
    ik = idemix.new_issuer_key(attrs, rng)
    sk = bncurve.rand_mod_order(rng)
    nonce = bncurve.big_to_bytes(bncurve.rand_mod_order(rng))
    req = idemix.new_cred_request(sk, nonce, ik.ipk, rng)
    cred = idemix.new_credential(ik, req, [11, 22, 33, 44], rng)
    rev_key = idemix.generate_long_term_revocation_key()
    cri = idemix.create_cri(rev_key, [], 0, idemix.ALG_NO_REVOCATION, rng)
    disclosure = [0, 0, 0, 0]
    msg = b"device pairing test"
    sigs = []
    for _ in range(3):
        nym, r_nym = idemix.make_nym(sk, ik.ipk, rng)
        sigs.append(
            idemix.new_signature(
                cred, sk, nym, r_nym, ik.ipk, disclosure, msg,
                rh_index, cri, rng,
            )
        )
    from fabric_tpu.protos import idemix_pb2

    # corrupt one signature's ABar so the pairing check fails that lane
    from fabric_tpu.idemix.scheme import ecp_from_proto, ecp_to_proto

    bad = idemix_pb2.Signature()
    bad.CopyFrom(sigs[1])
    a_bar = ecp_from_proto(bad.a_bar)
    bad.a_bar.CopyFrom(ecp_to_proto(bncurve.g1_mul(a_bar, 2)))
    sigs[1] = bad

    values = [[None] * 4] * 3
    host_out = verify_signatures_batch(
        sigs, [disclosure] * 3, ik.ipk, [msg] * 3, values, rh_index,
        device_pairing=False,
    )
    dev_out = verify_signatures_batch(
        sigs, [disclosure] * 3, ik.ipk, [msg] * 3, values, rh_index,
        device_pairing=True,
    )
    assert host_out == dev_out
    assert dev_out[0] is True or dev_out[0] == True  # noqa: E712
    assert not dev_out[1]


@full_kernel
@pytest.mark.slow
def test_ate2_sharded_matches_single_device():
    """Lane-sharded pairing over an 8-device mesh (SURVEY P6: the
    multi-chip scale-out of the idemix verify column) agrees lane-exact
    with the single-device program."""
    import jax

    from fabric_tpu.ops.pairing_kernel import Ate2Kernel
    from fabric_tpu.parallel.mesh import flat_mesh

    gamma = RNG.randrange(1, host.R)
    w = host.g2_mul(host.G2_GEN, gamma)
    kernel = Ate2Kernel(w)

    pairs = []
    for i in range(11):  # odd count: exercises padding to 16 lanes
        a = _rand_g1()
        if i % 3 == 2:
            pairs.append((a, host.g1_mul(a, (gamma + 1) % host.R)))
        elif i % 5 == 4:
            pairs.append(None)
        else:
            pairs.append((a, host.g1_mul(a, gamma)))

    single = kernel.check(list(pairs))
    mesh = flat_mesh(jax.devices("cpu")[:8])
    sharded = kernel.check_sharded(list(pairs), mesh)
    assert sharded == single
    assert True in single and False in single  # mixed verdicts
