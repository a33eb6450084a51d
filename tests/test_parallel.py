"""Mesh-sharded validation (SURVEY.md §2.13 P3/P6, BASELINE config #5):
the sharded provider and the multi-channel single-step validator must be
bit-exact with the host SoftwareProvider path."""

import hashlib
import threading
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from conftest import requires_crypto
from fabric_tpu.common import fabobs
from fabric_tpu.crypto import p256
from fabric_tpu.crypto.bccsp import (
    ECDSAPublicKey,
    SoftwareProvider,
    VerifyError,
)
from fabric_tpu.crypto.der import marshal_signature
from fabric_tpu.crypto.tpu_provider import TPUProvider
from fabric_tpu.endorser import create_proposal, create_signed_tx, endorse_proposal
from fabric_tpu.ledger import rwset as rw
from fabric_tpu.ledger.rwset_proto import serialize_tx_rwset
from fabric_tpu.msp.cryptogen import generate_org
from fabric_tpu.msp.identity import MSPManager
from fabric_tpu.msp.signer import SigningIdentity
from fabric_tpu.ops import bignum as bn
from fabric_tpu.parallel import (
    MultiChannelValidator,
    ShardedVerify,
    flat_mesh,
    grid_mesh,
)
from fabric_tpu.parallel.sharded import pad_lanes
from fabric_tpu.policy import from_dsl
from fabric_tpu.protos import common_pb2, protoutil
from fabric_tpu.validation.txflags import TxValidationCode
from fabric_tpu.validation.validator import (
    BlockValidator,
    ChaincodeDefinition,
    ChaincodeRegistry,
)

PROVIDER = SoftwareProvider()


@pytest.fixture(scope="module")
def cpu8():
    devices = jax.devices("cpu")
    if len(devices) < 8:
        pytest.skip("needs 8 virtual CPU devices (XLA_FLAGS in conftest)")
    return devices[:8]


# ----------------------------------------------------------------------
# flat (data-axis) sharding: ShardedVerify.verify_flat vs SoftwareProvider
# ----------------------------------------------------------------------


def _sig_cases(n):
    """(key, sig, digest, expected) mixing valid, wrong-digest, corrupt-DER
    and high-S lanes."""
    cases = []
    for i in range(n):
        priv = (i * 0x9E3779B97F4A7C15 + 11) % (p256.N - 1) + 1
        pub = p256.scalar_mult(priv, p256.GENERATOR)
        key = ECDSAPublicKey(pub[0], pub[1])
        digest = hashlib.sha256(f"case {i}".encode()).digest()
        k = (i * 0xD6E8FEB86659FD93 + 7) % (p256.N - 1) + 1
        r, s = p256.sign_digest(priv, digest, k=k)
        sig = marshal_signature(r, s)
        kind = i % 4
        if kind == 0:
            cases.append((key, sig, digest))
        elif kind == 1:  # wrong digest
            cases.append((key, sig, hashlib.sha256(b"other").digest()))
        elif kind == 2:  # corrupt DER
            cases.append((key, b"\x30\x03\x02\x01\x01", digest))
        else:  # high-S (rejected by the low-S rule, bccsp/sw/ecdsa.go:41)
            cases.append((key, marshal_signature(r, p256.N - s), digest))
    return cases


@pytest.mark.slow  # ~2min WARM on the 2-vCPU gate box (pure sharded-
# program execution, cache already hit — NOTES_BUILD tier-1 budget
# forensics); the multichannel grid test below keeps sharded-dispatch
# parity in tier-1.
def test_flat_sharded_matches_host(cpu8):
    cases = _sig_cases(45)  # pads to 48 lanes: 6 a device, 3 of them dead
    expected = []
    for key, sig, digest in cases:
        try:
            expected.append(PROVIDER.verify(key, sig, digest))
        except VerifyError:
            expected.append(False)

    sharded = ShardedVerify(flat_mesh(cpu8))
    limbs = TPUProvider().prep_limbs(
        [c[0] for c in cases], [c[1] for c in cases], [c[2] for c in cases]
    )
    size = pad_lanes(len(cases), sharded.data_size)
    got = sharded.verify_flat(*TPUProvider.pad_limbs(limbs, size))
    assert got.shape == (size,) and not got[len(cases):].any()
    assert list(got[: len(cases)]) == expected
    assert any(expected) and not all(expected)


# ----------------------------------------------------------------------
# channel-axis sharding: MultiChannelValidator vs per-channel oracle
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def net():
    org1 = generate_org("org1.example.com", "Org1MSP")
    org2 = generate_org("org2.example.com", "Org2MSP")
    mgr = MSPManager([org1.msp(provider=PROVIDER), org2.msp(provider=PROVIDER)])
    registry = ChaincodeRegistry(
        [
            ChaincodeDefinition(
                "mycc", from_dsl("AND('Org1MSP.member','Org2MSP.member')")
            )
        ]
    )
    return {
        "mgr": mgr,
        "registry": registry,
        "roots": {o.msp_id: bytes(o.ca.cert_pem) for o in (org1, org2)},
        "client": SigningIdentity(org1.users[0], PROVIDER),
        "p1": SigningIdentity(org1.peers[0], PROVIDER),
        "p2": SigningIdentity(org2.peers[0], PROVIDER),
    }


def _results_bytes(key):
    return serialize_tx_rwset(
        rw.TxRwSet((rw.NsRwSet("mycc", (), (rw.KVWrite(key, False, b"v"),)),))
    )


def _make_tx(net, channel, key, endorsers=("p1", "p2"), mangle=None):
    bundle = create_proposal(net["client"], channel, "mycc", [b"invoke", key.encode()])
    responses = [
        endorse_proposal(bundle, net[e], _results_bytes(key)) for e in endorsers
    ]
    env = create_signed_tx(bundle, net["client"], responses)
    if mangle:
        env = mangle(env)
    return env


def _make_block(envelopes, number):
    block = protoutil.new_block(number, b"\x22" * 32)
    for env in envelopes:
        block.data.data.append(env.SerializeToString())
    protoutil.seal_block(block)
    return block


def _bad_creator(env):
    env.signature = env.signature[:-4] + b"\x00\x00\x00\x00"
    return env


def _channel_block(net, channel, number):
    """A block mixing VALID, BAD_CREATOR_SIGNATURE and
    ENDORSEMENT_POLICY_FAILURE txs, unique per channel."""
    txs = [
        _make_tx(net, channel, f"{channel}-k0"),
        _make_tx(net, channel, f"{channel}-k1", mangle=_bad_creator),
        _make_tx(net, channel, f"{channel}-k2", endorsers=("p1",)),
        _make_tx(net, channel, f"{channel}-k3"),
    ]
    return _make_block(txs, number)


def _validator(net, channel):
    return BlockValidator(
        channel, net["mgr"], SoftwareProvider(), net["registry"]
    )


@requires_crypto
def test_multichannel_grid_bit_exact(cpu8, net):
    channels = [f"ch{i}" for i in range(4)]
    blocks = {ch: _channel_block(net, ch, 5) for ch in channels}

    # oracle: each channel through the host-only validator
    expected = {}
    for ch in channels:
        block = common_pb2.Block()
        block.CopyFrom(blocks[ch])
        expected[ch] = _validator(net, ch).validate(block).tobytes()

    raw = {ch: blocks[ch].SerializeToString() for ch in channels}
    mesh = grid_mesh(4, 2, cpu8)
    mc = MultiChannelValidator(
        mesh, {ch: _validator(net, ch) for ch in channels}
    )
    with fabobs.obs_installed() as obs:
        flags = mc.validate(blocks)
        lanes_counted = _device_lanes(obs)

    for ch in channels:
        assert flags[ch].tobytes() == expected[ch], ch
        assert flags[ch].tobytes() == _reference_filter(net, raw[ch]), ch
        assert (
            blocks[ch].metadata.metadata[common_pb2.TRANSACTIONS_FILTER]
            == expected[ch]
        )
    # every real lane counted once the mask was back (3 + 3 + 2 + 3 a
    # channel), and the output read off all eight devices of the mesh
    assert lanes_counted == 4 * 11
    assert mc.last_device_ids == frozenset(d.id for d in cpu8)
    assert mc.last_device_ms > 0
    # the scenario mix actually exercised all three codes
    codes = set(expected["ch0"])
    assert codes == {
        TxValidationCode.VALID,
        TxValidationCode.BAD_CREATOR_SIGNATURE,
        TxValidationCode.ENDORSEMENT_POLICY_FAILURE,
    }


@requires_crypto
def test_multichannel_rejects_unknown_channel(cpu8, net):
    mesh = grid_mesh(4, 2, cpu8)
    mc = MultiChannelValidator(mesh, {"ch0": _validator(net, "ch0")})
    with pytest.raises(KeyError):
        mc.validate({"nope": _channel_block(net, "nope", 1)})


def test_multichannel_epilogue_slices_host_mask_per_channel(monkeypatch):
    """PR 18 regression (fabtrace transfer-in-loop): the per-channel
    epilogue slices the ONE host materialization of the sharded mask —
    no second np.asarray copy per channel.  Fakes keep it device-free:
    each channel's ok_list must be exactly its own mask row's first n
    lanes, with the padded tail dropped."""
    from fabric_tpu.parallel import multichannel as mc

    class FakeSharded:
        data_size = 1
        channel_size = 1

        def channels_program(self):
            return SimpleNamespace(lower=lambda *stacked: None)

        def dispatch_channels(self, *stacked):
            # the (channels, lanes) ok plane, as the device would hold it
            return _DeviceArray(stacked[-1], device_ids=(0,))

    class FakePrep:
        def prep_limbs(self, keys, sigs, digests):
            import fabric_tpu.ops.bignum as bn

            n = len(keys)
            limbs = tuple(
                np.zeros((bn.NLIMBS, n), dtype=np.uint32) for _ in range(5)
            )
            ok = np.array([i % 2 == 0 for i in range(n)])
            return (*limbs, ok)

    class FakeValidator:
        def __init__(self, n):
            self.n = n

        def collect_sig_jobs(self, parsed):
            jobs = list(range(self.n))
            return jobs, jobs, jobs, jobs, jobs

        def finish_sig_results(self, jobs, job_identity, ok_list):
            return ok_list

        def validate(self, block, parsed, sig_results=None):
            return sig_results

    monkeypatch.setattr(mc, "parse_block", lambda data: data)
    v = mc.MultiChannelValidator.__new__(mc.MultiChannelValidator)
    v.validators = {"a": FakeValidator(3), "b": FakeValidator(5)}
    v.sharded = FakeSharded()
    v._prep = FakePrep()
    v._lowered = set()
    v.last_device_ms = 0.0

    block = SimpleNamespace(data=SimpleNamespace(data=[]))
    out = v.validate({"a": block, "b": block})
    assert out["a"] == [True, False, True]
    assert out["b"] == [True, False, True, False, True]


# ----------------------------------------------------------------------
# MultiChannelValidator around a device that is not there: the sharded
# program's place is taken by the host's own ECDSA over the very limb
# stacks the program would be given, so everything but the kernel runs
# (prep_limbs, padding, stacking, the mask's slices, the epilogue) and
# no XLA:CPU program is compiled or executed
# ----------------------------------------------------------------------


class _DeviceArray:
    """What the jitted call returns, as far as the validator reads it:
    where it lives, and its copy back to the host."""

    def __init__(self, mask, device_ids):
        self._mask = np.asarray(mask)
        self.sharding = SimpleNamespace(
            device_set={_Device(i) for i in device_ids}
        )

    def __array__(self, dtype=None, copy=None):
        return self._mask


class _Device:
    def __init__(self, id):
        self.id = id


class _HostSharded:
    """ShardedVerify's surface over a (channel_size, data_size) mesh that
    does not exist."""

    def __init__(self, channel_size=4, data_size=1, device_ids=(0, 1, 2, 3)):
        self.channel_size = channel_size
        self.data_size = data_size
        self.device_ids = device_ids
        self.lowered = []   # (shape, thread name) of every .lower()
        self.stacks = []    # every stack dispatched

    def channels_program(self):
        return self

    def lower(self, *stacked):
        self.lowered.append(
            (stacked[0].shape, threading.current_thread().name)
        )

    def dispatch_channels(self, e, r, s, qx, qy, ok):
        self.stacks.append((e, r, s, qx, qy, ok))
        mask = np.zeros(ok.shape, dtype=bool)
        for c, lane in zip(*np.nonzero(ok)):
            ints = [
                bn.limbs_to_int(a[c, :, lane]) for a in (e, r, s, qx, qy)
            ]
            mask[c, lane] = _host_ecdsa(*ints)
        return _DeviceArray(mask, self.device_ids)


def _host_ecdsa(e, r, s, qx, qy):
    """The ECDSA equation alone, as the kernel owes it for a lane whose host
    prechecks passed (OpenSSL through the cryptography package: the pure
    Python curve takes 0.1 s a lane)."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec, utils

    try:
        key = ec.EllipticCurvePublicNumbers(qx, qy, ec.SECP256R1()).public_key()
        key.verify(
            utils.encode_dss_signature(r, s), e.to_bytes(32, "big"),
            ec.ECDSA(utils.Prehashed(hashes.SHA256())),
        )
    except (ValueError, InvalidSignature):
        return False
    return True


def _host_mc(net, channels, **mesh):
    """A MultiChannelValidator whose device is the host (see above)."""
    from fabric_tpu.parallel import multichannel

    v = multichannel.MultiChannelValidator.__new__(
        multichannel.MultiChannelValidator
    )
    v.validators = {ch: _validator(net, ch) for ch in channels}
    v.sharded = _HostSharded(**mesh)
    v._prep = multichannel.TPUProvider()
    v._lowered = set()
    return v


def _reference_filter(net, raw_block):
    """The plain reference's TRANSACTIONS_FILTER for a path that ends at
    the signature and policy checks (benchmarks/reference.py imports
    nothing of fabric_tpu); the net's policy is AND(Org1, Org2)."""
    from benchmarks import reference as ref
    from benchmarks import reference_filter as flt

    _, envelopes = ref.block_envelopes(raw_block)
    return flt.sigpolicy_filter(ref.check_signatures_and_policy(
        envelopes, ref.Membership(net["roots"]),
        {"n": 2, "mspids": ["Org1MSP", "Org2MSP"]},
    ))


def _device_lanes(obs):
    series = obs.snapshot()["fabric_verify_lanes_total"]["series"]
    return int(series.get("rung=device", 0))


def _spans(obs):
    return [e for e in obs.trace_events() if e.get("ph") == "X"]


CHANNELS = [f"ch{i}" for i in range(4)]


@requires_crypto
def test_multichannel_flags_equal_the_plain_reference_byte_for_byte(net):
    blocks = {ch: _channel_block(net, ch, 7) for ch in CHANNELS}
    raw = {ch: blocks[ch].SerializeToString() for ch in CHANNELS}
    flags = _host_mc(net, CHANNELS).validate(blocks)
    for ch in CHANNELS:
        want = _reference_filter(net, raw[ch])
        assert flags[ch].tobytes() == want, ch
        assert (
            blocks[ch].metadata.metadata[common_pb2.TRANSACTIONS_FILTER] == want
        )
    assert set(_reference_filter(net, raw["ch0"])) == {
        TxValidationCode.VALID,
        TxValidationCode.BAD_CREATOR_SIGNATURE,
        TxValidationCode.ENDORSEMENT_POLICY_FAILURE,
    }


@requires_crypto
@pytest.mark.parametrize("poisoned", ["ch0", "ch2"])
def test_multichannel_poison_in_one_channel_changes_that_channel_only(
    net, poisoned
):
    def blocks(poison):
        out = {}
        for ch in CHANNELS:
            mangle = _bad_creator if (poison and ch == poisoned) else None
            out[ch] = _make_block(
                [
                    _make_tx(net, ch, f"{ch}-a"),
                    _make_tx(net, ch, f"{ch}-b", mangle=mangle),
                    _make_tx(net, ch, f"{ch}-c"),
                ],
                3,
            )
        return out

    clean = _host_mc(net, CHANNELS).validate(blocks(False))
    dirty = _host_mc(net, CHANNELS).validate(blocks(True))
    for ch in CHANNELS:
        assert clean[ch].tobytes() == bytes(3), ch
        want = bytes(3)
        if ch == poisoned:
            want = bytes([0, TxValidationCode.BAD_CREATOR_SIGNATURE, 0])
        assert dirty[ch].tobytes() == want, ch


@requires_crypto
@pytest.mark.parametrize("txs_per_channel", [(1, 4, 2, 3), (5, 1, 1, 1)])
def test_multichannel_channels_of_unequal_lane_counts(net, txs_per_channel):
    blocks, raw = {}, {}
    for ch, n in zip(CHANNELS, txs_per_channel):
        txs = [
            _make_tx(
                net, ch, f"{ch}-k{i}",
                endorsers=("p1",) if i % 3 == 2 else ("p1", "p2"),
            )
            for i in range(n)
        ]
        blocks[ch] = _make_block(txs, 1)
        raw[ch] = blocks[ch].SerializeToString()
    mc = _host_mc(net, CHANNELS)
    flags = mc.validate(blocks)
    for ch, n in zip(CHANNELS, txs_per_channel):
        assert len(flags[ch].tobytes()) == n
        assert flags[ch].tobytes() == _reference_filter(net, raw[ch]), ch
    # one stack, every channel padded to the widest one's bucket, and no
    # lane beyond a channel's own count alive
    ((e, *_, ok),) = mc.sharded.stacks
    assert e.shape == (4, bn.NLIMBS, 128) and ok.shape == (4, 128)
    lanes = [3 * n - n // 3 for n in txs_per_channel]
    assert [int(row.sum()) for row in ok] == lanes


@requires_crypto
def test_multichannel_fewer_channels_than_the_mesh_get_dead_rows(net):
    blocks = {ch: _channel_block(net, ch, 2) for ch in CHANNELS[:2]}
    expected = {}
    for ch in blocks:
        copy = common_pb2.Block()
        copy.CopyFrom(blocks[ch])
        expected[ch] = _validator(net, ch).validate(copy).tobytes()
    mc = _host_mc(net, CHANNELS)
    flags = mc.validate(blocks)
    assert {ch: f.tobytes() for ch, f in flags.items()} == expected
    ((e, *_, ok),) = mc.sharded.stacks
    assert e.shape[0] == 4 and not ok[2:].any() and not e[2:].any()


@requires_crypto
def test_multichannel_spans_of_one_validate(net):
    mc = _host_mc(net, CHANNELS)
    with fabobs.obs_installed() as obs:
        mc.validate({ch: _channel_block(net, ch, 1) for ch in CHANNELS})
        mc.validate({ch: _channel_block(net, ch, 2) for ch in CHANNELS[:1]})
        spans = _spans(obs)
    first = [e for e in spans if e["args"]["step"] == 0]
    second = [e for e in spans if e["args"]["step"] == 1]
    assert len(first) + len(second) == len(spans)  # every span names its step
    by_name = {}
    for e in first:
        by_name.setdefault(e["name"], []).append(e)
    assert {name: len(rows) for name, rows in by_name.items()} == {
        "mc.validate": 1, "mc.prepare": 4, "mc.parse": 4,
        "mc.collect_sig_jobs": 4, "mc.prep_limbs": 4, "mc.stack": 1,
        "mc.dispatch": 1, "mc.resolve": 1, "mc.epilogue": 4,
    }
    (whole,) = by_name["mc.validate"]
    assert "parent_id" not in whole["args"]
    assert whole["args"]["channels"] == 4
    assert whole["args"]["lanes"] == 4 * 11
    assert whole["args"]["bucket"] == 128
    root = whole["args"]["span_id"]
    for name in ("mc.prepare", "mc.stack", "mc.dispatch", "mc.resolve",
                 "mc.epilogue"):
        assert all(e["args"]["parent_id"] == root for e in by_name[name]), name
    # the per-channel spans name their channel; a prepare's three children
    # point at the prepare of the same channel
    for name in ("mc.prepare", "mc.epilogue"):
        assert sorted(e["args"]["channel"] for e in by_name[name]) == CHANNELS
    prepare_of = {
        e["args"]["channel"]: e["args"]["span_id"] for e in by_name["mc.prepare"]
    }
    assert all(e["args"]["lanes"] == 11 for e in by_name["mc.prepare"])
    for name in ("mc.parse", "mc.collect_sig_jobs", "mc.prep_limbs"):
        for e in by_name[name]:
            assert e["args"]["parent_id"] == prepare_of[e["args"]["channel"]]
    # the second call is step 1, with one channel's spans
    assert sorted(e["name"] for e in second).count("mc.prepare") == 1
    assert [e["args"]["channels"] for e in second
            if e["name"] == "mc.validate"] == [1]


@requires_crypto
def test_multichannel_counts_device_lanes_and_names_its_devices(net):
    mc = _host_mc(net, CHANNELS, device_ids=(4, 5, 6, 7))
    assert mc.last_device_ids == frozenset() and mc.last_device_ms == 0.0
    with fabobs.obs_installed() as obs:
        mc.validate({ch: _channel_block(net, ch, 1) for ch in CHANNELS})
        assert _device_lanes(obs) == 4 * 11
        mc.validate({ch: _channel_block(net, ch, 2) for ch in CHANNELS[:3]})
        assert _device_lanes(obs) == 7 * 11
        seconds = obs.snapshot()["fabric_verify_seconds"]["series"]
        spans = _spans(obs)
    assert mc.last_device_ids == frozenset({4, 5, 6, 7})
    assert [k for k in seconds if "rung=device" in k]
    # last_device_ms is the dispatch and the resolve of the last step, from
    # the clock reads that made the two spans (the ring rounds to 0.1 us)
    last = {
        e["name"]: e for e in spans
        if e["args"]["step"] == 1 and e["name"] in ("mc.dispatch", "mc.resolve")
    }
    both = last["mc.dispatch"]["dur"] + last["mc.resolve"]["dur"]
    assert mc.last_device_ms * 1e3 == pytest.approx(both, abs=0.11)
    assert last["mc.resolve"]["ts"] == pytest.approx(
        last["mc.dispatch"]["ts"] + last["mc.dispatch"]["dur"], abs=0.11
    )


@requires_crypto
def test_multichannel_first_call_of_a_shape_lowers_and_the_second_does_not(
    net, monkeypatch
):
    from fabric_tpu.parallel import multichannel

    calls = []

    def recording(fn, *args):
        calls.append((fn, args[0].shape))
        return fn(*args)

    monkeypatch.setattr(multichannel, "_on_fresh_stack", recording)
    mc = _host_mc(net, CHANNELS, channel_size=1, device_ids=(0,))
    one = lambda n: {"ch0": _channel_block(net, "ch0", n)}  # noqa: E731
    mc.validate(one(1))
    assert calls == [(mc.sharded.lower, (1, bn.NLIMBS, 128))]
    mc.validate(one(2))
    assert len(calls) == 1  # the shape is remembered
    mc.validate({ch: _channel_block(net, ch, 3) for ch in CHANNELS[:2]})
    assert [shape for _, shape in calls] == [
        (1, bn.NLIMBS, 128), (2, bn.NLIMBS, 128),
    ]
    assert len(mc.sharded.stacks) == 3  # every call dispatched all the same


@requires_crypto
def test_multichannel_lowers_on_an_empty_stack_of_another_thread(net):
    mc = _host_mc(net, CHANNELS)
    mc.validate({ch: _channel_block(net, ch, 1) for ch in CHANNELS})
    ((shape, thread),) = mc.sharded.lowered
    assert shape == (4, bn.NLIMBS, 128)
    assert thread.startswith("tpu-first-dispatch")
    assert thread != threading.current_thread().name


def test_sharded_dispatch_hands_back_the_programs_own_output(cpu8):
    """`dispatch_channels` is `verify_channels` before the copy back; both
    refuse a stack that does not divide the mesh.  The program is planted:
    no kernel is traced."""
    from fabric_tpu.parallel.sharded import ShardedVerify

    sharded = ShardedVerify(grid_mesh(4, 2, cpu8))
    handed = []

    def planted(*stacked):
        handed.append(stacked)
        return _DeviceArray(stacked[-1], device_ids=range(8))

    sharded._channels = planted
    limbs = [np.zeros((4, bn.NLIMBS, 6), dtype=np.uint32) for _ in range(5)]
    ok = np.arange(24).reshape(4, 6) % 2 == 0
    out = sharded.dispatch_channels(*limbs, ok)
    assert isinstance(out, _DeviceArray) and len(out.sharding.device_set) == 8
    copied = sharded.verify_channels(*limbs, ok)
    assert isinstance(copied, np.ndarray) and (copied == ok).all()
    assert len(handed) == 2
    for bad in (
        [a[:, :, :5] for a in limbs] + [ok[:, :5]],   # lanes % data axis
        [a[:3] for a in limbs] + [ok[:3]],            # channels % channel axis
    ):
        with pytest.raises(ValueError):
            sharded.dispatch_channels(*bad)
        with pytest.raises(ValueError):
            sharded.verify_channels(*bad)
