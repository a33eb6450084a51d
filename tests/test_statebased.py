"""State-based (key-level) endorsement tests — reference semantics from
core/common/validation/statebased/validator_keylevel_test.go:

- a key with VALIDATION_PARAMETER metadata is validated against that
  policy instead of the chaincode EP;
- a tx whose written key had its validation parameters updated by an
  earlier VALID tx in the same block is invalidated;
- if the earlier metadata-writer tx was itself invalid, the committed
  parameter applies;
- metadata-only writes carry state through commit (tx_ops.go merge).
"""

import pytest

from conftest import requires_crypto
from fabric_tpu.crypto.bccsp import SoftwareProvider
from fabric_tpu.endorser import create_proposal, create_signed_tx, endorse_proposal
from fabric_tpu.ledger import rwset as rw
from fabric_tpu.ledger.mvcc import deserialize_metadata, serialize_metadata_entries
from fabric_tpu.ledger.rwset_proto import serialize_tx_rwset
from fabric_tpu.msp.cryptogen import generate_org
from fabric_tpu.msp.identity import MSPManager
from fabric_tpu.msp.signer import SigningIdentity
from fabric_tpu.orderer import SoloChain
from fabric_tpu.orderer.blockcutter import BatchConfig
from fabric_tpu.peer import Channel
from fabric_tpu.policy import from_dsl
from fabric_tpu.policy.proto_convert import marshal_application_policy
from fabric_tpu.protos import common_pb2
from fabric_tpu.validation.statebased import VALIDATION_PARAMETER
from fabric_tpu.validation.txflags import TxValidationCode
from fabric_tpu.validation.validator import ChaincodeDefinition, ChaincodeRegistry

CHANNEL = "sbechannel"
PROVIDER = SoftwareProvider()


@pytest.fixture(scope="module")
def net():
    org1 = generate_org("org1.example.com", "Org1MSP")
    org2 = generate_org("org2.example.com", "Org2MSP")
    orderer_org = generate_org("orderer.example.com", "OrdererMSP")
    mgr = MSPManager([org1.msp(provider=PROVIDER), org2.msp(provider=PROVIDER)])
    # chaincode EP: either org alone endorses fine
    registry = ChaincodeRegistry(
        [ChaincodeDefinition("sbecc", from_dsl("OR('Org1MSP.member','Org2MSP.member')"))]
    )
    return {
        "mgr": mgr,
        "registry": registry,
        "client": SigningIdentity(org1.users[0], PROVIDER),
        "p1": SigningIdentity(org1.peers[0], PROVIDER),
        "p2": SigningIdentity(org2.peers[0], PROVIDER),
        "oid": SigningIdentity(orderer_org.peers[0], PROVIDER),
    }


def make_tx(net, writes=(), metadata_writes=(), endorsers=("p1",)):
    results = serialize_tx_rwset(
        rw.TxRwSet(
            (
                rw.NsRwSet(
                    "sbecc",
                    writes=tuple(
                        rw.KVWrite(k, False, v) for k, v in writes
                    ),
                    metadata_writes=tuple(metadata_writes),
                ),
            )
        )
    )
    bundle = create_proposal(net["client"], CHANNEL, "sbecc", [b"put"])
    responses = [
        endorse_proposal(bundle, net[e], results) for e in endorsers
    ]
    return create_signed_tx(bundle, net["client"], responses)


def vp_entries(policy_dsl):
    """VALIDATION_PARAMETER metadata entries carrying an ApplicationPolicy."""
    return (
        (VALIDATION_PARAMETER, marshal_application_policy(from_dsl(policy_dsl))),
    )


def run_block(net, tmp_path, name, envs_per_block):
    chain = SoloChain(
        CHANNEL, signer=net["oid"],
        batch_config=BatchConfig(max_message_count=100),
    )
    blocks = []
    chain.deliver = blocks.append
    peer = Channel(CHANNEL, str(tmp_path / name), net["mgr"], net["registry"], PROVIDER)
    flags_out = []
    for envs in envs_per_block:
        for env in envs:
            chain.order(env)
        chain.flush()
        flags_out.append(peer.store_block(blocks[-1]))
    return peer, flags_out


@requires_crypto
def test_vp_metadata_persisted_and_enforced(net, tmp_path):
    """Block 1 sets a key-level policy requiring Org2; block 2's tx
    endorsed only by Org1 on that key is invalidated."""
    set_vp = make_tx(
        net,
        writes=[("k", b"v0")],
        metadata_writes=[rw.KVMetadataWrite("k", vp_entries("AND('Org2MSP.member')"))],
        endorsers=("p1",),
    )
    org1_write = make_tx(net, writes=[("k", b"v1")], endorsers=("p1",))
    org2_write = make_tx(net, writes=[("k", b"v2")], endorsers=("p2",))

    peer, flags = run_block(
        net, tmp_path, "peer", [[set_vp], [org1_write], [org2_write]]
    )
    V = TxValidationCode
    assert [int(c) for c in flags[0].asarray()] == [int(V.VALID)]
    # committed metadata present
    md = deserialize_metadata(peer.ledger.state_db.get_state_metadata("sbecc", "k"))
    assert VALIDATION_PARAMETER in md
    # org1-only endorsement now fails the key-level policy
    assert [int(c) for c in flags[1].asarray()] == [int(V.ENDORSEMENT_POLICY_FAILURE)]
    assert peer.ledger.get_state("sbecc", "k") == b"v2"
    assert [int(c) for c in flags[2].asarray()] == [int(V.VALID)]


@requires_crypto
def test_in_block_vp_update_invalidates_later_tx(net, tmp_path):
    """tx0 updates k's validation parameter; tx1 (same block) writes k ->
    invalidated because its endorsements predate the new policy."""
    tx0 = make_tx(
        net,
        writes=[("k", b"v0")],
        metadata_writes=[rw.KVMetadataWrite("k", vp_entries("AND('Org1MSP.member')"))],
        endorsers=("p1",),
    )
    tx1 = make_tx(net, writes=[("k", b"v1")], endorsers=("p1", "p2"))
    _, flags = run_block(net, tmp_path, "peer", [[tx0, tx1]])
    V = TxValidationCode
    assert [int(c) for c in flags[0].asarray()] == [
        int(V.VALID),
        int(V.ENDORSEMENT_POLICY_FAILURE),
    ]


@requires_crypto
def test_invalid_metadata_writer_does_not_block(net, tmp_path):
    """If the metadata-writing tx is itself invalid (policy failure), a
    later tx in the same block validates against the committed state."""
    # chaincode EP is OR(...), but craft the metadata writer to fail:
    # it writes to a key whose VP (set in block 1) requires Org2 while
    # it is endorsed by Org1 only.
    setup = make_tx(
        net,
        writes=[("k", b"v0")],
        metadata_writes=[rw.KVMetadataWrite("k", vp_entries("AND('Org2MSP.member')"))],
        endorsers=("p1",),
    )
    bad_writer = make_tx(
        net,
        writes=[("k", b"x")],
        metadata_writes=[rw.KVMetadataWrite("k", vp_entries("AND('Org1MSP.member')"))],
        endorsers=("p1",),  # fails the Org2 key policy
    )
    org2_write = make_tx(net, writes=[("k", b"v2")], endorsers=("p2",))
    _, flags = run_block(net, tmp_path, "peer", [[setup], [bad_writer, org2_write]])
    V = TxValidationCode
    assert [int(c) for c in flags[1].asarray()] == [
        int(V.ENDORSEMENT_POLICY_FAILURE),
        int(V.VALID),  # not blocked by the invalid in-block update
    ]


@requires_crypto
def test_metadata_only_write_merges_value(net, tmp_path):
    """A metadata-only write keeps the committed value (tx_ops merge) and
    a metadata write on a missing key is a no-op."""
    put = make_tx(net, writes=[("k", b"v0")], endorsers=("p1",))
    md_only = make_tx(
        net,
        metadata_writes=[rw.KVMetadataWrite("k", vp_entries("OR('Org1MSP.member','Org2MSP.member')"))],
        endorsers=("p1",),
    )
    md_missing = make_tx(
        net,
        metadata_writes=[rw.KVMetadataWrite("ghost", vp_entries("AND('Org1MSP.member')"))],
        endorsers=("p1",),
    )
    peer, flags = run_block(net, tmp_path, "peer", [[put], [md_only, md_missing]])
    assert all(int(c) == int(TxValidationCode.VALID) for c in flags[1].asarray())
    assert peer.ledger.get_state("sbecc", "k") == b"v0"  # value preserved
    assert peer.ledger.state_db.get_state_metadata("sbecc", "k") is not None
    assert peer.ledger.get_state("sbecc", "ghost") is None  # no-op
    assert peer.ledger.state_db.get_state_metadata("sbecc", "ghost") is None


@requires_crypto
def test_vp_on_a_written_key_is_found_in_the_block_preload(net, tmp_path):
    """Block 2 writes no metadata, so only the committed VALIDATION_PARAMETER
    of `k` can send it down the sequential SBE path: the gate now reads it
    from the block's one bulk read, and the path and the flags are as they
    were with a point read per written key."""
    from fabric_tpu.common import fabobs

    set_vp = make_tx(
        net,
        writes=[("k", b"v0")],
        metadata_writes=[rw.KVMetadataWrite("k", vp_entries("AND('Org2MSP.member')"))],
        endorsers=("p1",),
    )
    block2 = [
        make_tx(net, writes=[("other", b"o")], endorsers=("p1",)),
        make_tx(net, writes=[("k", b"v1")], endorsers=("p1",)),  # fails k's VP
        make_tx(net, writes=[("k", b"v2")], endorsers=("p2",)),
        make_tx(net, writes=[("fresh", b"f")], endorsers=("p2",)),
    ]
    peer = Channel(CHANNEL, str(tmp_path / "peer"), net["mgr"], net["registry"], PROVIDER)
    sbe_blocks = []
    sequential = peer.validator._evaluate_policies_sbe

    def spy(groups, parsed, flags, deps, plugin_results):
        sbe_blocks.append(len(parsed))
        return sequential(groups, parsed, flags, deps, plugin_results)

    peer.validator._evaluate_policies_sbe = spy
    chain = SoloChain(
        CHANNEL, signer=net["oid"],
        batch_config=BatchConfig(max_message_count=100),
    )
    blocks = []
    chain.deliver = blocks.append
    statements = []
    peer.ledger.state_db._db.set_trace_callback(
        lambda sql: statements.append(sql.split()[0])
    )
    with fabobs.obs_installed(ring=1024) as reg:
        for envs in ([set_vp], block2):
            for env in envs:
                chain.order(env)
            chain.flush()
            del statements[:]
            flags = peer.store_block(blocks[-1])
        validate = [
            e["args"] for e in reg.trace_events() if e["name"] == "commit.validate"
        ][-1]
    V = TxValidationCode
    assert [int(c) for c in flags.asarray()] == [
        int(V.VALID), int(V.ENDORSEMENT_POLICY_FAILURE), int(V.VALID), int(V.VALID),
    ]
    # block 1 by its metadata write, block 2 by the committed parameter
    assert sbe_blocks == [1, 4]
    # three distinct written keys, one of them committed; the gate, the
    # key-level evaluator and MVCC all answered from that one read
    assert (validate["keys"], validate["rows"], validate["point_reads"]) == (3, 1, 0)
    assert statements.count("SELECT") == 1
    assert peer.ledger.get_state("sbecc", "k") == b"v2"
    md = deserialize_metadata(peer.ledger.state_db.get_state_metadata("sbecc", "k"))
    assert VALIDATION_PARAMETER in md  # carried forward by the value write


def test_metadata_serialization_roundtrip():
    entries = (("a", b"1"), (VALIDATION_PARAMETER, b"\x01\x02"))
    raw = serialize_metadata_entries(entries)
    assert deserialize_metadata(raw) == {"a": b"1", VALIDATION_PARAMETER: b"\x01\x02"}
    assert deserialize_metadata(None) is None
