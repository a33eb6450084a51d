"""North-star-shaped scale test: a 1,000-tx block with real envelopes
and signatures through the full channel commit pipeline (parse ->
validate -> MVCC -> sqlite commit), the in-suite version of BASELINE
config #2."""

import pytest

from conftest import requires_crypto
from fabric_tpu.crypto.bccsp import SoftwareProvider
from fabric_tpu.endorser import create_proposal, create_signed_tx, endorse_proposal
from fabric_tpu.ledger import rwset as rw
from fabric_tpu.ledger.rwset_proto import serialize_tx_rwset
from fabric_tpu.msp.cryptogen import generate_org
from fabric_tpu.msp.identity import MSPManager
from fabric_tpu.msp.signer import SigningIdentity
from fabric_tpu.peer.channel import Channel
from fabric_tpu.policy import from_dsl
from fabric_tpu.protos import protoutil
from fabric_tpu.validation.txflags import TxValidationCode
from fabric_tpu.validation.validator import (
    ChaincodeDefinition,
    ChaincodeRegistry,
)

PROVIDER = SoftwareProvider()
CHANNEL = "scalechan"
N_TXS = 1000


@requires_crypto
@pytest.mark.slow
def test_thousand_tx_block_commits(tmp_path):
    org1 = generate_org("org1.example.com", "Org1MSP")
    org2 = generate_org("org2.example.com", "Org2MSP")
    mgr = MSPManager(
        [org1.msp(provider=PROVIDER), org2.msp(provider=PROVIDER)]
    )
    registry = ChaincodeRegistry(
        [
            ChaincodeDefinition(
                "cc", from_dsl("AND('Org1MSP.member','Org2MSP.member')")
            )
        ]
    )
    client = SigningIdentity(org1.users[0], PROVIDER)
    endorsers = [
        SigningIdentity(org1.peers[0], PROVIDER),
        SigningIdentity(org2.peers[0], PROVIDER),
    ]

    block = protoutil.new_block(0, b"")
    for i in range(N_TXS):
        key = f"k{i:04d}"
        # one MVCC conflict pair per 100 txs: tx writes a key an earlier
        # in-block tx wrote and reads stale state
        if i % 100 == 99:
            key = f"k{i - 1:04d}"
        results = serialize_tx_rwset(
            rw.TxRwSet(
                (
                    rw.NsRwSet(
                        "cc",
                        (rw.KVRead(key, None),),
                        (rw.KVWrite(key, False, b"v"),),
                    ),
                )
            )
        )
        bundle = create_proposal(client, CHANNEL, "cc", [b"put", key.encode()])
        responses = [endorse_proposal(bundle, e, results) for e in endorsers]
        block.data.data.append(
            create_signed_tx(bundle, client, responses).SerializeToString()
        )
    protoutil.seal_block(block)

    ch = Channel(CHANNEL, str(tmp_path), mgr, registry, PROVIDER)
    flags = ch.store_block(block)

    codes = [TxValidationCode(int(c)) for c in flags.asarray()]
    n_conflicts = sum(
        1 for c in codes if c == TxValidationCode.MVCC_READ_CONFLICT
    )
    n_valid = sum(1 for c in codes if c == TxValidationCode.VALID)
    assert n_conflicts == N_TXS // 100
    assert n_valid == N_TXS - n_conflicts
    assert ch.ledger.height == 1
    assert ch.ledger.get_state("cc", "k0500") == b"v"
    # restart: savepoint recovery, no replay, same state
    ch.ledger.block_store.close()
    ch.ledger.pvt_store.close()
    ch.ledger.state_db.close()
    from fabric_tpu.ledger.kvledger import KVLedger

    again = KVLedger(str(tmp_path), CHANNEL)
    assert again.height == 1
    # tx 999 targeted k0998 (and was the MVCC-invalid one), so k0999
    # itself was never written
    assert again.get_state("cc", "k0999") is None
    assert again.get_state("cc", "k0998") == b"v"
