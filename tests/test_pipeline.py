"""Two-stage commit pipeline (SURVEY §2.13 P4): prepared blocks commit
in order with device verification overlapped on the submitter thread."""

import threading
import time

import pytest

pytest.importorskip(
    "cryptography", reason="MSP material needs the cryptography package"
)

from fabric_tpu.crypto.bccsp import SoftwareProvider
from fabric_tpu.endorser import create_proposal, create_signed_tx, endorse_proposal
from fabric_tpu.ledger import rwset as rw
from fabric_tpu.ledger.rwset_proto import serialize_tx_rwset
from fabric_tpu.msp.cryptogen import generate_org
from fabric_tpu.msp.identity import MSPManager
from fabric_tpu.msp.signer import SigningIdentity
from fabric_tpu.peer.channel import Channel
from fabric_tpu.peer.pipeline import CommitPipeline
from fabric_tpu.policy import from_dsl
from fabric_tpu.protos import protoutil
from fabric_tpu.validation.validator import (
    ChaincodeDefinition,
    ChaincodeRegistry,
)

PROVIDER = SoftwareProvider()
CHANNEL = "pipechan"


@pytest.fixture(scope="module")
def world():
    org = generate_org("org1.example.com", "Org1MSP")
    mgr = MSPManager([org.msp(provider=PROVIDER)])
    registry = ChaincodeRegistry(
        [ChaincodeDefinition("cc", from_dsl("OR('Org1MSP.member')"))]
    )
    return {
        "mgr": mgr,
        "registry": registry,
        "client": SigningIdentity(org.users[0], PROVIDER),
        "peer": SigningIdentity(org.peers[0], PROVIDER),
    }


def _tx(world, key):
    bundle = create_proposal(world["client"], CHANNEL, "cc", [b"put", key])
    results = serialize_tx_rwset(
        rw.TxRwSet(
            (rw.NsRwSet("cc", (), (rw.KVWrite(key.decode(), False, b"v"),)),)
        )
    )
    responses = [endorse_proposal(bundle, world["peer"], results)]
    return create_signed_tx(bundle, world["client"], responses)


def _chain(world, n_blocks, txs_per_block=3):
    blocks = []
    prev = b""
    for num in range(n_blocks):
        block = protoutil.new_block(num, prev)
        for i in range(txs_per_block):
            block.data.data.append(
                _tx(world, f"b{num}k{i}".encode()).SerializeToString()
            )
        protoutil.seal_block(block)
        prev = protoutil.block_header_hash(block.header)
        blocks.append(block)
    return blocks


def test_pipeline_commits_in_order_with_overlap(tmp_path, world):
    ch = Channel(
        CHANNEL,
        str(tmp_path),
        world["mgr"],
        world["registry"],
        PROVIDER,
    )
    blocks = _chain(world, 4)

    events = []
    commits = []
    orig_store = ch.store_block

    def slow_store(block, prepared=None):
        events.append(("commit_start", block.header.number, time.monotonic()))
        time.sleep(0.15)  # make the sequential stage visibly slow
        out = orig_store(block, prepared=prepared)
        events.append(("commit_end", block.header.number, time.monotonic()))
        return out

    ch.store_block = slow_store
    orig_prepare = ch.prepare_block

    def traced_prepare(block):
        events.append(("prepare_start", block.header.number, time.monotonic()))
        return orig_prepare(block)

    ch.prepare_block = traced_prepare

    pipe = CommitPipeline(
        ch, on_commit=lambda b, f: commits.append(b.header.number)
    )
    try:
        for b in blocks:
            pipe.submit(b)
        assert pipe.drain(timeout=60)
    finally:
        pipe.stop()

    assert commits == [0, 1, 2, 3]
    assert ch.ledger.height == 4
    assert ch.ledger.get_state("cc", "b3k2") == b"v"
    # overlap: block 2's prepare started before block 1's commit finished
    t_prep2 = next(t for k, n, t in events if k == "prepare_start" and n == 2)
    t_end1 = next(t for k, n, t in events if k == "commit_end" and n == 1)
    assert t_prep2 < t_end1, events


def _chain_for_channel(world, channel_id, n_blocks, txs_per_block=3):
    """Like _chain but for an arbitrary channel id, with one corrupted
    creator signature per block so the expected mask is NOT all-VALID —
    a race that flips a lane must show up as a byte difference."""
    blocks = []
    prev = b""
    for num in range(n_blocks):
        block = protoutil.new_block(num, prev)
        for i in range(txs_per_block):
            bundle = create_proposal(
                world["client"], channel_id, "cc", [b"put", f"b{num}k{i}".encode()]
            )
            results = serialize_tx_rwset(
                rw.TxRwSet(
                    (
                        rw.NsRwSet(
                            "cc",
                            (),
                            (rw.KVWrite(f"{channel_id}b{num}k{i}", False, b"v"),),
                        ),
                    )
                )
            )
            responses = [endorse_proposal(bundle, world["peer"], results)]
            env = create_signed_tx(bundle, world["client"], responses)
            if i == txs_per_block - 1:
                # corrupt the creator signature -> BAD_CREATOR_SIGNATURE
                env.signature = bytes(env.signature[:-1]) + bytes(
                    [env.signature[-1] ^ 0xFF]
                )
            block.data.data.append(env.SerializeToString())
        protoutil.seal_block(block)
        prev = protoutil.block_header_hash(block.header)
        blocks.append(block)
    return blocks


def test_pipeline_8_threads_mask_bitexact_vs_serial(tmp_path, world):
    """Hammer the commit machinery from 8 pipelines on 8 threads at once
    (shared provider, shared MSP manager, shared hostec tables/pool) and
    require every channel's TRANSACTIONS_FILTER to match a single-threaded
    reference byte for byte.  This is the regression test for the
    stage-A/stage-B shared state audited in PR 3 (validator ident-cache
    lock, provider factory lock, hostec table lock): any cross-thread
    interference that flips a lane breaks the mask equality."""
    n_threads, n_blocks = 8, 5
    chains = {
        f"hammer{t}": _chain_for_channel(world, f"hammer{t}", n_blocks)
        for t in range(n_threads)
    }

    def fresh_channel(channel_id, root):
        return Channel(
            channel_id,
            str(root),
            world["mgr"],
            world["registry"],
            PROVIDER,
        )

    # serial reference: one channel at a time, direct store_block
    reference = {}
    for cid, blocks in chains.items():
        ch = fresh_channel(cid, tmp_path / f"serial-{cid}")
        flags = []
        for b in blocks:
            # store_block mutates block metadata; keep the originals
            # pristine for the parallel run
            copy = protoutil.new_block(0, b"")
            copy.CopyFrom(b)
            flags.append(ch.store_block(copy).tobytes())
        reference[cid] = flags

    # parallel run: 8 pipelines, one submitter thread per channel, all
    # released together
    results = {cid: [] for cid in chains}
    errors = []
    barrier = threading.Barrier(n_threads)

    def drive(cid, blocks, pipe):
        try:
            barrier.wait(timeout=30)
            for b in blocks:
                pipe.submit(b)
        except Exception as exc:  # noqa: BLE001 - surfaced via errors
            errors.append((cid, repr(exc)))

    pipes = {}
    threads = []
    try:
        for cid, blocks in chains.items():
            ch = fresh_channel(cid, tmp_path / f"par-{cid}")
            pipes[cid] = CommitPipeline(
                ch,
                on_commit=lambda b, f, cid=cid: results[cid].append(
                    f.tobytes()
                ),
                on_error=lambda b, exc, cid=cid: errors.append(
                    (cid, repr(exc))
                ),
            )
        for cid, blocks in chains.items():
            t = threading.Thread(
                target=drive, args=(cid, blocks, pipes[cid]), daemon=True
            )
            threads.append(t)
            t.start()
        for t in threads:
            t.join(timeout=120)
        for pipe in pipes.values():
            assert pipe.drain(timeout=120)
    finally:
        for pipe in pipes.values():
            pipe.stop()

    assert not errors, errors
    for cid in chains:
        assert len(results[cid]) == n_blocks, (cid, len(results[cid]))
        assert results[cid] == reference[cid], (
            f"{cid}: pipelined mask diverged from the serial reference"
        )
        # the corrupted lane really is invalid in the reference
        assert any(bytes(f) != b"\x00" * 3 for f in reference[cid])


def test_pipeline_submit_after_stop_raises_fast(tmp_path, world):
    """A full queue + a stopped committer must not deadlock submit
    (the bounded-put fix in pipeline.submit)."""
    ch = Channel(
        CHANNEL, str(tmp_path), world["mgr"], world["registry"], PROVIDER
    )
    blocks = _chain(world, 1)
    pipe = CommitPipeline(ch)
    pipe.stop()
    with pytest.raises(Exception, match="stopped"):
        pipe.submit(blocks[0])


def test_pipeline_surfaces_commit_errors(tmp_path, world):
    ch = Channel(
        CHANNEL,
        str(tmp_path),
        world["mgr"],
        world["registry"],
        PROVIDER,
    )
    blocks = _chain(world, 2)
    errors = []
    pipe = CommitPipeline(
        ch, on_error=lambda b, exc: errors.append((b.header.number, str(exc)))
    )
    try:
        pipe.submit(blocks[0])
        # out-of-order submission: block 0 again -> block store rejects
        pipe.submit(blocks[0])
        assert pipe.drain(timeout=30)
    finally:
        pipe.stop()
    assert ch.ledger.height == 1
    assert errors and errors[0][0] == 0


def test_drain_false_surfaces_last_error(tmp_path, world):
    """Satellite regression: a commit-loop failure must be recorded on
    the pipeline (last_error) so a soak run that sees drain() == False
    can tell 'slow' from 'dead' — pre-fix, the terminal exception was
    visible only to the optional on_error callback."""
    ch = Channel(
        CHANNEL, str(tmp_path), world["mgr"], world["registry"], PROVIDER
    )
    blocks = _chain(world, 1)
    pipe = CommitPipeline(ch)
    try:
        assert pipe.last_error is None and not pipe.dead
        pipe.submit(blocks[0])
        pipe.submit(blocks[0])  # duplicate -> block store rejects
        assert pipe.drain(timeout=30)
        assert pipe.last_error is not None
        assert not pipe.dead  # the loop survived: slow/erroring, not dead
    finally:
        pipe.stop()


class _AsyncLadderProvider(SoftwareProvider):
    """Provider with the async dispatch seam (device kernels, pool
    shards, the serve sidecar): records dispatch/resolve ordering so
    the tests can see prepare dispatching without waiting."""

    def __init__(self):
        super().__init__()
        self.dispatched = 0
        self.resolved = 0

    def batch_verify_async(self, keys, sigs, digests):
        out = SoftwareProvider.batch_verify(self, keys, sigs, digests)
        self.dispatched += 1

        def resolve():
            self.resolved += 1
            return out

        return resolve


def test_channel_prepare_dispatches_async_and_store_resolves(
    tmp_path, world
):
    """Channel.prepare_block must NOT wait on a provider that exposes
    batch_verify_async: the resolver rides the prepared tuple and
    store_block collects the verdicts at stage B, so block N's
    signature math overlaps block N-1's commit epilogue across the
    whole dispatch ladder (serve sidecar included)."""
    prov = _AsyncLadderProvider()
    ch = Channel(
        CHANNEL, str(tmp_path), world["mgr"], world["registry"], prov
    )
    block = _chain(world, 1)[0]
    prepared = ch.prepare_block(block)
    assert prov.dispatched == 1 and prov.resolved == 0, (
        "prepare_block resolved the async dispatch instead of deferring"
    )
    assert callable(prepared[3]), "resolver did not ride the prepared tuple"
    flags = ch.store_block(block, prepared=prepared)
    assert prov.resolved == 1
    assert ch.ledger.height == 1
    assert flags.tobytes() == b"\x00" * 3, "async-prepared masks not VALID"


def test_channel_async_resolver_failure_fails_closed(tmp_path, world):
    """A resolver that dies at stage B (sidecar lost mid-batch AND the
    client shim's own degrade failed too) must surface through the
    commit error path: the block is NOT committed — fail closed,
    never fail open."""

    class _DyingProvider(SoftwareProvider):
        def batch_verify_async(self, keys, sigs, digests):
            def resolve():
                raise RuntimeError("dispatch lost")

            return resolve

    ch = Channel(
        CHANNEL, str(tmp_path), world["mgr"], world["registry"],
        _DyingProvider(),
    )
    block = _chain(world, 1)[0]
    prepared = ch.prepare_block(block)
    with pytest.raises(RuntimeError, match="dispatch lost"):
        ch.store_block(block, prepared=prepared)
    assert ch.ledger.height == 0

    # and through the two-stage pipeline: on_error sees it, no commit
    errors = []
    pipe = CommitPipeline(
        ch, on_error=lambda b, exc: errors.append(str(exc))
    )
    try:
        pipe.submit(block)
        assert pipe.drain(timeout=30)
    finally:
        pipe.stop()
    assert errors and "dispatch lost" in errors[0]
    assert ch.ledger.height == 0


# ----------------------------------------------------------------------
# One bulk read of committed state a block (statedb.BlockPreload): the
# mechanism pinned by a count of the sqlite statements a block issues
# ----------------------------------------------------------------------


def _read_write_tx(world, key, version):
    """The benchmark cell's tx: reads and writes one key of its own."""
    bundle = create_proposal(world["client"], CHANNEL, "cc", [b"put", key.encode()])
    results = serialize_tx_rwset(
        rw.TxRwSet(
            (
                rw.NsRwSet(
                    "cc",
                    (rw.KVRead(key, version),),
                    (rw.KVWrite(key, False, b"v"),),
                ),
            )
        )
    )
    responses = [endorse_proposal(bundle, world["peer"], results)]
    return create_signed_tx(bundle, world["client"], responses)


def test_500_tx_block_reads_committed_state_in_one_statement(tmp_path, world):
    from fabric_tpu.common import fabobs
    from fabric_tpu.common.txflags import TxValidationCode

    ch = Channel(CHANNEL, str(tmp_path), world["mgr"], world["registry"], PROVIDER)
    n = 500
    blocks, prev = [], b""
    for num in range(2):
        block = protoutil.new_block(num, prev)
        for i in range(n):
            # block 1 reads what block 0 wrote; its tx 7 reads a stale version
            version = None
            if num == 1:
                version = rw.Version(0, i) if i != 7 else rw.Version(0, 499)
            block.data.data.append(
                _read_write_tx(world, f"k{i:03d}", version).SerializeToString()
            )
        protoutil.seal_block(block)
        prev = protoutil.block_header_hash(block.header)
        blocks.append(block)

    statements = []
    ch.ledger.state_db._db.set_trace_callback(
        lambda sql: statements.append(sql.split()[0])
    )
    with fabobs.obs_installed(ring=4096) as reg:
        for block in blocks:
            del statements[:]
            flags = ch.store_block(block)
            # before the preload: 3 point SELECTs a tx, 1,500 a block
            assert statements.count("SELECT") == 1
        spans = {
            (e["name"], e["args"]["block"]): e["args"]
            for e in reg.trace_events()
            if e["name"] in ("commit.validate", "ledger.mvcc")
        }
        series = reg.snapshot()["fabric_state_reads_total"]["series"]
    assert [int(c) for c in flags.asarray()] == [
        int(TxValidationCode.MVCC_READ_CONFLICT if i == 7 else TxValidationCode.VALID)
        for i in range(n)
    ]
    assert ch.ledger.state_db.get_version("cc", "k008") == rw.Version(1, 8)
    assert ch.ledger.state_db.get_version("cc", "k007") == rw.Version(0, 7)
    # the policy stage reads the block's written keys; MVCC finds the keys
    # it reads and writes already there, and reads nothing
    for number, rows in ((0, 0), (1, n)):
        validate = spans[("commit.validate", number)]
        assert (validate["keys"], validate["rows"], validate["point_reads"]) == (n, rows, 0)
        mvcc = spans[("ledger.mvcc", number)]
        assert (mvcc["keys"], mvcc["rows"], mvcc["point_reads"]) == (0, 0, 0)
    assert series == {"how=preloaded": 2.0 * n, "how=point": 0.0}
    assert ch._committed is None  # dropped with the block
    ch.ledger.close()
