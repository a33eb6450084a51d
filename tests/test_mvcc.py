"""MVCC validator tests — table-driven, modeled on the reference's
validation/validator_test.go scenarios."""

from fabric_tpu.ledger.mvcc import Validator
from fabric_tpu.ledger.rwset import (
    CollHashedRwSet,
    KVRead,
    KVReadHash,
    KVWrite,
    KVWriteHash,
    NsRwSet,
    RangeQueryInfo,
    TxRwSet,
    Version,
)
from fabric_tpu.ledger.statedb import UpdateBatch, VersionedDB
from fabric_tpu.validation.txflags import TxValidationCode

V = TxValidationCode.VALID
MVCC = TxValidationCode.MVCC_READ_CONFLICT
PHANTOM = TxValidationCode.PHANTOM_READ_CONFLICT


def seed_db(entries):
    db = VersionedDB()
    batch = UpdateBatch()
    for ns, key, value, ver in entries:
        batch.put(ns, key, value, ver)
    db.apply_updates(batch)
    return db


def tx(reads=(), writes=(), rq=(), coll=(), ns="cc1"):
    return TxRwSet((NsRwSet(ns, tuple(reads), tuple(writes), tuple(rq), tuple(coll)),))


def run(db, txs, block_num=5):
    v = Validator(db)
    codes, updates, hashed = v.validate_and_prepare_batch(
        block_num, txs, [V] * len(txs)
    )
    return codes, updates, hashed


def test_version_match_and_mismatch():
    db = seed_db([("cc1", "k1", b"v1", Version(1, 0)), ("cc1", "k2", b"v2", Version(1, 1))])
    txs = [
        tx(reads=[KVRead("k1", Version(1, 0))], writes=[KVWrite("k1", value=b"new")]),
        tx(reads=[KVRead("k2", Version(9, 9))]),  # stale
        tx(reads=[KVRead("missing", None)]),  # correctly read-as-absent
        tx(reads=[KVRead("missing", Version(1, 0))]),  # phantom existence
    ]
    codes, updates, _ = run(db, txs)
    assert codes == [V, MVCC, V, MVCC]
    assert updates.get("cc1", "k1") == (b"new", Version(5, 0), None)


def test_intra_block_conflict_and_apply_as_you_go():
    db = seed_db([("cc1", "k1", b"v1", Version(1, 0))])
    txs = [
        tx(reads=[KVRead("k1", Version(1, 0))], writes=[KVWrite("k1", value=b"a")]),
        # reads k1 at committed version, but tx0 wrote it in-block -> conflict
        tx(reads=[KVRead("k1", Version(1, 0))]),
        # doesn't read k1; writes something else -> fine
        tx(writes=[KVWrite("k9", value=b"z")]),
    ]
    codes, updates, _ = run(db, txs)
    assert codes == [V, MVCC, V]
    assert updates.get("cc1", "k9") == (b"z", Version(5, 2), None)


def test_invalid_tx_does_not_apply_writes():
    db = seed_db([("cc1", "k1", b"v1", Version(1, 0))])
    txs = [
        tx(reads=[KVRead("k1", Version(0, 0))], writes=[KVWrite("k2", value=b"x")]),
        tx(reads=[KVRead("k2", None)]),  # k2 not written since tx0 invalid
    ]
    codes, updates, _ = run(db, txs)
    assert codes == [MVCC, V]
    assert updates.get("cc1", "k2") is None


def test_upstream_invalid_skipped():
    db = seed_db([])
    txs = [tx(writes=[KVWrite("k", value=b"v")])] * 2
    v = Validator(db)
    codes, updates, _ = v.validate_and_prepare_batch(
        7, txs, [TxValidationCode.ENDORSEMENT_POLICY_FAILURE, V]
    )
    assert codes == [TxValidationCode.ENDORSEMENT_POLICY_FAILURE, V]
    assert updates.get("cc1", "k") == (b"v", Version(7, 1), None)


def test_delete_write_and_read_of_deleted():
    db = seed_db([("cc1", "k1", b"v1", Version(1, 0))])
    txs = [
        tx(reads=[KVRead("k1", Version(1, 0))], writes=[KVWrite("k1", is_delete=True)]),
    ]
    codes, updates, _ = run(db, txs)
    assert codes == [V]
    db.apply_updates(updates)
    assert db.get_state("cc1", "k1") is None


class TestRangeQueries:
    def seed(self):
        return seed_db(
            [("cc1", f"k{i}", b"v", Version(1, i)) for i in range(1, 6)]
        )  # k1..k5

    def rq(self, start, end, reads, exhausted=True):
        return RangeQueryInfo(start, end, exhausted, tuple(reads))

    def test_unchanged_range_ok(self):
        db = self.seed()
        reads = [KVRead(f"k{i}", Version(1, i)) for i in range(1, 4)]  # k1..k3 < k4
        txs = [tx(rq=[self.rq("k1", "k4", reads)])]
        codes, _, _ = run(db, txs)
        assert codes == [V]

    def test_phantom_insert_by_prior_tx(self):
        db = self.seed()
        reads = [KVRead(f"k{i}", Version(1, i)) for i in range(1, 4)]
        txs = [
            tx(writes=[KVWrite("k25", value=b"new")]),  # k25 sorts inside [k1,k4)
            tx(rq=[self.rq("k1", "k4", reads)]),
        ]
        codes, _, _ = run(db, txs)
        assert codes == [V, PHANTOM]

    def test_phantom_delete_by_prior_tx(self):
        db = self.seed()
        reads = [KVRead(f"k{i}", Version(1, i)) for i in range(1, 4)]
        txs = [
            tx(writes=[KVWrite("k2", is_delete=True)]),
            tx(rq=[self.rq("k1", "k4", reads)]),
        ]
        codes, _, _ = run(db, txs)
        assert codes == [V, PHANTOM]

    def test_version_change_in_range(self):
        db = self.seed()
        reads = [KVRead(f"k{i}", Version(1, i)) for i in range(1, 4)]
        txs = [
            tx(writes=[KVWrite("k2", value=b"upd")]),
            tx(rq=[self.rq("k1", "k4", reads)]),
        ]
        codes, _, _ = run(db, txs)
        assert codes == [V, PHANTOM]

    def test_itr_not_exhausted_includes_end_key(self):
        db = self.seed()
        # Simulation stopped at k3: EndKey=k3 must be included on re-check.
        reads = [KVRead(f"k{i}", Version(1, i)) for i in range(1, 4)]
        txs = [tx(rq=[self.rq("k1", "k3", reads, exhausted=False)])]
        codes, _, _ = run(db, txs)
        assert codes == [V]
        # A write to k3 by a prior tx now matters.
        txs = [
            tx(writes=[KVWrite("k3", value=b"!")]),
            tx(rq=[self.rq("k1", "k3", reads, exhausted=False)]),
        ]
        codes, _, _ = run(db, txs)
        assert codes == [V, PHANTOM]


class TestHashedReads:
    def test_hashed_read_conflicts(self):
        db = VersionedDB()
        from fabric_tpu.ledger.statedb import HashedUpdateBatch

        pre = HashedUpdateBatch()
        pre.put("cc1", "collA", b"\x01" * 32, b"\xaa" * 32, Version(1, 0))
        db.apply_updates(UpdateBatch(), pre)

        ok_read = KVReadHash(b"\x01" * 32, Version(1, 0))
        stale_read = KVReadHash(b"\x01" * 32, Version(0, 0))
        txs = [
            tx(coll=[CollHashedRwSet("collA", (ok_read,))]),
            tx(coll=[CollHashedRwSet("collA", (stale_read,))]),
            # writes the hash, then a later tx reads it -> in-block conflict
            tx(coll=[CollHashedRwSet("collA", (), (KVWriteHash(b"\x01" * 32, value_hash=b"\xbb" * 32),))]),
            tx(coll=[CollHashedRwSet("collA", (ok_read,))]),
        ]
        codes, _, hashed = run(db, txs)
        assert codes == [V, MVCC, V, MVCC]
        assert len(hashed) == 1


# ----------------------------------------------------------------------
# The block's preload of committed rows (statedb.BlockPreload) against the
# point-read oracle: the same validator with a preload that loads nothing,
# so that every lookup is the db's point read, as before the preload.
# ----------------------------------------------------------------------

import random

import pytest

from fabric_tpu.common import fabobs
from fabric_tpu.ledger.kvledger import deterministic_update_bytes
from fabric_tpu.ledger.rwset import KVMetadataWrite, KVMetadataWriteHash
from fabric_tpu.ledger.simulator import TxSimulator
from fabric_tpu.ledger.statedb import BlockPreload, HashedUpdateBatch

POLICY_FAILURE = TxValidationCode.ENDORSEMENT_POLICY_FAILURE
DIFF_NS = "cc"
DIFF_COLL = "collA"


class PointReadsOnly(BlockPreload):
    """The oracle's preload: loads nothing and asks the db as the validator
    did before there was a preload, one point read a lookup."""

    def load(self, keys, hashed_keys=()):
        pass

    def version(self, ns, key):
        return self.db.get_version(ns, key)

    def metadata(self, ns, key):
        return self.db.get_state_metadata(ns, key)

    def value(self, ns, key):
        vv = self.db.get_state(ns, key)
        return vv.value if vv else None

    def hashed_version(self, ns, coll, key_hash):
        return self.db.get_key_hash_version(ns, coll, key_hash)

    def hashed_metadata(self, ns, coll, key_hash):
        return self.db.get_hashed_metadata(ns, coll, key_hash)

    def hashed_value(self, ns, coll, key_hash):
        vv = self.db.get_hashed_state(ns, coll, key_hash)
        return vv.value if vv else None


def _kh(i):
    return bytes([i % 251]) * 32


def seed_for_differential(db, n=60):
    """Keys k000..k(n-1) of which every third carries metadata, and as
    many key hashes, committed at block 1."""
    batch, hashed = UpdateBatch(), HashedUpdateBatch()
    for i in range(n):
        md = b"md%d" % i if i % 3 == 0 else None
        batch.put(DIFF_NS, f"k{i:03d}", b"v%d" % i, Version(1, i), md)
        hashed.put(DIFF_NS, DIFF_COLL, _kh(i), b"h%d" % i, Version(1, i), md)
    db.apply_updates(batch, hashed)


def random_block(rng, db, n_txs=48, n_keys=60):
    """One block over every case the validator treats apart: reads of
    present, absent and stale versions; a read of a key an earlier tx of
    the block wrote; deletes; metadata-only writes on present and absent
    keys; hashed reads and writes; a raw and a Merkle range query; txs
    that arrive invalid or without a rwset."""
    def present():
        return f"k{rng.randrange(n_keys):03d}"

    def absent():
        return f"zz{rng.randrange(10 ** 6)}"

    written = []
    txs, codes = [], []
    for t in range(n_txs):
        kind = rng.randrange(14)
        reads, writes, mdw, rqs = [], [], [], []
        hreads, hwrites, hmdw = [], [], []
        if kind == 0:  # read the committed version, write the key
            k = present()
            reads.append(KVRead(k, db.get_version(DIFF_NS, k)))
            writes.append(KVWrite(k, value=b"w%d" % t))
        elif kind == 1:  # stale read
            k = present()
            reads.append(KVRead(k, Version(0, 7)))
            writes.append(KVWrite(k, value=b"stale"))
        elif kind == 2:  # read-as-absent, then create
            k = absent()
            reads.append(KVRead(k, None))
            writes.append(KVWrite(k, value=b"new"))
        elif kind == 3:  # claims a version for an absent key
            reads.append(KVRead(absent(), Version(1, 0)))
        elif kind == 4 and written:  # reads what an earlier tx wrote
            k = rng.choice(written)
            reads.append(KVRead(k, db.get_version(DIFF_NS, k)))
            writes.append(KVWrite(absent(), value=b"after"))
        elif kind == 5:  # delete
            k = rng.choice([present(), absent()])
            writes.append(KVWrite(k, is_delete=True))
        elif kind == 6:  # metadata-only write, present or absent key
            k = rng.choice([present(), absent()])
            entries = rng.choice([(("VP", b"p%d" % t),), None])
            mdw.append(KVMetadataWrite(k, entries))
        elif kind == 7:  # value and metadata in one tx
            k = present()
            writes.append(KVWrite(k, value=b"both"))
            mdw.append(KVMetadataWrite(k, (("VP", b"q%d" % t),)))
        elif kind == 8:  # blind write: carries the committed metadata on
            writes.append(KVWrite(present(), value=b"blind%d" % t))
        elif kind == 9:  # hashed read (right, stale or absent) and write
            i = rng.randrange(n_keys)
            version = rng.choice(
                [db.get_key_hash_version(DIFF_NS, DIFF_COLL, _kh(i)),
                 Version(0, 3), None]
            )
            hreads.append(KVReadHash(_kh(i), version))
            hwrites.append(KVWriteHash(_kh(i), value_hash=b"hw%d" % t))
        elif kind == 10:  # hashed delete / hashed metadata-only write
            i = rng.randrange(n_keys + 20)  # some of them absent
            if rng.random() < 0.5:
                hwrites.append(KVWriteHash(_kh(i), is_delete=True))
            else:
                hmdw.append(KVMetadataWriteHash(_kh(i), (("VP", b"h"),)))
        elif kind in (11, 12):  # range query: raw (11) or Merkle (12)
            sim = TxSimulator(
                db, f"t{t}",
                range_query_hashing_max_degree=0 if kind == 11 else 3,
            )
            lo = rng.randrange(n_keys - 25)
            list(sim.get_state_range_scan_iterator(
                DIFF_NS, f"k{lo:03d}", f"k{lo + 20:03d}"
            ))
            rqs.extend(
                sim.get_tx_simulation_results().rwset.ns_rw_sets[0].range_queries
            )
        else:
            writes.append(KVWrite(absent(), value=b"plain"))
        written.extend(w.key for w in writes)
        coll = (
            [CollHashedRwSet(DIFF_COLL, tuple(hreads), tuple(hwrites), tuple(hmdw))]
            if hreads or hwrites or hmdw else []
        )
        rwset = TxRwSet((NsRwSet(
            DIFF_NS, tuple(reads), tuple(writes), tuple(rqs), tuple(coll),
            tuple(mdw),
        ),))
        arrives = rng.random()
        if arrives < 0.08:
            txs.append(rwset), codes.append(POLICY_FAILURE)
        elif arrives < 0.12:
            txs.append(None), codes.append(V)
        else:
            txs.append(rwset), codes.append(V)
    return txs, codes


def assert_same_as_point_reads(db, block_num, txs, codes, committed=None):
    """Codes, both update batches and the commit hash's update bytes of
    the preloading validator equal the point-read oracle's."""
    oracle = Validator(db).validate_and_prepare_batch(
        block_num, txs, codes, committed=PointReadsOnly(db)
    )
    committed = committed if committed is not None else BlockPreload(db)
    got = Validator(db).validate_and_prepare_batch(
        block_num, txs, codes, committed=committed
    )
    assert got[0] == oracle[0]
    assert dict(got[1].items()) == dict(oracle[1].items())
    assert dict(got[2].items()) == dict(oracle[2].items())
    assert deterministic_update_bytes(got[1], got[2]) == (
        deterministic_update_bytes(oracle[1], oracle[2])
    )
    return got, committed


def run_differential(db, seed, blocks=3):
    rng = random.Random(seed)
    seed_for_differential(db)
    seen = set()
    for block_num in range(2, 2 + blocks):
        txs, codes = random_block(rng, db)
        (out, updates, hashed), committed = assert_same_as_point_reads(
            db, block_num, txs, codes
        )
        assert committed.keys > 0
        seen.update(out)
        # later blocks read what this one left: versions, metadata, deletes
        db.apply_updates(updates, hashed)
    return seen


@pytest.mark.parametrize("seed", range(8))
def test_preload_equals_point_reads_in_memory(seed):
    seen = run_differential(VersionedDB(), seed)
    assert {V, MVCC, POLICY_FAILURE} <= seen


def test_differential_blocks_reach_every_code():
    seen = set()
    for seed in range(8):
        seen |= run_differential(VersionedDB(), seed, blocks=1)
    assert {V, MVCC, PHANTOM, POLICY_FAILURE} <= seen


def big_block(n=2500):
    """2,500 distinct keys, each read and written by a tx of its own;
    every other one committed."""
    db_rows = [("cc1", f"big{i:05d}", b"v", Version(1, i)) for i in range(0, n, 2)]
    txs = [
        tx(
            reads=[KVRead(f"big{i:05d}", Version(1, i) if i % 2 == 0 else None)],
            writes=[KVWrite(f"big{i:05d}", value=b"n")],
        )
        for i in range(n)
    ]
    return db_rows, txs


def test_preload_of_2500_keys_in_memory():
    rows, txs = big_block()
    db = seed_db(rows)
    (codes, updates, _), committed = assert_same_as_point_reads(
        db, 5, txs, [V] * len(txs)
    )
    assert codes == [V] * len(txs) and len(updates) == len(txs)
    assert committed.counts() == (2500, 1250, 0)


def test_key_absent_from_preload_falls_back_and_is_counted():
    db = seed_db([("cc1", "k1", b"v1", Version(1, 0)), ("cc1", "k2", b"v2", Version(1, 1))])

    class LosesK2(BlockPreload):
        def load(self, keys, hashed_keys=()):
            super().load([k for k in keys if k != ("cc1", "k2")], hashed_keys)

    txs = [
        tx(reads=[KVRead("k1", Version(1, 0))], writes=[KVWrite("k1", value=b"a")]),
        tx(reads=[KVRead("k2", Version(1, 1))], writes=[KVWrite("k2", value=b"b")]),
        tx(reads=[KVRead("k2", Version(9, 9))]),  # conflicts with tx1's write
    ]
    (codes, _, _), committed = assert_same_as_point_reads(
        db, 5, txs, [V] * 3, committed=LosesK2(db)
    )
    assert codes == [V, V, MVCC]
    # k2's version for tx1's read and its metadata for tx1's write
    assert committed.counts() == (1, 1, 2)
    # a preload that holds nothing answers like the db, one point read each
    bare = BlockPreload(db)
    assert bare.version("cc1", "k1") == Version(1, 0)
    assert bare.metadata("cc1", "nope") is None
    assert bare.counts() == (0, 0, 2)


def test_metadata_only_write_reads_no_value_for_a_key_known_absent():
    db = seed_db([("cc1", "k1", b"v1", Version(1, 0))])
    txs = [
        TxRwSet((NsRwSet("cc1", metadata_writes=(KVMetadataWrite("ghost", (("a", b"1"),)),)),)),
        TxRwSet((NsRwSet("cc1", metadata_writes=(KVMetadataWrite("k1", (("a", b"1"),)),)),)),
    ]
    (codes, updates, _), committed = assert_same_as_point_reads(db, 5, txs, [V, V])
    assert codes == [V, V]
    assert updates.get("cc1", "ghost") is None  # no-op, and no read for it
    assert updates.get("cc1", "k1").value == b"v1"  # the one value read
    assert committed.counts() == (2, 1, 1)


def test_stage_accounts_its_state_reads_once():
    db = seed_db([("cc1", "k1", b"v1", Version(1, 0))])
    committed = BlockPreload(db)
    with fabobs.obs_installed() as reg:
        committed.load([("cc1", "k1"), ("cc1", "k2"), ("cc1", "k1")])
        assert committed.account() == {"keys": 2, "rows": 1, "point_reads": 0}
        committed.version("cc1", "k3")  # the next stage's
        assert committed.account() == {"keys": 0, "rows": 0, "point_reads": 1}
        series = reg.snapshot()["fabric_state_reads_total"]["series"]
    assert series == {"how=preloaded": 2.0, "how=point": 1.0}
