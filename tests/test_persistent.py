"""Persistent state/history (sqlite stateleveldb analog) + rich selector
queries (statecouchdb analog)."""

import json

import pytest

from fabric_tpu.ledger import rwset as rw
from fabric_tpu.ledger.kvledger import KVLedger
from fabric_tpu.ledger.persistent import SqliteVersionedDB
from fabric_tpu.ledger.queries import QueryError, execute, matches
from fabric_tpu.ledger.rwset import Version
from fabric_tpu.ledger.simulator import TxSimulator
from fabric_tpu.ledger.statedb import (
    HashedUpdateBatch,
    PvtUpdateBatch,
    UpdateBatch,
    VersionedDB,
)
from fabric_tpu.protos import protoutil


def make_block(number, prev_hash, payloads):
    block = protoutil.new_block(number, prev_hash)
    for p in payloads:
        block.data.data.append(p)
    return protoutil.seal_block(block)


def write_rwset(ns, items):
    return rw.TxRwSet(
        (
            rw.NsRwSet(
                ns, (), tuple(rw.KVWrite(k, v is None, v or b"") for k, v in items)
            ),
        )
    )


# ----------------------------------------------------------------------
# SqliteVersionedDB vs in-memory VersionedDB parity
# ----------------------------------------------------------------------


def _fill(db):
    batch = UpdateBatch()
    batch.put("ns1", "a", b"1", Version(0, 0))
    batch.put("ns1", "b", b"2", Version(0, 1), metadata=b"md")
    batch.put("ns1", "béta", b"3", Version(0, 2))
    batch.put("ns2", "z", b"4", Version(0, 3))
    hashed = HashedUpdateBatch()
    hashed.put("ns1", "coll", b"\x01\x02", b"vh", Version(0, 1), metadata=b"hm")
    pvt = PvtUpdateBatch()
    pvt.put("ns1", "coll", "pk", b"pv", Version(0, 1))
    db.apply_updates(batch, hashed, pvt)


@pytest.mark.parametrize("factory", [VersionedDB, "sqlite"])
def test_db_parity(factory, tmp_path):
    db = (
        SqliteVersionedDB(str(tmp_path / "s.db"))
        if factory == "sqlite"
        else factory()
    )
    _fill(db)
    assert db.get_state("ns1", "a").value == b"1"
    assert db.get_state("ns1", "b").metadata == b"md"
    assert db.get_state("ns1", "nope") is None
    assert db.get_version("ns1", "b") == Version(0, 1)
    assert db.get_hashed_state("ns1", "coll", b"\x01\x02").value == b"vh"
    assert db.get_hashed_metadata("ns1", "coll", b"\x01\x02") == b"hm"
    assert db.get_private_data("ns1", "coll", "pk").value == b"pv"
    assert db.num_keys() == 4
    scan = [(k, vv.value) for k, vv in db.get_state_range("ns1", "a", "c", False)]
    assert scan == [("a", b"1"), ("b", b"2"), ("béta", b"3")]
    scan = [(k, vv.value) for k, vv in db.get_state_range("ns1", "b", "", False)]
    assert [k for k, _ in scan] == ["b", "béta"]
    assert [x[0:2] for x in db.iter_all_state()] == [
        ("ns1", "a"),
        ("ns1", "b"),
        ("ns1", "béta"),
        ("ns2", "z"),
    ]
    # deletes
    batch = UpdateBatch()
    batch.delete("ns1", "a", Version(1, 0))
    db.apply_updates(batch)
    assert db.get_state("ns1", "a") is None
    assert db.num_keys() == 3


def test_sqlite_persists_across_reopen(tmp_path):
    path = str(tmp_path / "s.db")
    db = SqliteVersionedDB(path)
    _fill(db)
    db.commit_block(UpdateBatch(), savepoint=7, commit_hash=b"\xaa" * 32)
    db.close()
    db2 = SqliteVersionedDB(path)
    assert db2.get_state("ns1", "béta").value == b"3"
    assert db2.savepoint() == 7
    assert db2.commit_hash() == b"\xaa" * 32


# ----------------------------------------------------------------------
# load_committed (the block preload's bulk read) and the differential runs
# of tests/test_mvcc.py on the sqlite state db
# ----------------------------------------------------------------------


@pytest.mark.parametrize("factory", [VersionedDB, "sqlite"])
def test_load_committed_parity(factory, tmp_path):
    db = (
        SqliteVersionedDB(str(tmp_path / "s.db"))
        if factory == "sqlite"
        else factory()
    )
    _fill(db)
    keys = [("ns1", "a"), ("ns1", "b"), ("ns1", "nope"), ("ns2", "z"),
            ("ns3", "a"), ("ns1", "a")]
    hashed_keys = [("ns1", "coll", b"\x01\x02"), ("ns1", "coll", b"\x09"),
                   ("ns1", "other", b"\x01\x02")]
    pub, hashed = db.load_committed(keys, hashed_keys)
    assert pub == {
        ("ns1", "a"): (Version(0, 0), None),
        ("ns1", "b"): (Version(0, 1), b"md"),
        ("ns1", "nope"): None,
        ("ns2", "z"): (Version(0, 3), None),
        ("ns3", "a"): None,
    }
    assert hashed == {
        ("ns1", "coll", b"\x01\x02"): (Version(0, 1), b"hm"),
        ("ns1", "coll", b"\x09"): None,
        ("ns1", "other", b"\x01\x02"): None,
    }
    assert db.load_committed([]) == ({}, {})


def _statements(db):
    seen = []
    db._db.set_trace_callback(lambda sql: seen.append(sql.split()[0]))
    return seen


@pytest.mark.parametrize("seed", range(6))
def test_preload_equals_point_reads_on_sqlite(seed, tmp_path):
    from test_mvcc import MVCC, POLICY_FAILURE, V, run_differential

    db = SqliteVersionedDB(str(tmp_path / "s.db"))
    assert {V, MVCC, POLICY_FAILURE} <= run_differential(db, seed)


def test_preload_of_2500_keys_is_three_chunks_in_one_snapshot(tmp_path):
    from test_mvcc import V, assert_same_as_point_reads, big_block

    db = SqliteVersionedDB(str(tmp_path / "s.db"))
    rows, txs = big_block()
    batch = UpdateBatch()
    for ns, key, value, version in rows:
        batch.put(ns, key, value, version)
    db.apply_updates(batch)
    seen = _statements(db)
    pub, _ = db.load_committed(
        [("cc1", r.key) for t in txs for r in t.ns_rw_sets[0].reads]
    )
    # 900 + 900 + 700 keys of one namespace, inside one read transaction
    assert seen == ["BEGIN", "SELECT", "SELECT", "SELECT", "COMMIT"]
    assert not db._db.in_transaction
    assert len(pub) == 2500
    assert sum(1 for row in pub.values() if row is not None) == 1250
    del seen[:]
    (codes, updates, _), committed = assert_same_as_point_reads(
        db, 5, txs, [V] * len(txs)
    )
    assert codes == [V] * len(txs) and len(updates) == len(txs)
    assert committed.counts() == (2500, 1250, 0)
    # the oracle's 5,000 point reads, then the preload's three
    assert seen.count("SELECT") == 2 * 2500 + 3


def test_one_statement_needs_no_transaction_of_its_own(tmp_path):
    db = SqliteVersionedDB(str(tmp_path / "s.db"))
    _fill(db)
    seen = _statements(db)
    db.load_committed([("ns1", "a"), ("ns1", "b")])
    assert seen == ["SELECT"]


# ----------------------------------------------------------------------
# KVLedger: restart without replay
# ----------------------------------------------------------------------


def test_kvledger_restart_uses_savepoint_not_replay(tmp_path, monkeypatch):
    ledger = KVLedger(str(tmp_path), "ch")
    prev = b""
    for n in range(5):
        block = make_block(n, prev, [b"opaque-envelope"])
        ledger.commit(block, rwsets=[write_rwset("cc", [(f"k{n}", b"v%d" % n)])])
        prev = protoutil.block_header_hash(block.header)
    saved_hash = ledger.commit_hash
    assert ledger.get_state("cc", "k4") == b"v4"
    assert ledger.get_history_for_key("cc", "k3") == [Version(3, 0)]
    ledger.block_store.close()
    ledger.pvt_store.close()
    ledger.state_db.close()

    replays = []
    monkeypatch.setattr(
        KVLedger,
        "_apply_committed_block",
        lambda self, block: replays.append(block.header.number),
    )
    again = KVLedger(str(tmp_path), "ch")
    # all 5 blocks were under the savepoint: recovery replayed NOTHING
    assert replays == []
    assert again.height == 5
    assert again.get_state("cc", "k2") == b"v2"
    assert again.commit_hash == saved_hash
    assert again.get_history_for_key("cc", "k1") == [Version(1, 0)]


def test_kvledger_replays_only_tail_after_partial_commit(tmp_path, monkeypatch):
    """A block in the store but past the savepoint (crash between block
    append and state write) is replayed on reopen — and only it."""
    ledger = KVLedger(str(tmp_path), "ch")
    b0 = make_block(0, b"", [b"x"])
    ledger.commit(b0, rwsets=[write_rwset("cc", [("k0", b"v0")])])
    # simulate the crash window: append block 1 to the store only
    b1 = make_block(1, protoutil.block_header_hash(b0.header), [b"y"])
    protoutil.init_block_metadata(b1)
    ledger.block_store.add_block(b1)
    ledger.block_store.close()
    ledger.pvt_store.close()
    ledger.state_db.close()

    replays = []
    orig = KVLedger._apply_committed_block
    monkeypatch.setattr(
        KVLedger,
        "_apply_committed_block",
        lambda self, block: (replays.append(block.header.number), orig(self, block)),
    )
    again = KVLedger(str(tmp_path), "ch")
    assert replays == [1]
    assert again.get_state("cc", "k0") == b"v0"


# ----------------------------------------------------------------------
# rich queries
# ----------------------------------------------------------------------

MARBLES = [
    ("m1", {"docType": "marble", "color": "red", "size": 5, "owner": "tom"}),
    ("m2", {"docType": "marble", "color": "blue", "size": 10, "owner": "jerry"}),
    ("m3", {"docType": "marble", "color": "red", "size": 25, "owner": "tom"}),
    ("m4", {"docType": "pebble", "color": "red", "size": 5, "owner": "anna"}),
    ("m5", {"docType": "marble", "color": "green", "size": 50, "owner": "anna",
            "tags": ["shiny", "rare"]}),
]


def _query_db(db_kind, tmp_path):
    db = (
        SqliteVersionedDB(str(tmp_path / "q.db"))
        if db_kind == "sqlite"
        else VersionedDB()
    )
    batch = UpdateBatch()
    for i, (key, doc) in enumerate(MARBLES):
        batch.put("marbles", key, json.dumps(doc).encode(), Version(0, i))
    batch.put("marbles", "raw", b"\x00not-json", Version(0, 9))
    db.apply_updates(batch)
    return db


@pytest.mark.parametrize("db_kind", ["mem", "sqlite"])
def test_rich_query_selectors(db_kind, tmp_path):
    db = _query_db(db_kind, tmp_path)

    def q(sel, **kw):
        return [k for k, _ in db.execute_query("marbles", {"selector": sel, **kw})]

    assert q({"color": "red"}) == ["m1", "m3", "m4"]
    assert q({"docType": "marble", "color": "red"}) == ["m1", "m3"]
    assert q({"size": {"$gt": 5, "$lte": 25}}) == ["m2", "m3"]
    assert q({"owner": {"$in": ["tom", "anna"]}}) == ["m1", "m3", "m4", "m5"]
    assert q({"$or": [{"color": "blue"}, {"size": 50}]}) == ["m2", "m5"]
    assert q({"$not": {"docType": "marble"}}) == ["m4"]
    assert q({"tags": {"$elemMatch": {"$eq": "rare"}}}) == ["m5"]
    assert q({"tags": {"$exists": True}}) == ["m5"]
    assert q({"owner": {"$regex": "^t"}}) == ["m1", "m3"]
    assert q({"color": "red"}, limit=2) == ["m1", "m3"]
    assert q({"color": "red"}, skip=1) == ["m3", "m4"]
    assert q({"docType": "marble"}, sort=[{"size": "desc"}]) == [
        "m5", "m3", "m2", "m1",
    ]
    # projection
    rows = db.execute_query(
        "marbles", {"selector": {"color": "blue"}, "fields": ["owner"]}
    )
    assert rows == [("m2", b'{"owner": "jerry"}')]
    # non-JSON rows never match
    assert q({}) == ["m1", "m2", "m3", "m4", "m5"]


def test_query_errors():
    with pytest.raises(QueryError):
        execute([], {"no_selector": {}})
    with pytest.raises(QueryError):
        execute([("k", b"{}")], {"selector": {"$bogus": []}})
    with pytest.raises(QueryError):
        matches({"f": {"$unknown": 1}}, {"f": 1})


def test_simulator_rich_query_records_no_reads(tmp_path):
    db = _query_db("mem", tmp_path)
    sim = TxSimulator(db, "tx1")
    rows = sim.execute_query("marbles", json.dumps({"selector": {"owner": "tom"}}))
    assert [k for k, _ in rows] == ["m1", "m3"]
    res = sim.get_tx_simulation_results()
    pub = res.rwset
    # rich queries are not phantom-protected: empty read set
    assert all(not ns.reads and not ns.range_queries for ns in pub.ns_rw_sets)
