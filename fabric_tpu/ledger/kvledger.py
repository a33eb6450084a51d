"""Per-channel ledger: commit orchestration (reference
core/ledger/kvledger/kv_ledger.go:596-680 + lockbased_txmgr.go).

Commit path per block:
1. MVCC validate-and-prepare against committed state + in-block writes
   (updates TRANSACTIONS_FILTER for MVCC/phantom conflicts);
2. commit-hash chaining: commitHash = SHA-256(varint(len(filter)) ||
   filter || deterministic-update-bytes || previousCommitHash), stored in
   block metadata COMMIT_HASH (kv_ledger.go:758-770) — byte-exact with
   the reference, including the txmgr Updates/KVWrite proto and
   order-preserving version encoding;
3. block appended to the block store;
4. state DB apply; history DB entries.

State and history are derived caches: on open, any blocks present in the
store but missing from state are replayed (recoverDBs analog).
"""

from __future__ import annotations

import hashlib
import os
from typing import Dict, List, Optional, Sequence, Tuple

from fabric_tpu.common import fabobs, flogging
from fabric_tpu.common.faults import fault_point
from fabric_tpu.ledger.blockstore import BlockStore, refuse_corrupt
from fabric_tpu.ledger.mvcc import Validator
from fabric_tpu.ledger.pvtdatastore import MissingEntry, PvtDataStore, PvtEntry
from fabric_tpu.ledger.rwset import TxRwSet, Version
from fabric_tpu.ledger.statedb import (
    BlockPreload,
    HashedUpdateBatch,
    PvtUpdateBatch,
    UpdateBatch,
    VersionedDB,
)
from fabric_tpu.protos import common_pb2, protoutil, txmgr_updates_pb2
from fabric_tpu.ledger.txparse import parse_transaction
from fabric_tpu.common.txflags import TxValidationCode, ValidationFlags

logger = flogging.must_get_logger("kvledger")


def encode_order_preserving_varuint64(n: int) -> bytes:
    """reference common/ledger/util EncodeOrderPreservingVarUint64:
    [num-significant-bytes][big-endian significant bytes]."""
    be = n.to_bytes(8, "big")
    stripped = be.lstrip(b"\x00")
    return bytes([len(stripped)]) + stripped


def version_to_bytes(v: Version) -> bytes:
    return encode_order_preserving_varuint64(
        v.block_num
    ) + encode_order_preserving_varuint64(v.tx_num)


def _proto_varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def deterministic_update_bytes(
    updates: UpdateBatch, hashed: HashedUpdateBatch
) -> bytes:
    """txmgr deterministicBytesForPubAndHashUpdates: namespaces sorted,
    public writes then collections (sorted), keys sorted; namespace/
    collection fields set only on the first entry of each group; the empty
    namespace (channel config) is skipped."""
    # NB: metadata is deliberately excluded from the commit hash —
    # reference update_batch_bytes.go only serializes value writes.
    pub_by_ns: Dict[str, Dict[str, Tuple[Optional[bytes], Version]]] = {}
    for (ns, key), entry in updates.items():
        pub_by_ns.setdefault(ns, {})[key] = (entry.value, entry.version)
    hashed_by_ns: Dict[str, Dict[str, Dict[bytes, Tuple[Optional[bytes], Version]]]] = {}
    for (ns, coll, key_hash), entry in hashed.items():
        hashed_by_ns.setdefault(ns, {}).setdefault(coll, {})[key_hash] = (
            entry.value,
            entry.version,
        )

    msg = txmgr_updates_pb2.Updates()
    for ns in sorted(set(pub_by_ns) | set(hashed_by_ns)):
        if ns == "":
            continue
        first_in_ns = True

        def add(key: bytes, value: Optional[bytes], version: Version, coll: str = ""):
            # `coll` is set only on the first entry of a collection group
            # (caller passes "" for the rest), matching the reference's
            # field-elision rule for both namespace and collection.
            nonlocal first_in_ns
            kv = msg.kvwrites.add()
            if first_in_ns:
                kv.namespace = ns.encode()
                first_in_ns = False
            if coll:
                kv.collection = coll.encode()
            kv.key = key
            kv.isDelete = value is None
            if value is not None:
                kv.value = value
            kv.version_bytes = version_to_bytes(version)

        for key in sorted(pub_by_ns.get(ns, {})):
            value, version = pub_by_ns[ns][key]
            add(key.encode(), value, version)
        for coll in sorted(hashed_by_ns.get(ns, {})):
            for j, key_hash in enumerate(sorted(hashed_by_ns[ns][coll])):
                vh, version = hashed_by_ns[ns][coll][key_hash]
                add(key_hash, vh, version, coll=coll if j == 0 else "")
    return msg.SerializeToString()


def pvt_data_matches_hashes(
    rwset: Optional[TxRwSet], ns: str, coll: str, raw: bytes
) -> bool:
    """Does a cleartext KVRWSet match the tx's on-block hashed writes for
    (ns, coll)? Used to screen untrusted (gossip-fetched) private data
    before commit — a mismatch is treated as missing, never an error
    (reference gossip/privdata purge of invalid fetched data)."""
    from fabric_tpu.protos import kv_rwset_pb2

    expected: Dict[bytes, Tuple[bool, bytes]] = {}
    if rwset is not None:
        for ns_rw in rwset.ns_rw_sets:
            if ns_rw.namespace != ns:
                continue
            for c in ns_rw.coll_hashed:
                if c.collection_name == coll:
                    for hw in c.hashed_writes:
                        expected[hw.key_hash] = (hw.is_delete, hw.value_hash)
    kv = kv_rwset_pb2.KVRWSet()
    try:
        kv.ParseFromString(raw)
    except Exception:  # fablint: disable=broad-except  # malformed pvt payload = explicit False (lane invalid)
        return False
    for w in kv.writes:
        kh = hashlib.sha256(w.key.encode()).digest()
        exp = expected.get(kh)
        if exp is None:
            return False
        is_del, vh = exp
        if w.is_delete != is_del:
            return False
        if not w.is_delete and hashlib.sha256(w.value).digest() != vh:
            return False
    return True


class KVLedger:
    """One channel's ledger (block store + state + history).

    `persistent=True` (the default) keeps state + history in an embedded
    on-disk B-tree (fabric_tpu.ledger.persistent, the stateleveldb
    analog) with a per-block savepoint, so reopening a tall ledger
    replays only the blocks committed after the last durable state write
    instead of the whole chain (kv_ledger.go recoverDBs). In-memory mode
    remains for simulation/tests and rebuilds everything by replay."""

    def __init__(
        self,
        ledger_dir: str,
        channel_id: str,
        btl_policy=None,
        persistent: bool = True,
        device_mvcc: bool = False,
        # optional public-state mirror (ledger/statecouch.CouchStateAdapter):
        # receives each block's public UpdateBatch after the embedded
        # commit — best-effort, a mirror outage never blocks consensus
        state_mirror=None,
    ):
        self.state_mirror = state_mirror
        self.channel_id = channel_id
        self.persistent = persistent
        # SURVEY P5: resolve block-internal MVCC invalidation chains on
        # device (mvcc_device.DeviceValidator) instead of the Python scan
        self.device_mvcc = device_mvcc
        self.history: Dict[Tuple[str, str], List[Version]] = {}
        self.commit_hash = b""
        self._closed = False
        try:
            self.block_store = BlockStore(
                os.path.join(ledger_dir, f"{channel_id}.chain")
            )
            self.pvt_store = PvtDataStore(
                os.path.join(ledger_dir, f"{channel_id}.pvtdata"),
                btl_policy=btl_policy,
            )
            if persistent:
                from fabric_tpu.ledger.persistent import SqliteVersionedDB

                self.state_db = SqliteVersionedDB(
                    os.path.join(ledger_dir, f"{channel_id}.state.db")
                )
            else:
                self.state_db = VersionedDB()
            from fabric_tpu.ledger.confighistory import ConfigHistoryMgr

            self.config_history = ConfigHistoryMgr(
                self.state_db if persistent else None
            )
            self._recover()
        except BaseException:
            # a refused recovery — whether raised opening a store (a
            # corrupt chain/pvtdata refuses in its constructor) or
            # during replay — must not leak the file handles already
            # open: the operator will reopen (possibly with
            # RECOVERY_STRICT=0) or run the offline admin CLI against
            # the same directory
            self.close()
            raise

    # -- recovery: replay the block store into derived state ---------------
    def _recover(self) -> None:
        """Replay blocks the store has but the derived caches lack
        (kv_ledger.go recoverDBs), hardened for the fabcrash kill
        windows:

        * block store AHEAD of the state db (crash after append, before
          the sqlite transaction committed): replay the gap idempotently
          into state + history + pvt (INSERT OR REPLACE semantics);
        * pvt store BEHIND a stored block (its torn tail was truncated):
          record missing-data markers so the reconciler re-fetches — the
          hashed writes are on-block and already replayed;
        * state db AHEAD of the block store (chain truncated behind our
          back): nothing can be repaired forward — refuse to serve
          (strict, the default) or rebuild the derived caches from the
          chain (FABRIC_TPU_RECOVERY_STRICT=0 salvage)."""
        height = self.block_store.height
        start = 0
        if self.persistent:
            savepoint = self.state_db.savepoint()
            if savepoint is not None:
                if savepoint >= height:
                    refuse_corrupt(
                        logger,
                        f"[{self.channel_id}] state db",
                        f"savepoint {savepoint} is AHEAD of block store "
                        f"height {height}: the chain lost committed "
                        f"blocks behind our back",
                        "statedb-ahead",
                        "rebuild derived state from the surviving chain",
                    )
                    self.state_db.clear()
                    savepoint = None
            if savepoint is not None:
                start = savepoint + 1
                self.commit_hash = self.state_db.commit_hash()
        # pvt torn-tail repair for blocks the state db already covers —
        # the replay loop below repairs its own blocks' pvt gaps.  On a
        # snapshot-bootstrapped ledger blocks below the base are not
        # stored: nothing to derive markers from, start at the base.
        for bn in range(
            max(
                self.pvt_store.last_committed_block + 1,
                self.block_store.base_height,
            ),
            min(start, height),
        ):
            block = self.block_store.get_block_by_number(bn)
            self._repair_pvt_gap(
                block, self._extract_rwsets(block), self._codes(block)
            )
        recovered = 0
        for block in self.block_store.iter_blocks(start):
            self._apply_committed_block(block)
            recovered += 1
        if recovered and self.persistent:
            # persistent mode replays only a crash gap (non-persistent
            # replays the whole chain by design every open)
            logger.warning(
                "[%s] recovery replayed %d block(s) above state savepoint "
                "into state/pvt", self.channel_id, recovered,
            )
            fabobs.obs_count(
                "fabric_ledger_recovered_blocks_total", recovered
            )

    def _apply_committed_block(self, block: common_pb2.Block) -> None:
        flags = self._extract_flags(block)
        rwsets = self._extract_rwsets(block)
        # Restore the COMMIT_HASH chain so post-restart commits keep
        # chaining from the last stored hash (kv_ledger.go recoverDBs +
        # addBlockCommitHash: the chain must not reset on restart).
        metas = block.metadata.metadata
        if len(metas) > common_pb2.COMMIT_HASH and metas[common_pb2.COMMIT_HASH]:
            meta = protoutil.unmarshal(
                common_pb2.Metadata, metas[common_pb2.COMMIT_HASH]
            )
            self.commit_hash = meta.value
        codes = [
            TxValidationCode.VALID
            if flags.is_valid(i)
            else TxValidationCode(int(flags.asarray()[i]))
            for i in range(len(flags))
        ]
        # On replay the stored filter already includes MVCC verdicts; apply
        # writes of the VALID txs without re-deciding.
        _, updates, hashed = Validator(self.state_db).validate_and_prepare_batch(
            block.header.number, rwsets, codes, do_mvcc=False
        )
        # pvt cleartext state is derived from the pvt store on replay
        if self.pvt_store.last_committed_block < block.header.number:
            self._repair_pvt_gap(block, rwsets, codes)
        pvt_batch = self._pvt_batch(
            block.header.number,
            self.pvt_store.get_pvt_data_by_block(block.header.number),
            codes,
            rwsets,
            verify_hashes=False,
        )
        self._commit_state(block, updates, hashed, pvt_batch)

    def _codes(self, block: common_pb2.Block) -> List[TxValidationCode]:
        flags = self._extract_flags(block)
        return [TxValidationCode(int(c)) for c in flags.asarray()]

    def _repair_pvt_gap(self, block, rwsets, codes) -> None:
        """The pvt record for an already-stored block is gone (its torn
        tail was truncated by recovery).  The cleartext cannot be
        recreated locally — record missing markers for every collection
        the block's VALID txs wrote, so the guard invariant (pvt store
        never behind the chain) holds and the reconciler re-fetches.
        The on-block hashed writes replay regardless."""
        missing = [
            MissingEntry(tx_num, ns_rw.namespace, coll.collection_name)
            for tx_num, (rwset, code) in enumerate(zip(rwsets, codes))
            if code == TxValidationCode.VALID and rwset is not None
            for ns_rw in rwset.ns_rw_sets
            for coll in ns_rw.coll_hashed
            if coll.hashed_writes
        ]
        logger.warning(
            "[%s] pvt store behind stored block %d on recovery: "
            "recording %d missing-data marker(s) for the reconciler",
            self.channel_id, block.header.number, len(missing),
        )
        self.pvt_store.commit(block.header.number, [], missing)

    def _extract_flags(self, block: common_pb2.Block) -> ValidationFlags:
        raw = bytes(block.metadata.metadata[common_pb2.TRANSACTIONS_FILTER])
        return (
            ValidationFlags.from_bytes(raw)
            if raw
            else ValidationFlags(len(block.data.data), TxValidationCode.VALID)
        )

    def _extract_rwsets(self, block: common_pb2.Block) -> List[Optional[TxRwSet]]:
        return [
            parse_transaction(i, data).rwset
            for i, data in enumerate(block.data.data)
        ]

    # -- the commit path ---------------------------------------------------
    def commit(
        self,
        block: common_pb2.Block,
        rwsets: Optional[List[Optional[TxRwSet]]] = None,
        pvt_data: Optional[Dict[Tuple[int, str, str], bytes]] = None,
        missing_pvt: Optional[List[MissingEntry]] = None,
        committed: Optional[BlockPreload] = None,
    ) -> ValidationFlags:
        """ValidateAndPrepare + commit (kv_ledger.go commit): assumes the
        block already carries the txvalidator's TRANSACTIONS_FILTER; MVCC
        verdicts are merged in here and the final filter is what gets
        stored. `rwsets` lets the caller share the validator's parse pass
        (hot path); when absent the block is re-decoded (replay path).

        `pvt_data` maps (tx_num, ns, collection) -> serialized cleartext
        KVRWSet assembled by the coordinator; writes are hash-checked
        against the tx's on-block hashed rwset before being applied
        (kv_ledger.go CommitLegacy's pvt data validation).

        `committed` is the block's preload of committed rows where the
        caller began one (Channel.store_block's policy stage): MVCC reads
        only the rows it lacks."""
        import time as _time

        t0 = _time.perf_counter()
        flags = self._extract_flags(block)
        if rwsets is None:
            rwsets = self._extract_rwsets(block)
        incoming = [TxValidationCode(int(c)) for c in flags.asarray()]
        if committed is None:
            committed = BlockPreload(self.state_db)
        if self.device_mvcc:
            from fabric_tpu.ledger.mvcc_device import DeviceValidator

            validator = DeviceValidator(self.state_db)
            codes, updates, hashed = validator.validate_and_prepare_batch(
                block.header.number, rwsets, incoming
            )
        else:
            codes, updates, hashed = Validator(
                self.state_db
            ).validate_and_prepare_batch(
                block.header.number, rwsets, incoming, committed=committed
            )
        state_reads = committed.account()
        # Assemble + hash-check private data FIRST: anything that can raise
        # must run before commit_hash is chained or any store is touched,
        # or a failed commit leaves this peer's COMMIT_HASH diverged from
        # the network on retry.
        entries = [
            PvtEntry(tx_num, ns, coll, raw)
            for (tx_num, ns, coll), raw in sorted((pvt_data or {}).items())
            if tx_num < len(codes) and codes[tx_num] == TxValidationCode.VALID
        ]
        pvt_batch = self._pvt_batch(
            block.header.number, entries, codes, rwsets, verify_hashes=True
        )
        # A tx that ended up invalid (e.g. MVCC) needs no private data —
        # a missing marker for it would feed the reconciler forever.
        missing = [
            m
            for m in (missing_pvt or [])
            if m.tx_num < len(codes)
            and codes[m.tx_num] == TxValidationCode.VALID
        ]

        for i, code in enumerate(codes):
            flags.set_flag(i, code)
        protoutil.init_block_metadata(block)
        block.metadata.metadata[common_pb2.TRANSACTIONS_FILTER] = flags.tobytes()

        # commit hash (kv_ledger.go addBlockCommitHash)
        update_bytes = deterministic_update_bytes(updates, hashed)
        filter_bytes = flags.tobytes()
        value = (
            _proto_varint(len(filter_bytes))
            + filter_bytes
            + update_bytes
            + self.commit_hash
        )
        self.commit_hash = hashlib.sha256(value).digest()
        meta = common_pb2.Metadata()
        meta.value = self.commit_hash
        block.metadata.metadata[common_pb2.COMMIT_HASH] = meta.SerializeToString()

        # pvtdata store commit precedes the block append (store.go Commit);
        # if a crash hit between the two last time, the pvt record for this
        # block is already durable — skip, don't error, so redelivery of
        # the block can complete the interrupted commit.
        t1 = _time.perf_counter()
        # kill window (fabcrash): nothing for this block is durable yet —
        # a kill here loses the block entirely and the restart re-pulls it
        fault_point("kvledger.commit.pre_pvt", key=int(block.header.number))
        if self.pvt_store.last_committed_block < block.header.number:
            self.pvt_store.commit(block.header.number, entries, missing)

        self.block_store.add_block(block)
        # kill window (fabcrash): pvt + block durable, state db not —
        # recovery replays this block into state/pvt idempotently
        fault_point(
            "kvledger.commit.post_block", key=int(block.header.number)
        )
        t2 = _time.perf_counter()
        self._commit_state(block, updates, hashed, pvt_batch)
        t3 = _time.perf_counter()
        # per-stage split for the commit log line + committer metrics
        # (reference kv_ledger.go:663-672 state_validation /
        # block_and_pvtdata_commit / state_commit)
        self.last_commit_timings = {
            "state_validation": t1 - t0,
            "block_and_pvtdata_commit": t2 - t1,
            "state_commit": t3 - t2,
        }
        # the same four clock reads as spans, so every block's split is in
        # the flight ring and not the last block's alone
        number = block.header.number
        fabobs.obs_record_span(
            "ledger.mvcc", t0, t1, block=number, **state_reads
        )
        fabobs.obs_record_span("ledger.block_append", t1, t2, block=number)
        fabobs.obs_record_span("ledger.state_commit", t2, t3, block=number)
        return flags

    def _pvt_batch(
        self,
        block_num: int,
        entries: List[PvtEntry],
        codes: List[TxValidationCode],
        rwsets: List[Optional[TxRwSet]],
        verify_hashes: bool,
    ) -> PvtUpdateBatch:
        """Cleartext private writes -> state batch, checked against the
        tx's hashed rwset (the on-block source of truth)."""
        import hashlib as _hashlib

        from fabric_tpu.protos import kv_rwset_pb2

        batch = PvtUpdateBatch()
        for e in entries:
            if e.tx_num >= len(codes) or codes[e.tx_num] != TxValidationCode.VALID:
                continue
            expected: Dict[bytes, Tuple[bool, bytes]] = {}
            rwset = rwsets[e.tx_num] if e.tx_num < len(rwsets) else None
            if rwset is not None:
                for ns_rw in rwset.ns_rw_sets:
                    if ns_rw.namespace != e.namespace:
                        continue
                    for coll in ns_rw.coll_hashed:
                        if coll.collection_name == e.collection:
                            for hw in coll.hashed_writes:
                                expected[hw.key_hash] = (hw.is_delete, hw.value_hash)
            kv = kv_rwset_pb2.KVRWSet()
            kv.ParseFromString(e.rwset)
            for w in kv.writes:
                kh = _hashlib.sha256(w.key.encode()).digest()
                exp = expected.get(kh)
                if verify_hashes:
                    if exp is None:
                        raise ValueError(
                            f"pvt write {e.namespace}/{e.collection}/{w.key} "
                            "not present in the hashed rwset"
                        )
                    is_del, vh = exp
                    if w.is_delete != is_del or (
                        not w.is_delete
                        and _hashlib.sha256(w.value).digest() != vh
                    ):
                        raise ValueError(
                            f"pvt value hash mismatch for "
                            f"{e.namespace}/{e.collection}/{w.key}"
                        )
                batch.put(
                    e.namespace,
                    e.collection,
                    w.key,
                    None if w.is_delete else w.value,
                    Version(block_num, e.tx_num),
                )
        return batch

    def _commit_state(
        self,
        block: common_pb2.Block,
        updates: UpdateBatch,
        hashed: HashedUpdateBatch,
        pvt: Optional[PvtUpdateBatch] = None,
    ) -> None:
        if self.persistent:
            # state + history + savepoint + commit hash, one transaction
            self.state_db.commit_block(
                updates,
                hashed,
                pvt,
                savepoint=block.header.number,
                commit_hash=self.commit_hash,
            )
        else:
            for (ns, key), entry in updates.items():
                self.history.setdefault((ns, key), []).append(entry.version)
            self.state_db.apply_updates(updates, hashed, pvt)
        # collection-config history (confighistory/mgr.go commit hook)
        self.config_history.record_from_updates(block.header.number, updates)
        if self.state_mirror is not None and len(updates):
            # operational mirror (statecouch): best-effort, post-commit —
            # the embedded store is authoritative and a mirror outage
            # must never block the commit path
            try:
                self.state_mirror.apply_updates(updates)
            except Exception as exc:  # noqa: BLE001
                logger.warning(
                    "[%s] state mirror update failed at block %d: %s",
                    self.channel_id, block.header.number, exc,
                )

    def commit_reconciled_pvt(self, items) -> int:
        """Reconciler write-back (reference reconcile.go ->
        CommitPvtDataOfOldBlocks): late-arriving private data for already
        committed blocks, hash-checked against the on-block hashed rwset;
        entries that fail verification are dropped, good ones land in the
        pvt store AND the cleartext pvt state. `items` is
        [(block_num, tx_num, ns, coll, kvrwset_bytes)]; returns how many
        entries were accepted."""
        by_block: Dict[int, List[PvtEntry]] = {}
        for block_num, tx_num, ns, coll, raw in items:
            by_block.setdefault(block_num, []).append(
                PvtEntry(tx_num, ns, coll, raw)
            )
        accepted = 0
        for block_num in sorted(by_block):
            block = self.block_store.get_block_by_number(block_num)
            if block is None:
                continue
            flags = self._extract_flags(block)
            rwsets = self._extract_rwsets(block)
            codes = [TxValidationCode(int(c)) for c in flags.asarray()]
            good: List[PvtEntry] = []
            batch = PvtUpdateBatch()
            for entry in by_block[block_num]:
                try:
                    if not self._pvt_entry_complete(entry, rwsets):
                        continue  # subset/empty payload: an attacker must
                        # not be able to clear the missing marker
                    one = self._pvt_batch(
                        block_num, [entry], codes, rwsets, verify_hashes=True
                    )
                except Exception:  # fablint: disable=broad-except  # includes proto DecodeError;
                    # one forged/mismatched/garbled entry must not abort
                    # the rest of the batch
                    continue
                for (ns, coll, key), e in one.items():
                    # never regress pvt state a LATER block already wrote
                    # (reference CommitPvtDataOfOldBlocks version check)
                    current = self.state_db.get_private_data(ns, coll, key)
                    if current is not None and not (
                        current.version.block_num < e.version.block_num
                        or (
                            current.version.block_num == e.version.block_num
                            and current.version.tx_num <= e.version.tx_num
                        )
                    ):
                        continue
                    batch.put(ns, coll, key, e.value, e.version)
                good.append(entry)
            if not good:
                continue
            self.pvt_store.commit_pvt_data_of_old_blocks(block_num, good)
            self.state_db.apply_updates(UpdateBatch(), None, batch)
            accepted += len(good)
        return accepted

    def _pvt_entry_complete(self, entry: PvtEntry, rwsets) -> bool:
        """The payload must cover EVERY key hash the tx's on-block hashed
        rwset lists for this collection — partial data must not clear the
        missing marker."""
        import hashlib as _hashlib

        from fabric_tpu.protos import kv_rwset_pb2

        expected = set()
        rwset = rwsets[entry.tx_num] if entry.tx_num < len(rwsets) else None
        if rwset is None:
            return False
        for ns_rw in rwset.ns_rw_sets:
            if ns_rw.namespace != entry.namespace:
                continue
            for coll in ns_rw.coll_hashed:
                if coll.collection_name == entry.collection:
                    expected = {hw.key_hash for hw in coll.hashed_writes}
        if not expected:
            return False
        kv = kv_rwset_pb2.KVRWSet()
        kv.ParseFromString(entry.rwset)
        provided = {
            _hashlib.sha256(w.key.encode()).digest() for w in kv.writes
        }
        return provided == expected

    # -- admin ops (reference kvledger reset.go / rollback.go /
    #    rebuild_dbs.go: state & history are derived caches over the
    #    block store, so both ops are truncate-then-replay) -------------
    def rebuild_dbs(self) -> None:
        """Drop the derived state/history caches and replay the block
        store (peer node rebuild-dbs / reset). Refused on a
        snapshot-bootstrapped ledger: pre-snapshot state exists only in
        the (gone) snapshot, not the block store (the reference refuses
        reset/rollback/rebuild on bootstrapped ledgers too)."""
        if self.block_store.base_height > 0:
            raise ValueError(
                "cannot rebuild a snapshot-bootstrapped ledger: state "
                f"below block {self.block_store.base_height} is not in "
                "the block store"
            )
        if self.persistent:
            self.state_db.clear()
        else:
            # carry the generation stamp forward (+1): a resident MVCC
            # table bound to the old db must see the rebuild as an
            # out-of-band mutation, not a fresh generation-0 twin
            old_generation = self.state_db.state_generation
            self.state_db = VersionedDB()
            self.state_db.state_generation = old_generation + 1
        from fabric_tpu.ledger.confighistory import ConfigHistoryMgr

        self.config_history = ConfigHistoryMgr(
            self.state_db if self.persistent else None
        )
        self.history = {}
        self.commit_hash = b""
        self._recover()

    def rollback(self, target_block: int) -> None:
        """Roll the channel back so target_block is the last block."""
        if self.block_store.base_height > 0:
            raise ValueError(
                "cannot roll back a snapshot-bootstrapped ledger"
            )
        self.block_store.truncate_to(target_block + 1)
        # the pvt store must rewind too, or re-committed blocks skip pvt
        # persistence (last_committed guard) and replay stale records
        self.pvt_store.rollback_to(target_block + 1)
        self.rebuild_dbs()

    def close(self) -> None:
        """Release file handles/connections (ledgermgmt.Close): required
        before another process (or the offline admin CLI) opens the same
        ledger directory.  Idempotent and safe on a partially-constructed
        ledger — recovery error paths call it before re-raising, and a
        crash-restart runbook may close defensively."""
        if getattr(self, "_closed", False):
            return
        self._closed = True
        for store in (
            getattr(self, "block_store", None),
            getattr(self, "pvt_store", None),
            getattr(self, "state_db", None) if self.persistent else None,
        ):
            if store is not None:
                store.close()

    # -- queries (qscc analog) --------------------------------------------
    @property
    def height(self) -> int:
        return self.block_store.height

    def get_state(self, ns: str, key: str) -> Optional[bytes]:
        vv = self.state_db.get_state(ns, key)
        return vv.value if vv else None

    def get_private_data(self, ns: str, coll: str, key: str) -> Optional[bytes]:
        vv = self.state_db.get_private_data(ns, coll, key)
        return vv.value if vv else None

    def get_history_for_key(self, ns: str, key: str) -> List[Version]:
        if self.persistent:
            return self.state_db.get_history(ns, key)
        return list(self.history.get((ns, key), []))

    def execute_query(self, ns: str, query) -> List[Tuple[str, bytes]]:
        """Rich selector query over committed state (statecouchdb.go:695)."""
        return self.state_db.execute_query(ns, query)

    def tx_exists(self, txid: str) -> bool:
        return self.block_store.tx_exists(txid)
