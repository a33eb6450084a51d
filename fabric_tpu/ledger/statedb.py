"""Versioned state database (reference statedb SPI + stateleveldb).

An embedded ordered KV store holding (value, version) per (namespace, key)
plus the hashed private-data namespaces (privacyenabledstate analog). The
in-memory index is a dict plus a sorted-key view for range scans; the
kvledger layer persists through snapshots of the block store (state is a
derived cache, rebuildable — the reference's crash-consistency model,
SURVEY.md §5 checkpoint/resume).
"""

from __future__ import annotations

import bisect
from dataclasses import dataclass
from typing import Dict, Iterable, Iterator, List, NamedTuple, Optional, Tuple

from fabric_tpu.common import fabobs
from fabric_tpu.ledger.rwset import Version


@dataclass(frozen=True)
class VersionedValue:
    value: bytes
    version: Version
    metadata: Optional[bytes] = None  # serialized KVMetadataWrite entries


class BatchEntry(NamedTuple):
    """One pending update: value None = key delete; metadata is the
    serialized state metadata carried with the write (None = no
    metadata / metadata deleted)."""

    value: Optional[bytes]
    version: Version
    metadata: Optional[bytes] = None


class UpdateBatch:
    """Pending writes of a block (reference statedb.UpdateBatch): puts AND
    deletes both carry the committing version; deletes shadow reads."""

    def __init__(self):
        self._updates: Dict[Tuple[str, str], BatchEntry] = {}

    def put(
        self,
        ns: str,
        key: str,
        value: bytes,
        version: Version,
        metadata: Optional[bytes] = None,
    ) -> None:
        self._updates[(ns, key)] = BatchEntry(value, version, metadata)

    def delete(self, ns: str, key: str, version: Version) -> None:
        self._updates[(ns, key)] = BatchEntry(None, version)

    def exists(self, ns: str, key: str) -> bool:
        return (ns, key) in self._updates

    def get(self, ns: str, key: str) -> Optional[BatchEntry]:
        return self._updates.get((ns, key))

    def items(self):
        return self._updates.items()

    def __len__(self):
        return len(self._updates)


class HashedUpdateBatch:
    """Private-data hashed writes: keyed (ns, collection, key_hash)."""

    def __init__(self):
        self._updates: Dict[Tuple[str, str, bytes], BatchEntry] = {}

    def put(
        self,
        ns: str,
        coll: str,
        key_hash: bytes,
        value_hash: Optional[bytes],
        version: Version,
        metadata: Optional[bytes] = None,
    ) -> None:
        self._updates[(ns, coll, key_hash)] = BatchEntry(
            value_hash, version, metadata
        )

    def contains(self, ns: str, coll: str, key_hash: bytes) -> bool:
        return (ns, coll, key_hash) in self._updates

    def get(self, ns: str, coll: str, key_hash: bytes) -> Optional[BatchEntry]:
        return self._updates.get((ns, coll, key_hash))

    def items(self):
        return self._updates.items()

    def __len__(self):
        return len(self._updates)


class PvtUpdateBatch:
    """Cleartext private-data writes keyed (ns, collection, key)
    (reference privacyenabledstate UpdateBatch.PvtUpdates)."""

    def __init__(self):
        self._updates: Dict[Tuple[str, str, str], BatchEntry] = {}

    def put(
        self,
        ns: str,
        coll: str,
        key: str,
        value: Optional[bytes],
        version: Version,
    ) -> None:
        self._updates[(ns, coll, key)] = BatchEntry(value, version)

    def get(self, ns: str, coll: str, key: str) -> Optional[BatchEntry]:
        return self._updates.get((ns, coll, key))

    def items(self):
        return self._updates.items()

    def __len__(self):
        return len(self._updates)


#: one committed row without its value: (version, metadata); None = the
#: key is absent from committed state
CommittedRow = Optional[Tuple[Version, Optional[bytes]]]


class BlockPreload:
    """The committed rows one block asks for, read in bulk and kept for that
    block alone (reference validator.go preLoadCommittedVersionOfRSet ->
    statedb.BulkOptimizable.LoadCommittedVersions): the policy stage's SBE
    gate and the MVCC validator answer from the same map, so a key's row is
    read once a block and not once per asker.  It holds what was committed
    BEFORE the block, which cannot change while the one committer is inside
    it; a key nobody loaded falls back to the db's point read, as
    upstream's GetVersion does, so no answer can differ.  Values are not
    held: `value()` reads the row unless the map already knows the key is
    absent."""

    __slots__ = (
        "db", "_pub", "_hashed", "keys", "rows", "point_reads", "_accounted",
    )

    def __init__(self, db):
        self.db = db
        self._pub: Dict[Tuple[str, str], CommittedRow] = {}
        self._hashed: Dict[Tuple[str, str, bytes], CommittedRow] = {}
        self.keys = 0  # keys read in bulk
        self.rows = 0  # ... of which committed state held a row
        self.point_reads = 0  # lookups the map could not answer
        self._accounted = (0, 0, 0)

    def load(
        self,
        keys: Iterable[Tuple[str, str]],
        hashed_keys: Iterable[Tuple[str, str, bytes]] = (),
    ) -> None:
        """Read the rows of `keys` ((ns, key)) and `hashed_keys` ((ns, coll,
        key_hash)) that the map does not hold yet, in one bulk read."""
        pub, hashed = self._pub, self._hashed
        want = [k for k in keys if k not in pub]
        want_hashed = [k for k in hashed_keys if k not in hashed]
        if not want and not want_hashed:
            return
        got, got_hashed = self.db.load_committed(want, want_hashed)
        pub.update(got)
        hashed.update(got_hashed)
        self.keys += len(got) + len(got_hashed)
        self.rows += sum(
            row is not None for row in (*got.values(), *got_hashed.values())
        )

    def counts(self) -> Tuple[int, int, int]:
        return self.keys, self.rows, self.point_reads

    def account(self) -> Dict[str, int]:
        """What the block's stage that ends here read, that is since the
        last call: counted into fabric_state_reads_total, once a stage and
        not once a key, and returned as that stage's span attributes."""
        now = self.counts()
        keys, rows, point_reads = (
            n - was for n, was in zip(now, self._accounted)
        )
        self._accounted = now
        fabobs.obs_count("fabric_state_reads_total", keys, how="preloaded")
        fabobs.obs_count("fabric_state_reads_total", point_reads, how="point")
        return {"keys": keys, "rows": rows, "point_reads": point_reads}

    # -- lookups: the map first, the db's point read on a miss --------------
    def _row(self, ns: str, key: str) -> CommittedRow:
        try:
            return self._pub[(ns, key)]
        except KeyError:
            self.point_reads += 1
            vv = self.db.get_state(ns, key)
            return (vv.version, vv.metadata) if vv else None

    def _hashed_row(self, ns: str, coll: str, key_hash: bytes) -> CommittedRow:
        try:
            return self._hashed[(ns, coll, key_hash)]
        except KeyError:
            self.point_reads += 1
            vv = self.db.get_hashed_state(ns, coll, key_hash)
            return (vv.version, vv.metadata) if vv else None

    def version(self, ns: str, key: str) -> Optional[Version]:
        row = self._row(ns, key)
        return row[0] if row else None

    def metadata(self, ns: str, key: str) -> Optional[bytes]:
        row = self._row(ns, key)
        return row[1] if row else None

    def hashed_version(
        self, ns: str, coll: str, key_hash: bytes
    ) -> Optional[Version]:
        row = self._hashed_row(ns, coll, key_hash)
        return row[0] if row else None

    def hashed_metadata(
        self, ns: str, coll: str, key_hash: bytes
    ) -> Optional[bytes]:
        row = self._hashed_row(ns, coll, key_hash)
        return row[1] if row else None

    def value(self, ns: str, key: str) -> Optional[bytes]:
        if self._pub.get((ns, key), True) is None:
            return None  # known absent: nothing to read
        self.point_reads += 1
        vv = self.db.get_state(ns, key)
        return vv.value if vv else None

    def hashed_value(
        self, ns: str, coll: str, key_hash: bytes
    ) -> Optional[bytes]:
        if self._hashed.get((ns, coll, key_hash), True) is None:
            return None
        self.point_reads += 1
        vv = self.db.get_hashed_state(ns, coll, key_hash)
        return vv.value if vv else None


class VersionedDB:
    """Committed state: (ns, key) -> VersionedValue, ordered per namespace."""

    def __init__(self):
        self._data: Dict[str, Dict[str, VersionedValue]] = {}
        self._sorted_keys: Dict[str, List[str]] = {}
        self._hashed: Dict[Tuple[str, str, bytes], VersionedValue] = {}
        self._pvt: Dict[Tuple[str, str, str], VersionedValue] = {}
        # coherence stamp for device-resident derived caches (see
        # SqliteVersionedDB.state_generation): out-of-band mutators
        # (rollback / rebuild / anything bypassing the validator flow)
        # must bump_generation() so resident version tables fail closed
        self.state_generation = 0

    def bump_generation(self) -> None:
        self.state_generation += 1

    # -- reads ------------------------------------------------------------
    def get_state(self, ns: str, key: str) -> Optional[VersionedValue]:
        return self._data.get(ns, {}).get(key)

    def get_state_metadata(self, ns: str, key: str) -> Optional[bytes]:
        """Serialized VALIDATION_PARAMETER et al. for a key (reference
        statedb GetStateMetadata)."""
        vv = self.get_state(ns, key)
        return vv.metadata if vv else None

    def get_version(self, ns: str, key: str) -> Optional[Version]:
        vv = self.get_state(ns, key)
        return vv.version if vv else None

    def get_hashed_state(
        self, ns: str, coll: str, key_hash: bytes
    ) -> Optional[VersionedValue]:
        return self._hashed.get((ns, coll, key_hash))

    def get_hashed_metadata(
        self, ns: str, coll: str, key_hash: bytes
    ) -> Optional[bytes]:
        vv = self._hashed.get((ns, coll, key_hash))
        return vv.metadata if vv else None

    def get_key_hash_version(self, ns: str, coll: str, key_hash: bytes) -> Optional[Version]:
        entry = self._hashed.get((ns, coll, key_hash))
        return entry.version if entry else None

    def load_committed(
        self,
        keys: Iterable[Tuple[str, str]],
        hashed_keys: Iterable[Tuple[str, str, bytes]] = (),
    ) -> Tuple[
        Dict[Tuple[str, str], CommittedRow],
        Dict[Tuple[str, str, bytes], CommittedRow],
    ]:
        """Bulk read for BlockPreload: (version, metadata) of every key
        asked, None where committed state holds no row (reference
        statedb.BulkOptimizable.LoadCommittedVersions)."""
        data = self._data
        pub: Dict[Tuple[str, str], CommittedRow] = {}
        for ns, key in keys:
            vv = data.get(ns, {}).get(key)
            pub[(ns, key)] = (vv.version, vv.metadata) if vv else None
        hashed: Dict[Tuple[str, str, bytes], CommittedRow] = {}
        for k in hashed_keys:
            vv = self._hashed.get(k)
            hashed[k] = (vv.version, vv.metadata) if vv else None
        return pub, hashed

    def get_private_data(
        self, ns: str, coll: str, key: str
    ) -> Optional[VersionedValue]:
        """Cleartext private read (privacyenabledstate GetPrivateData);
        returns None when this peer never received the collection data."""
        return self._pvt.get((ns, coll, key))

    def get_state_range(
        self, ns: str, start_key: str, end_key: str, include_end: bool
    ) -> Iterator[Tuple[str, VersionedValue]]:
        """Sorted iteration over [start_key, end_key) or [..., end_key].
        Empty end_key means an open-ended scan (reference semantics)."""
        keys = self._sorted_keys.get(ns, [])
        i = bisect.bisect_left(keys, start_key)
        table = self._data.get(ns, {})
        while i < len(keys):
            k = keys[i]
            if end_key:
                if include_end:
                    if k > end_key:
                        break
                elif k >= end_key:
                    break
            yield k, table[k]
            i += 1

    # -- writes -----------------------------------------------------------
    def apply_updates(
        self,
        batch: UpdateBatch,
        hashed: Optional[HashedUpdateBatch] = None,
        pvt: Optional[PvtUpdateBatch] = None,
    ) -> None:
        for (ns, key), entry in batch.items():
            table = self._data.setdefault(ns, {})
            keys = self._sorted_keys.setdefault(ns, [])
            if entry.value is None:
                if key in table:
                    del table[key]
                    idx = bisect.bisect_left(keys, key)
                    if idx < len(keys) and keys[idx] == key:
                        keys.pop(idx)
            else:
                if key not in table:
                    bisect.insort(keys, key)
                table[key] = VersionedValue(
                    entry.value, entry.version, entry.metadata
                )
        if hashed is not None:
            for (ns, coll, key_hash), entry in hashed.items():
                if entry.value is None:
                    self._hashed.pop((ns, coll, key_hash), None)
                else:
                    self._hashed[(ns, coll, key_hash)] = VersionedValue(
                        entry.value, entry.version, entry.metadata
                    )
        if pvt is not None:
            for (ns, coll, key), entry in pvt.items():
                if entry.value is None:
                    self._pvt.pop((ns, coll, key), None)
                else:
                    self._pvt[(ns, coll, key)] = VersionedValue(
                        entry.value, entry.version
                    )

    def num_keys(self) -> int:
        return sum(len(t) for t in self._data.values())

    # -- full iteration (snapshot export) ----------------------------------
    def iter_all_state(self) -> Iterator[Tuple[str, str, VersionedValue]]:
        """Deterministic (ns, key, value) iteration over all public state."""
        for ns in sorted(self._data):
            table = self._data[ns]
            for key in self._sorted_keys[ns]:
                yield ns, key, table[key]

    def iter_all_hashed(
        self,
    ) -> Iterator[Tuple[str, str, bytes, VersionedValue]]:
        for ns, coll, kh in sorted(self._hashed):
            yield ns, coll, kh, self._hashed[(ns, coll, kh)]

    # -- rich queries (statecouchdb.go:695 analog) -------------------------
    def execute_query(self, ns: str, query):
        """Selector query over a namespace's JSON values (see
        fabric_tpu.ledger.queries). Not phantom-protected, like the
        reference's CouchDB queries."""
        from fabric_tpu.ledger import queries as rich_queries

        table = self._data.get(ns, {})
        rows = (
            (key, table[key].value) for key in self._sorted_keys.get(ns, [])
        )
        return rich_queries.execute(rows, query)

    def execute_query_paginated(
        self, ns: str, query, page_size: int, bookmark: str = ""
    ):
        """One page + next bookmark (statecouchdb.go:653
        ExecuteQueryWithPagination)."""
        from fabric_tpu.ledger import queries as rich_queries

        table = self._data.get(ns, {})
        rows = (
            (key, table[key].value) for key in self._sorted_keys.get(ns, [])
        )
        return rich_queries.execute_paginated(rows, query, page_size, bookmark)
