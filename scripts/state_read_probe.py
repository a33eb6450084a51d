#!/usr/bin/env python3
"""What a read of committed state costs on this machine's file systems: the
1,482 point SELECTs a 500-tx block of `peer-catchup` made before PR 35
(three per transaction, each an autocommit read transaction on a WAL
database) against the one `IN` query `SqliteVersionedDB.load_committed`
makes, on a state db opened as `Channel` opens it; alone, and beside a
second thread that runs Python all the while (the prepare stage's part:
`sqlite3` releases the GIL at every statement).  No JAX, no chip: run it
through `chiprun` to read the chip machine's host, whose checkout is a 9p
mount under gVisor.

    chiprun -- python3 scripts/state_read_probe.py

Prints one JSON line per directory tried.  A cost of the host's, not a
measurement of the benchmark."""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from fabric_tpu.ledger.persistent import SqliteVersionedDB  # noqa: E402
from fabric_tpu.ledger.rwset import Version  # noqa: E402
from fabric_tpu.ledger.statedb import UpdateBatch  # noqa: E402

KEYS = 500
ROUNDS = 20
# beside the busy thread a round of point reads takes seconds on the chip
# machine (a GIL hand-off per statement): two rounds say enough
ROUNDS_BESIDE = 2


def _median_ms(fn, rounds: int = ROUNDS) -> float:
    took = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        fn()
        took.append((time.perf_counter() - t0) * 1e3)
    return sorted(took)[len(took) // 2]


def probe(directory: str) -> dict:
    os.makedirs(directory, exist_ok=True)
    work = tempfile.mkdtemp(dir=directory, prefix="state_read_probe.")
    try:
        db = SqliteVersionedDB(os.path.join(work, "probe.state.db"))
        # a ledger some blocks tall, as in the window; the block's own keys
        # are absent from it, as in the cell
        for block in range(20):
            batch = UpdateBatch()
            for i in range(KEYS):
                batch.put("bench", f"k{block}-{i}", b"value-000001", Version(block, i))
            db.commit_block(batch, savepoint=block)
        keys = [("bench", f"k99-{i}") for i in range(KEYS)]

        def point():
            for ns, key in keys:
                db.get_state_metadata(ns, key)
                db.get_version(ns, key)
                db.get_state_metadata(ns, key)

        def bulk():
            db.load_committed(keys)

        out = {
            "directory": directory,
            "point_1500_ms": _median_ms(point),
            "bulk_500_ms": _median_ms(bulk),
        }
        stop = threading.Event()

        def spin():
            x = 0
            while not stop.is_set():
                x += sum(range(100))

        other = threading.Thread(target=spin, daemon=True)
        other.start()
        try:
            out["point_1500_ms_beside_a_busy_thread"] = _median_ms(
                point, ROUNDS_BESIDE
            )
            out["bulk_500_ms_beside_a_busy_thread"] = _median_ms(bulk)
        finally:
            stop.set()
            other.join()
        out["us_per_point_select"] = out["point_1500_ms"] / (3 * KEYS) * 1e3
        db.close()
        return out
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main() -> int:
    repo = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    for directory in (
        os.path.join(os.path.abspath(repo), ".bench_work"),
        tempfile.gettempdir(),
    ):
        print(json.dumps(probe(directory)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
