#!/usr/bin/env python3
"""One run of the benchmark, and then what the commit path read of committed
state in that process: `fabric_state_reads_total{how}` and the `keys` /
`rows` / `point_reads` attributes of every `commit.validate` and
`ledger.mvcc` span still in fabobs' ring (warm-up blocks included).  The
benchmark prints neither; its own lines come first, unchanged.

    chiprun -- python3 scripts/state_reads_of_a_run.py \\
        --workload peer-catchup --seed 2147500101 --seconds 30 --trace 0

Every argument goes to `benchmarks/run.py`.  The last line is this
script's, on standard error: {"phase": "state_reads", ...}."""

from __future__ import annotations

import json
import os
import runpy
import sys

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SPANS = ("commit.validate", "ledger.mvcc")


def state_reads() -> dict:
    from fabric_tpu.common import fabobs

    reg = fabobs.active()
    if reg is None:
        return {"phase": "state_reads", "fabobs": "off"}
    family = reg.snapshot().get("fabric_state_reads_total", {})
    out = {"phase": "state_reads", "counter": family.get("series", {})}
    for name in SPANS:
        rows = [
            e["args"] for e in reg.trace_events()
            if e.get("ph") == "X" and e["name"] == name and "keys" in e["args"]
        ]
        out[name] = {
            "blocks": len(rows),
            "keys_min_max": [
                min((r["keys"] for r in rows), default=None),
                max((r["keys"] for r in rows), default=None),
            ],
            "rows": sum(r["rows"] for r in rows),
            "point_reads": sum(r["point_reads"] for r in rows),
        }
    return out


def main() -> int:
    os.chdir(REPO)
    sys.path.insert(0, os.path.abspath(REPO))
    sys.argv = [os.path.join("benchmarks", "run.py"), *sys.argv[1:]]
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
    except SystemExit as done:
        rc = done.code if isinstance(done.code, int) else 1
    else:
        rc = 0
    print(json.dumps(state_reads()), file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
