#!/usr/bin/env python3
"""A small extract of a profiler trace, for benchmarks/tests/data: every
module event of every device plane, the first op events of each plane, and the
host annotations named, in trace_reduce's plain structure, beside a few
numbers read off the module events by plain arithmetic (`read_by_hand`, for
the tests to hold the reducers to).

    python3 benchmarks/tools/trace_extract.py PROFILE.xplane.pb OUT.json \\
        --annotations bench.trace_slice,bench.step,mc.dispatch --ops 200

Run it where the trace is (a run with --keep-work leaves it under
.bench_work/<workload>/profile/); a whole trace of four planes is ~270 MB.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Sequence

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.dirname(BENCH_DIR) not in sys.path:
    sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmarks import trace_reduce as tr  # noqa: E402


def extract(trace: tr.Trace, annotations: Sequence[str], ops: int) -> Dict:
    out: Dict[str, Dict[str, List]] = {}
    for plane in tr.device_planes(trace):
        lines = trace[plane]
        out[plane] = {
            tr.MODULES_LINE: [list(e) for e in lines.get(tr.MODULES_LINE, [])],
            # op names are whole HLO lines: keep what stands before " = "
            tr.OPS_LINE: [
                [tr.op_name(name), start, dur]
                for name, start, dur in sorted(
                    lines.get(tr.OPS_LINE, []), key=lambda e: e[1]
                )[:ops]
            ],
        }
    out["/host:CPU"] = {
        "annotations": [list(e) for e in tr.host_annotations(trace, annotations)]
    }
    return out


def read_by_hand(extracted: Dict) -> Dict:
    """Plain arithmetic over the module events: per plane the programs, the
    runs' starts and lengths in ms; nothing of the reducers is used."""
    planes = {}
    for plane, lines in extracted.items():
        if tr.MODULES_LINE not in lines:
            continue
        rows = sorted(lines[tr.MODULES_LINE], key=lambda e: e[1])
        planes[plane] = {
            "programs": sorted({name.split("(", 1)[0] for name, _, _ in rows}),
            "starts_ns": [start for _, start, _ in rows],
            "lengths_ms": [dur / 1e6 for _, _, dur in rows],
        }
    return {"planes": planes}


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("xplane")
    ap.add_argument("out")
    ap.add_argument("--annotations", default="bench.trace_slice")
    ap.add_argument("--ops", type=int, default=200)
    args = ap.parse_args(argv)
    extracted = extract(
        tr.load_xplane(args.xplane), args.annotations.split(","), args.ops
    )
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w", encoding="utf-8") as fh:
        json.dump({"trace": extracted, "read_by_hand": read_by_hand(extracted)}, fh)
    print(json.dumps(read_by_hand(extracted)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
