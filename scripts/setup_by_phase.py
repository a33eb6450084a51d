#!/usr/bin/env python3
"""Set-up by phase, the way the driver's check sees it: every tree starts
from an EMPTY compile cache of its own, runs one cell once cold and then warm,
and the `setup` line's marks are laid side by side.

    chiprun --timeout 3000 -- python3 scripts/setup_by_phase.py \
        --workload peer-catchup --tree parent=.trees/parent --tree change=. \
        --seeds 2147483777,2147483801,2147483827,2147483851

Each seed is one run of every tree (the first one cold); the warm runs go
parent, change, change, parent, ... so that a drift of the machine falls on
both.  This process never touches JAX: each run is a child that owns the chip
in turn.  Every run's output is kept under `--out`, and the last lines are the
summary: per tree the marks of each run, the warm medians of every
mark-to-mark phase and of every end-to-end metric.

PR 27 was refused for `setup_s` after a comparison in which both trees shared
one warm cache, so the change never loaded a program compiled from its own
kernel.  This is the comparison that would have shown it.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time
from typing import Dict, List, Optional


def lines_of(path: str) -> List[Dict]:
    out = []
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if line.startswith("{"):
                try:
                    out.append(json.loads(line))
                except ValueError:
                    pass
    return out


def one_run(tree: str, label: str, workload: str, seed: int, seconds: float,
            cache: str, out_dir: str, tag: str) -> Dict:
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=cache)
    env.pop("BENCH_RUN", None)
    stem = os.path.join(out_dir, f"{workload}.{label}.{tag}")
    cmd = [
        sys.executable, "benchmarks/run.py", "--workload", workload,
        "--seed", str(seed), "--seconds", str(seconds), "--trace", "0",
    ]
    t0 = time.perf_counter()
    with open(stem + ".out", "w") as out, open(stem + ".err", "w") as err:
        rc = subprocess.call(cmd, cwd=tree, env=env, stdout=out, stderr=err)
    wall = time.perf_counter() - t0
    lines = lines_of(stem + ".out")
    setup = next((x for x in lines if x.get("phase") == "setup"), {})
    result = lines[-1] if lines and "metrics" in lines[-1] else {}
    return {
        "tree": label, "tag": tag, "seed": seed, "rc": rc,
        "wall_s": round(wall, 1),
        "marks": setup.get("seconds_since_start", {}),
        "warmup": setup.get("warmup", {}),
        "compile_cache_dir": setup.get("compile_cache_dir"),
        "correct": result.get("correct"),
        "failed": result.get("failed"),
        "metrics": {
            k: v["value"] for k, v in result.get("metrics", {}).items()
        },
    }


def phases(marks: Dict[str, float], setup_s: Optional[float]) -> Dict[str, float]:
    """Mark-to-mark seconds, in the order the marks were taken."""
    ordered = sorted(marks.items(), key=lambda kv: kv[1])
    if setup_s is not None:
        ordered.append(("window_opens", setup_s))
    out, prev = {}, 0.0
    for name, at in ordered:
        out[name] = round(at - prev, 2)
        prev = at
    return out


def median_of(rows: List[Dict[str, float]]) -> Dict[str, float]:
    keys = [k for k in rows[0] if all(k in r for r in rows)] if rows else []
    return {k: round(statistics.median(r[k] for r in rows), 3) for k in keys}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--tree", action="append", required=True,
                    help="label=directory; the first is the side compared against")
    ap.add_argument("--seeds", required=True,
                    help="comma-separated; the first seed's run is the cold one")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", default="chiprun_out/setup_by_phase")
    args = ap.parse_args()

    trees = [(t.split("=", 1)[0], os.path.abspath(t.split("=", 1)[1]))
             for t in args.tree]
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    caches = {}
    for label, tree in trees:
        cache = os.path.join(tree, f".jax_cache_fresh.{args.workload}")
        shutil.rmtree(cache, ignore_errors=True)
        os.makedirs(cache)
        caches[label] = cache

    runs: List[Dict] = []
    for i, seed in enumerate(seeds):
        order = trees if i % 2 == 0 else trees[::-1]
        for label, tree in order:
            tag = "cold" if i == 0 else f"warm{i}"
            run = one_run(tree, label, args.workload, seed, args.seconds,
                          caches[label], out_dir, tag)
            run["phases"] = phases(run["marks"], run["metrics"].get("setup_s"))
            runs.append(run)
            print(json.dumps(run, sort_keys=True), flush=True)

    summary = {"workload": args.workload, "trees": {}}
    for label, _ in trees:
        mine = [r for r in runs if r["tree"] == label]
        warm = [r for r in mine if r["tag"] != "cold"]
        summary["trees"][label] = {
            "cold_phases": mine[0]["phases"] if mine else {},
            "warm_phase_medians": median_of([r["phases"] for r in warm]),
            "warm_setup_s": [r["metrics"].get("setup_s") for r in warm],
            "metric_medians_all_runs": median_of([r["metrics"] for r in mine]),
            "all_correct": all(r["correct"] is True for r in mine),
        }
    with open(os.path.join(out_dir, f"{args.workload}.summary.json"), "w") as fh:
        json.dump({"summary": summary, "runs": runs}, fh, indent=1, sort_keys=True)
    print(json.dumps(summary, sort_keys=True), flush=True)
    return 0 if all(r["rc"] == 0 for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
