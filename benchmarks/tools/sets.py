#!/usr/bin/env python3
"""Sets of runs of one cell, one after another, each a process of its own:

    python3 benchmarks/tools/sets.py --workload peer-catchup --tag base \\
        --seeds 2147486001,2147486003,... --sets 2 --seconds 30

Every run's result line, its `setup`, `window`, `series` and `output_check`
lines and what it was started with go as one JSON line into
``chiprun_out/sets/<tag>.jsonl``, the window's whole series into
``chiprun_out/sets/<tag>/``.  At the end the report of
``benchmarks/tools/report.py`` over that file is printed.  This process never
touches JAX: the chip belongs to the run it has started and waits for.

``--env KEY=VALUE`` (repeatable) is added to the runs' environment: how a
`benchmark` PR tries a remedy (README, "Drawing a bound").
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

KEPT_PHASES = ("setup", "window", "series", "output_check")


def one(workload: str, seed: int, seconds: float, trace: int, env: dict,
        series_path: str, extra: list) -> dict:
    cmd = [
        sys.executable, os.path.join(BENCH_DIR, "run.py"),
        "--workload", workload, "--seed", str(seed),
        "--seconds", str(seconds), "--trace", str(trace),
        "--series", series_path, *extra,
    ]
    t0 = time.time()
    proc = subprocess.run(
        cmd, cwd=REPO_ROOT, env=dict(os.environ, **env),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    row = {"seed": seed, "rc": proc.returncode, "trace": trace,
           "wall_s": round(time.time() - t0, 1), "phases": {}}
    for ln in proc.stdout.splitlines():
        if not ln.startswith("{"):
            continue
        try:
            obj = json.loads(ln)
        except ValueError:
            continue
        if obj.get("phase") in KEPT_PHASES:
            row["phases"][obj["phase"]] = obj
        elif "correct" in obj and "metrics" in obj:
            row["result"] = obj
    if "result" not in row:
        row["stderr_tail"] = proc.stderr[-3000:]
        row["stdout_tail"] = proc.stdout[-2000:]
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--env", action="append", default=[])
    args, extra = ap.parse_known_args()
    env = dict(kv.split("=", 1) for kv in args.env)
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = os.path.join(REPO_ROOT, "chiprun_out", "sets")
    os.makedirs(os.path.join(out_dir, args.tag), exist_ok=True)
    out_path = os.path.join(out_dir, args.tag + ".jsonl")
    plan = [(s, seed) for s in range(args.sets) for seed in seeds]
    with open(out_path, "a", encoding="utf-8") as fh:
        for set_no, seed in plan:
            series = os.path.join(out_dir, args.tag, f"set{set_no}_{seed}.json")
            row = one(args.workload, seed, args.seconds, args.trace, env,
                      series, extra)
            row.update(set=set_no, tag=args.tag, workload=args.workload,
                       seconds=args.seconds, env=env)
            fh.write(json.dumps(row) + "\n")
            fh.flush()
            metrics = {k: v["value"] for k, v in
                       row.get("result", {}).get("metrics", {}).items()}
            print(f"[{time.strftime('%H:%M:%S')}] {args.tag} set {set_no} "
                  f"seed {seed} rc {row['rc']} wall {row['wall_s']} "
                  f"correct {row.get('result', {}).get('correct')} {metrics}",
                  flush=True)
    from benchmarks.tools import report

    report.print_report(out_path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
