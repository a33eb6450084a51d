#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one driver or
one per-layer metric is a file of its own, found by the name BENCHMARK.json
gives (see benchmarks/README.md).  The last line of standard output is the
result: one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (with --trace 1 also ``breakdown``) and, last,
``checks``: each number the output check compared, beside its limit.

Without a TPU the run exits non-zero and prints no result.
``--rehearse-on-cpu`` drives the whole path on the sandbox's CPU at a tiny
size instead; what it prints is no measurement, so its line carries no metric
at all.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict, Optional, Sequence  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def load_cell(workload: str) -> Dict:
    """The cell's entry, configuration, traffic mix and metric entries."""
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"benchmark: no workload {workload!r}; BENCHMARK.json has "
            f"{sorted(cells)}"
        )
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(REPO_ROOT, configs[cell["config"]]["file"]),
              encoding="utf-8") as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json"),
              encoding="utf-8") as fh:
        traffic = json.load(fh)

    def mine(metric: Dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell, "config": config, "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def load_layer_metric(name: str):
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + name.replace(".", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_context(r, outcome: Dict) -> Dict:
    """What the per-layer readers read: the driver's counts and spans, and
    the profiler's trace where the run has one."""
    from benchmarks import harness as hs
    from benchmarks import trace_reduce as tr

    ctx = dict(outcome["layer"])
    ctx["device_kind"] = r.devices[0].device_kind
    ctx["trace"] = ctx["slice_ns"] = ctx["window_ns"] = None
    path = r.tracer.xplane_path()
    if path is not None and r.devices[0].platform == "tpu":
        t0 = time.perf_counter()
        trace = tr.load_xplane(path)
        hs.say(
            phase="trace", xplane_bytes=os.path.getsize(path),
            load_seconds=round(time.perf_counter() - t0, 1),
            events=sum(len(e) for ls in trace.values() for e in ls.values()),
        )
        ctx["trace"] = trace
        ctx["slice_ns"] = tr.traced_slice(trace, hs.SLICE_ANNOTATION)
        ctx["window_ns"] = tr.whole_cycles(trace, ctx["slice_ns"])
    return ctx


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="sandbox rehearsal at 64-tx blocks on the CPU backend; its "
        "result line carries no metric",
    )
    ap.add_argument(
        "--control", default=None,
        help="after the output check, put the plain reference with this "
        "guarantee broken (accept_high_s, skip_policy, skip_mvcc; several "
        "with commas) in the program's place and compare it the same way: "
        "an earlier line gives its numbers, and its `correct` has to be "
        "false.  The result line is not touched",
    )
    ap.add_argument(
        "--series", default=None, metavar="PATH",
        help="write the window's per-block (per-request) series to PATH as "
        "JSON: offered and done times, the harness's own work, the program's "
        "spans per unit (benchmarks/window_series.py reads it)",
    )
    ap.add_argument(
        "--compile-child", action="store_true",
        help="this process is the child that a checkout's first run of a "
        "cell starts to fill the compile cache (harness.compile_in_a_child): "
        "it starts no child of its own",
    )
    ap.add_argument(
        "--keep-work", action="store_true",
        help="leave .bench_work/<workload> (ledger, profile) for a look by hand",
    )
    return ap.parse_args(argv)


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = parse_args(argv)
    try:
        import fabric_tpu  # noqa: F401
    except ImportError as exc:
        print(f"benchmark: the program is not in this checkout: {exc}",
              file=sys.stderr, flush=True)
        return 3

    from benchmarks import harness as hs

    loaded = load_cell(args.workload)
    if not (args.rehearse_on_cpu or args.compile_child):
        hs.compile_in_a_child(
            args.workload, args.seed,
            {"config": loaded["config"], "traffic": loaded["traffic"]},
            os.path.abspath(__file__),
        )
    r = hs.Run(
        args.workload, loaded["config"], loaded["traffic"], args.seed,
        args.seconds, bool(args.trace), args.rehearse_on_cpu,
        T_PROCESS_START if argv is None else time.perf_counter(),
        chips=int(loaded["cell"]["chips"]), controls=controls_of(args),
        series=bool(args.series),
    )
    return one_run(r, args, loaded)


def controls_of(args: argparse.Namespace) -> Sequence[str]:
    from benchmarks import reference as ref

    controls = args.control.split(",") if args.control else []
    if any(rule not in ref.BREAK_RULES for rule in controls):
        raise SystemExit(f"benchmark: --control takes {ref.BREAK_RULES}")
    return controls


def report_series(series: Optional[Dict], args: argparse.Namespace) -> None:
    """Under --series PATH: what the window's spread is made of, on an earlier
    line, and the whole series to PATH."""
    from benchmarks import harness as hs
    from benchmarks import window_series as ws

    if not series or not series["done_at"]:
        return
    hs.say(phase="series", unit=series["unit"],
           harness_work=series["harness_work"], **ws.summarize(series))
    os.makedirs(os.path.dirname(os.path.abspath(args.series)), exist_ok=True)
    with open(args.series, "w", encoding="utf-8") as fh:
        json.dump(dict(series, workload=args.workload, seed=args.seed,
                       seconds=args.seconds), fh)


def one_run(r, args: argparse.Namespace, loaded: Dict) -> int:
    """Drive the cell's driver over `r` and print the result line.  (The
    tests hand in a Run with a provider planted; a run of the benchmark
    comes here through main() alone.)"""
    from benchmarks import harness as hs
    from benchmarks import trace_reduce as tr

    driver = importlib.import_module(
        "benchmarks.drivers." + loaded["traffic"]["driver"]
    )
    try:
        try:
            outcome = driver.run(r)
        except hs.SeamGaveWay as exc:
            print(f"benchmark: FAILED before a result: {exc}",
                  file=sys.stderr, flush=True)
            return 2
        checks = outcome["checks"]
        measured = outcome["end_to_end"]
        report_series(outcome.get("series"), args)
        metrics: Dict[str, Dict] = {}
        device = r.device_info()
        device["memory_peak_bytes"] = outcome["memory_peak_bytes"]
        result = {
            "correct": False, "attempted": outcome["attempted"],
            "failed": outcome["failed"], "metrics": metrics, "device": device,
        }
        if args.trace:
            ctx = layer_context(r, outcome)
            if ctx["trace"] is not None:
                lo, hi = ctx["window_ns"]
                device["busy_s"] = tr.busy_seconds(ctx["trace"], (lo, hi))
                device["window_s"] = (hi - lo) / 1e9
                result["breakdown"] = {
                    "device_ops": tr.top_device_ops(ctx["trace"], (lo, hi)),
                    "idle_gaps": tr.longest_idle_gaps(
                        ctx["trace"], ctx["annotations"], (lo, hi)
                    ),
                }
            wanted = loaded["per_layer"]
            values = {
                m["name"]: load_layer_metric(m["name"]).read(ctx)
                for m in wanted
            }
        else:
            wanted = loaded["end_to_end"]
            values = {m["name"]: measured.get(m["name"]) for m in wanted}
        for m in wanted:
            if values[m["name"]] is not None:
                metrics[m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"],
                }
        if not args.trace:
            missing = [m["name"] for m in wanted if m["name"] not in metrics]
            checks.add("end_to_end_metrics_missing", len(missing))
        if not r.on_chip:
            # a CPU run is no measurement: its numbers go to an earlier
            # line under another name, and the result carries none
            hs.say(phase="rehearsal_numbers_not_measurements", **{
                k: v["value"] for k, v in metrics.items()
            })
            metrics.clear()
            result["rehearsal"] = "cpu: not a measurement"
        result["correct"] = checks.correct
        result["checks"] = checks.rows  # last, by the contract
        for name, row in checks.rows.items():
            print(f"check {name}: {row['value']} (limit {row['limit']})",
                  file=sys.stderr, flush=True)
        print(json.dumps(result), flush=True)
        return 0 if checks.correct else 1
    finally:
        if not args.keep_work:
            r.cleanup()


if __name__ == "__main__":
    sys.exit(main())
