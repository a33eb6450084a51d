#!/usr/bin/env python3
"""The verify program timed alone: kernel ms against lane width.

    chiprun -- python3 scripts/kernel_width_points.py --lanes 2048,4096
    chiprun -- python3 scripts/kernel_width_points.py --tree .repo_copy/parent \
        --lanes 2048 --trace-lanes 2048

One process, one tree (`--tree`, default this checkout; a chip belongs to one
process, so two trees are two commands in one call).  For each width it
compiles `verify_batch_bytes_jit` (the program both benchmark cells launch),
checks the mask of real signatures against the host oracle, and times
`--launches` launches after the first, each ended by `block_until_ready`.
It also prints what the compiled program will execute: the `while` loops
in it and the device ops of one launch, counted from the executable's text
(entry ops + trip count x the ops of each loop body, nested loops
multiplied through).  `--trace-lanes` profiles two more launches at that
width and counts the op events the device really recorded, with the ops
that took most of the time.  One JSON line per width, on standard output.

Without a TPU this exits 2: a wall time from the CPU backend is not a
kernel time (`--allow-cpu` is for rehearsing the script at a tiny width).
"""

from __future__ import annotations

import argparse
import glob
import hashlib
import json
import os
import re
import statistics
import sys
import tempfile
import time
from typing import Dict, List, Tuple

KEY_BUCKET = 32  # TPUProvider.KEY_BUCKET

# HLO instructions that move no data and start no device op
_FREE_OPS = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id",
))
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*.*?\s([a-z][a-z\-]*)\(")
_TRIP = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLED = re.compile(r"\b(body|condition)=%?([\w.\-]+)")
_INT_CONSTANT = re.compile(r"\bs32\[\]\S*\s+constant\((\d+)\)")


def executed_ops(hlo_text: str) -> Dict[str, int]:
    """{"ops": device ops one launch executes, "whiles": static `while`
    instructions, "while_steps": loop steps executed} from the text of a
    compiled executable.  A fusion, a copy or a custom call counts one; a
    `while` counts trip count x its body (the condition is a scalar
    compare).  The trip count is the executable's `known_trip_count`, or,
    where the TPU compiler has dropped it, the one integer constant the
    condition compares the counter with (`fori_loop` and `scan` count up
    from 0).  A loop that shows neither raises: a guess would be a wrong
    count."""
    comps: Dict[str, List[Tuple[str, str]]] = {}
    entry = None
    name = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
            if line.startswith("ENTRY"):
                entry = name
            continue
        if name is None:
            continue
        if line.startswith("}"):
            name = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            comps[name].append((m.group(1), line))
    if entry is None:
        raise ValueError("no ENTRY computation in the executable's text")

    totals = {"whiles": 0, "while_steps": 0}

    def count(comp: str, times: int) -> int:
        n = 0
        for op, line in comps[comp]:
            if op in _FREE_OPS:
                continue
            if op == "while":
                called = dict(_CALLED.findall(line))
                trip = _TRIP.search(line)
                if trip:
                    trips = int(trip.group(1))
                else:
                    cond = comps[called["condition"]]
                    bounds = [int(c) for _, l in cond for c in _INT_CONSTANT.findall(l)]
                    if len(bounds) != 1 or not any("direction=LT" in l for _, l in cond):
                        raise ValueError(f"while without a trip count: {line[:120]}")
                    trips = bounds[0]
                totals["whiles"] += 1
                totals["while_steps"] += times * trips
                n += trips * count(called["body"], times * trips)
            else:
                n += 1
        return n

    ops = count(entry, 1)
    return {"ops": ops, **totals}


def _inputs(lanes: int):
    """Real signatures tiled to `lanes` lanes: 8 distinct lanes over 4 keys,
    two of them tampered, one switched off by the host precheck."""
    import numpy as np

    from fabric_tpu.common import p256
    from fabric_tpu.ops import bignum as bn

    keys = [p256.KeyPair(d, p256.scalar_mult(d, p256.GENERATOR))
            for d in (0x1F3A5, 0x2B7C11, 0x3D9E77, 0x4A1B2C3D)]
    rows = []
    for i in range(8):
        kp = keys[i % len(keys)]
        digest = hashlib.sha256(b"kernel_width_points %d" % i).digest()
        r, s = p256.sign_digest(kp.priv, digest, k=0x1234567 + 97 * i)
        ok = True
        if i == 3:
            digest = hashlib.sha256(b"another message").digest()
        if i == 5:
            r = (r + 1) % p256.N
        if i == 6:
            ok = False
        want = ok and p256.verify_digest(kp.pub, digest, r, s)
        rows.append((digest, r, s, i % len(keys), ok, want))
    reps = -(-lanes // len(rows))
    tiled = (rows * reps)[:lanes]

    def be(vals):
        return np.frombuffer(
            b"".join(v.to_bytes(32, "big") for v in vals), dtype=np.uint8
        ).reshape(len(vals), 32)

    e_b = np.frombuffer(b"".join(t[0] for t in tiled), dtype=np.uint8).reshape(lanes, 32)
    kx = np.zeros((bn.NLIMBS, KEY_BUCKET), dtype=np.uint32)
    ky = np.zeros((bn.NLIMBS, KEY_BUCKET), dtype=np.uint32)
    for j, kp in enumerate(keys):
        kx[:, j] = bn.int_to_limbs(kp.pub[0])
        ky[:, j] = bn.int_to_limbs(kp.pub[1])
    args = (
        e_b, be([t[1] for t in tiled]), be([t[2] for t in tiled]), kx, ky,
        np.asarray([t[3] for t in tiled], dtype=np.int32),
        np.asarray([t[4] for t in tiled], dtype=bool),
    )
    return args, np.asarray([t[5] for t in tiled], dtype=bool)


def _traced_ops(compiled, args, launches: int = 2) -> Dict:
    """Profile `launches` launches; the device's own op events per launch and
    the ops that took most of the time."""
    import jax

    from benchmarks import trace_reduce as tr

    with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as d:
        with jax.profiler.trace(d):
            for _ in range(launches):
                compiled(*args).block_until_ready()
        paths = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
        if not paths:
            return {"error": "the profiler wrote no trace"}
        trace = tr.load_xplane(paths[0])
    planes = tr.device_planes(trace)
    if not planes:
        return {"error": "no device plane in the trace"}
    events = trace[planes[0]].get(tr.OPS_LINE, [])
    by_name: Dict[str, float] = {}
    for ev_name, _, dur in events:
        key = ev_name.split(" = ")[0]
        by_name[key] = by_name.get(key, 0.0) + dur
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:8]
    # by kind (the op's name less its number): a `while` event covers the
    # events of its body, so the kinds inside the loops say what a step is
    # made of; [events per launch, ms per launch]
    by_kind: Dict[str, List[float]] = {}
    for ev_name, _, dur in events:
        kind = re.sub(r"[.\d]+$", "", ev_name.split(" = ")[0].lstrip("%"))
        slot = by_kind.setdefault(kind, [0, 0.0])
        slot[0] += 1
        slot[1] += dur
    kinds = sorted(by_kind.items(), key=lambda kv: -kv[1][1])[:16]
    modules = trace[planes[0]].get(tr.MODULES_LINE, [])
    return {
        "launches_traced": launches,
        "op_events": len(events),
        "op_events_per_launch": len(events) / launches,
        "module_ms": [round(dur / 1e6, 4) for _, _, dur in modules],
        "top_ops_ms_per_launch": {k: round(v / 1e6 / launches, 4) for k, v in top},
        "kinds_events_and_ms_per_launch": {
            k: [n / launches, round(ms / 1e6 / launches, 4)] for k, (n, ms) in kinds
        },
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--lanes", default="2048,4096")
    ap.add_argument("--launches", type=int, default=5)
    ap.add_argument("--trace-lanes", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()

    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    import jax
    import numpy as np

    from fabric_tpu.ops import p256_kernel as pk

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"no TPU here ({dev.platform}): a CPU wall time is not a kernel time",
              file=sys.stderr)
        return 2
    rc = 0
    for lanes in (int(x) for x in args.lanes.split(",")):
        host_args, want = _inputs(lanes)
        t0 = time.perf_counter()
        lowered = pk.verify_batch_bytes_jit.lower(*host_args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        dev_args = [jax.device_put(a) for a in host_args]
        got = compiled(*dev_args).block_until_ready()
        t3 = time.perf_counter()
        wrong = int((np.asarray(got) != want).sum())
        walls = []
        for _ in range(args.launches):
            ta = time.perf_counter()
            compiled(*dev_args).block_until_ready()
            walls.append((time.perf_counter() - ta) * 1e3)
        row = {
            "tree": tree, "lanes": lanes,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": jax.device_count()},
            "trace_lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
            "first_launch_ms": round((t3 - t2) * 1e3, 3),
            "launch_wall_ms": [round(w, 4) for w in walls],
            "launch_wall_ms_median": round(statistics.median(walls), 4),
            "mask_wrong_lanes": wrong, "true_lanes": int(want.sum()),
        }
        try:
            row["compiled"] = executed_ops(compiled.as_text())
        except (ValueError, KeyError) as e:
            row["compiled"] = {"error": str(e)}
        if lanes == args.trace_lanes:
            row["traced"] = _traced_ops(compiled, dev_args)
        print(json.dumps(row, sort_keys=True), flush=True)
        if wrong:
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
