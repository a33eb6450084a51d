#!/usr/bin/env python3
"""Compile the main path's programs for a DESCRIBED TPU v5e, no chip.

Rehearsal 3 of /opt/skills/guides/on-chip-measurement §2: the TPU
compiler is installed in the sandbox and compiles against a topology
description, so what it refuses (or takes half an hour over) is found
without spending chip time.  Nothing runs — this says nothing about
results or run time.

    JAX_PLATFORMS=cpu python scripts/chip_compile_rehearsal.py            # main-path rows
    JAX_PLATFORMS=cpu python scripts/chip_compile_rehearsal.py --rows all # + report-only rows
    JAX_PLATFORMS=cpu python scripts/chip_compile_rehearsal.py \
        --rows pairing8 --cut 1800

The parent never imports JAX: it runs ONE child per row, one after
another (only one process at a time may load the TPU library — do not
run this beside a pytest run of tests/test_chip_compile.py), kills a
child at --cut seconds, and prints one JSON object per row plus a
markdown table.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

LANES = 4096
KEY_BUCKET = 32  # TPUProvider.KEY_BUCKET

# a row is one program
MAIN_ROWS = ("bytes", "limbs", "channels4")
REPORT_ROWS = ("mvcc5000", "pairing8")


# ---------------------------------------------------------------------------
# child: one row, in-process
# ---------------------------------------------------------------------------


def _memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {
        "code_mb": round(ma.generated_code_size_in_bytes / 1e6, 2),
        "temp_mb": round(ma.temp_size_in_bytes / 1e6, 2),
        "args_mb": round(ma.argument_size_in_bytes / 1e6, 2),
        "out_mb": round(ma.output_size_in_bytes / 1e6, 4),
    }


def _lower_compile(jitted, args) -> dict:
    """Lower and compile; times, memory analysis, devices it spans."""
    import jax

    t0 = time.perf_counter()
    lowered = jitted.lower(*args)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    out = {
        "lower_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1),
        "memory": _memory(compiled),
    }
    shardings = jax.tree_util.tree_leaves(compiled.input_shardings)
    out["devices"] = len({d for s in shardings for d in s.device_set})
    return out


def _verify_shapes(kind: str, sharding):
    import jax
    import jax.numpy as jnp

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    if kind == "bytes":
        return (
            sds((LANES, 32), jnp.uint8),
            sds((LANES, 32), jnp.uint8),
            sds((LANES, 32), jnp.uint8),
            sds((20, KEY_BUCKET), jnp.uint32),
            sds((20, KEY_BUCKET), jnp.uint32),
            sds((LANES,), jnp.int32),
            sds((LANES,), jnp.bool_),
        )
    return tuple(sds((20, LANES), jnp.uint32) for _ in range(5)) + (
        sds((LANES,), jnp.bool_),
    )


def run_row(program: str) -> dict:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    # a described-device compile is written to the persistent cache but
    # can never be read back without a chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    one_chip = SingleDeviceSharding(topo.devices[0])

    from fabric_tpu.ops import bignum as bn
    from fabric_tpu.ops import p256_kernel as pk

    info = {"row": program}

    if program == "bytes":
        info.update(
            _lower_compile(
                jax.jit(pk.verify_batch_bytes_device),
                _verify_shapes("bytes", one_chip),
            )
        )
    elif program == "limbs":
        info.update(
            _lower_compile(
                jax.jit(pk.verify_batch_device),
                _verify_shapes("limbs", one_chip),
            )
        )
    elif program == "channels4":
        # ShardedVerify.verify_channels on a (4, 1) mesh over the
        # described devices: 4 channels x 4096 lanes, one channel per chip
        from fabric_tpu.parallel.mesh import grid_mesh
        from fabric_tpu.parallel.sharded import ShardedVerify

        mesh = grid_mesh(4, 1, topo.devices)
        sv = ShardedVerify(mesh)
        limb = NamedSharding(mesh, P("channel", None, "data"))
        mask = NamedSharding(mesh, P("channel", "data"))
        args = tuple(
            jax.ShapeDtypeStruct((4, 20, LANES), jnp.uint32, sharding=limb)
            for _ in range(5)
        ) + (jax.ShapeDtypeStruct((4, LANES), jnp.bool_, sharding=mask),)
        info.update(_lower_compile(sv._build_channels(), args))
    elif program == "mvcc5000":
        # DeviceValidator._resolve at a 5,000-tx block: 2 reads + 2
        # writes per tx, shapes bucketed as validate_and_prepare_batch does
        from fabric_tpu.ledger import mvcc_device as md

        t = md._next_pow2(5000)
        rw = md._next_pow2(10000)
        i32 = lambda n: jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
        args = (
            i32(rw), i32(rw),
            jax.ShapeDtypeStruct((rw,), jnp.bool_, sharding=one_chip),
            i32(rw), i32(rw),
        )
        fn = md._resolve.__wrapped__
        info.update(
            _lower_compile(
                jax.jit(lambda *a: fn(*a, num_txs=t, num_keys=rw)), args
            )
        )
    elif program == "pairing8":
        from fabric_tpu.common import fp256bn as host
        from fabric_tpu.ops import pairing_kernel as pair

        sched_g = pair._g2_schedule()
        sched_w = pair.LineSchedule(host.G2_GEN)
        import numpy as np

        w_np = (
            sched_w.dbl_a, sched_w.dbl_b, sched_w.add_a, sched_w.add_b,
            np.stack([c[0] for c in sched_w.corr]),
            np.stack([c[1] for c in sched_w.corr]),
        )
        w_args = tuple(
            jax.ShapeDtypeStruct(np.asarray(a).shape, np.asarray(a).dtype,
                                 sharding=one_chip)
            for a in w_np
        )
        col = jax.ShapeDtypeStruct((bn.NLIMBS, 8), jnp.uint32, sharding=one_chip)
        ok = jax.ShapeDtypeStruct((8,), jnp.bool_, sharding=one_chip)

        def run(w_arrs, p1x, p1y, p2x, p2y, okm):
            return pair._unity_check(w_arrs, sched_g, p1x, p1y, p2x, p2y, okm)

        info.update(
            _lower_compile(jax.jit(run), (w_args, col, col, col, col, ok))
        )
    else:
        raise SystemExit(f"unknown row {program!r}")
    info["result"] = "compiles"
    return info


# ---------------------------------------------------------------------------
# parent: one child per row, sequentially, never touching JAX
# ---------------------------------------------------------------------------


def _run_child(row: str, cut: float) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    t0 = time.perf_counter()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child", row],
            env=env, capture_output=True, text=True, timeout=cut,
        )
    except subprocess.TimeoutExpired:
        return {"row": row, "result": f"cut at {cut / 60:.1f} min"}
    wall = round(time.perf_counter() - t0, 1)
    for line in reversed(proc.stdout.splitlines()):
        if line.startswith("{"):
            out = json.loads(line)
            out["wall_s"] = wall
            return out
    tail = (proc.stderr or proc.stdout).strip().splitlines()[-1:]
    return {
        "row": row,
        "result": "refused: " + (tail[0] if tail else f"rc {proc.returncode}"),
        "wall_s": wall,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--rows", default="main",
        help="main | report | all | comma-separated row names "
        "(bytes, limbs, channels4, mvcc5000, pairing8)",
    )
    ap.add_argument("--cut", type=float, default=900.0,
                    help="seconds before a row's child is killed")
    ap.add_argument("--child", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        print(json.dumps(run_row(args.child)), flush=True)
        return 0

    groups = {
        "main": MAIN_ROWS,
        "report": REPORT_ROWS,
        "all": MAIN_ROWS + REPORT_ROWS,
    }
    rows = groups.get(args.rows) or tuple(args.rows.split(","))
    results = []
    for row in rows:
        res = _run_child(row, args.cut)
        print(json.dumps(res), flush=True)
        results.append(res)

    print("\n| row | lower | TPU compile | memory | result |")
    print("| --- | --- | --- | --- | --- |")
    for r in results:
        mem = r.get("memory")
        mem_s = (
            f"code {mem['code_mb']} MB, temp {mem['temp_mb']} MB, "
            f"args {mem['args_mb']} MB" if mem else "-"
        )
        print(
            f"| {r['row']} "
            f"| {r.get('lower_s', '-')} s | {r.get('compile_s', '-')} s "
            f"| {mem_s} | {r['result']} |"
        )
    return 0 if all(r["result"] == "compiles" for r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
