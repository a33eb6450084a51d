"""Reducers for the per-layer metric files of ``sidecar-fleet``: the
batcher's coalescing spans (``batcher.launch`` with ``requests`` and
``inflight``, ``batcher.linger``), the provider's ``tpu.dispatch`` (``lanes``,
``bucket``), and the device's launches of the bytes program at several
widths.  Each returns None where it finds nothing to read: a program without
the spans or their attributes leaves the metric out.

The context is layer_readers.py's (``spans``: the ring's spans of the
undisturbed part of the window; ``trace``; ``slice_ns``), plus what the
driver ``sidecar_fleet`` adds to a traced run's: ``fleet_launches`` (every
``batcher.launch`` span of the whole window, the traced slice's among them)
and ``slice_opened_us`` (the ring's clock when the profiler's slice opened:
the two clocks' offset)."""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from benchmarks import trace_reduce as tr

# the bytes program TPUProvider dispatches, at every width
PROGRAM = "jit_verify_batch_bytes_device"


def _args(ctx: Dict, name: str, attr: str) -> List[float]:
    return [
        float(e["args"][attr]) for e in ctx.get("spans") or []
        if e["name"] == name and attr in (e.get("args") or {})
    ]


def mean_attr(ctx: Dict, name: str, attr: str) -> Optional[float]:
    """The mean of one attribute over the spans of that name."""
    values = _args(ctx, name, attr)
    return sum(values) / len(values) if values else None


def bucket_fill_pct(ctx: Dict) -> Optional[float]:
    """Useful lanes over the lanes the launches were padded to."""
    lanes = _args(ctx, "tpu.dispatch", "lanes")
    buckets = _args(ctx, "tpu.dispatch", "bucket")
    if not buckets or len(lanes) != len(buckets):
        return None
    return 100.0 * sum(lanes) / sum(buckets)


def whole_runs(trace, window: Tuple[float, float]
               ) -> List[Tuple[float, float]]:
    """[(start, end)] of the bytes program's runs inside ``window``, less a
    run that the tracer cut: one that touches the first or the last instant
    the device plane recorded (the profiler stops after the slice closes,
    and a launch still running then ends there on the trace)."""
    planes = tr.device_planes(trace)
    if not planes:
        return []
    first, last = tr.device_extent(trace, planes[0])
    return [
        (s, e) for s, e in tr.program_events(trace, PROGRAM, window)
        if s > first + tr.EDGE_NS and e < last - tr.EDGE_NS
    ]


def kernel_ms_per_launch(ctx: Dict) -> Optional[float]:
    """Device ms of one whole launch of the bytes program in the traced
    slice, whatever its width."""
    if ctx.get("trace") is None or ctx.get("slice_ns") is None:
        return None
    runs = whole_runs(ctx["trace"], ctx["slice_ns"])
    if not runs:
        return None
    return sum(e - s for s, e in runs) / len(runs) / 1e6


def runs_with_lanes(ctx: Dict) -> List[Tuple[float, float, float]]:
    """[(start, end, useful lanes)] of the whole launches of the bytes
    program inside the traced slice.

    A launch's useful lanes are on the ring (its ``batcher.launch``), its
    device time on the trace.  The ring's clock is put on the trace's by the
    slice's opening, which both saw.  A launch dispatched with no other in
    flight (``inflight`` 0) follows the runs of every earlier one, so its
    run is the first to start after it; the launches that follow it, up to
    the next such, take the runs after that one in order, as the device runs
    a queue.  A run that no launch can be matched to is left out."""
    trace, window = ctx.get("trace"), ctx.get("slice_ns")
    opened, launches = ctx.get("slice_opened_us"), ctx.get("fleet_launches")
    if trace is None or window is None or opened is None or not launches:
        return []
    runs = tr.program_events(trace, PROGRAM, (float("-inf"), float("inf")))
    offset_ns = window[0] - opened * 1e3
    ordered = sorted(
        (e["ts"] * 1e3 + offset_ns, e["args"]) for e in launches
        if "inflight" in (e.get("args") or {})
    )
    lanes_of: Dict[int, float] = {}
    run = None
    for start, args in ordered:
        if args["inflight"] == 0:
            run = next((i for i, (s, _) in enumerate(runs) if s > start), None)
        elif run is not None:
            run += 1
        if run is None or run >= len(runs) or runs[run][0] <= start:
            run = None
            continue
        lanes_of[run] = float(args["lanes"])
    whole = set(whole_runs(trace, window))
    return [
        (s, e, lanes_of[i]) for i, (s, e) in enumerate(runs)
        if i in lanes_of and (s, e) in whole
    ]


def roofline_pct(ctx: Dict) -> Optional[float]:
    """The least time the chip could take for the useful lanes of the
    slice's whole launches (trace_reduce.ops_per_verify over the peak),
    over those launches' device time."""
    matched = runs_with_lanes(ctx)
    if not matched:
        return None
    lanes = sum(n for _, _, n in matched)
    seconds = sum(e - s for s, e, _ in matched) / 1e9
    return tr.roofline_share_pct(lanes, seconds, ctx["device_kind"])
