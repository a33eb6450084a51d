"""The reducers the per-layer metric files share.  Each takes the traced
run's context and returns a number, or None where it finds nothing to read
(the harness then leaves the metric out of the line; it never prints 0 for a
share).

The context: ``spans`` (fabobs spans of the window: name, ts and dur in
microseconds), ``trace`` (see trace_reduce.py; None off the chip),
``slice_ns`` (the traced slice on the trace's clock: whole launches are
counted in it), ``window_ns`` (the whole cycles inside it: busy and idle time
are read over them, see trace_reduce.whole_cycles), ``device_kind``,
``lanes_per_launch`` (useful lanes: what one block or request holds, not the
bucket it is padded to), ``client_wall_ms``.
"""

from __future__ import annotations

from typing import Dict, Optional

from benchmarks import trace_reduce as tr


def span_mean_ms(ctx: Dict, name: str) -> Optional[float]:
    durations = [e["dur"] for e in ctx.get("spans") or [] if e["name"] == name]
    if not durations:
        return None
    return sum(durations) / len(durations) / 1e3


def client_overhead_ms(ctx: Dict, served_span: str) -> Optional[float]:
    """Mean request wall on the client's clock less the mean of the server's
    span: encode, socket, decode, admission and the mask's way back."""
    served = span_mean_ms(ctx, served_span)
    walls = ctx.get("client_wall_ms") or []
    if served is None or not walls:
        return None
    return sum(walls) / len(walls) - served


def program_ms_per_launch(ctx: Dict, program: str) -> Optional[float]:
    if ctx.get("trace") is None:
        return None
    found = tr.program_seconds_per_launch(
        ctx["trace"], program, ctx.get("slice_ns")
    )
    return None if found is None else found[0] * 1e3


def program_roofline_pct(ctx: Dict, program: str) -> Optional[float]:
    """The least time the chip could take for the useful lanes of one launch
    (the padded lanes of the bucket do not count), over the program's device
    time per launch."""
    if ctx.get("trace") is None or not ctx.get("lanes_per_launch"):
        return None
    found = tr.program_seconds_per_launch(
        ctx["trace"], program, ctx.get("slice_ns")
    )
    if found is None:
        return None
    return tr.roofline_share_pct(
        ctx["lanes_per_launch"], found[0], ctx["device_kind"]
    )


def device_idle_pct(ctx: Dict) -> Optional[float]:
    if ctx.get("trace") is None or ctx.get("window_ns") is None:
        return None
    lo, hi = ctx["window_ns"]
    busy = tr.busy_seconds(ctx["trace"], (lo, hi))
    return tr.idle_share_pct(busy, (hi - lo) / 1e9)
