"""Reducers over the program's own fabobs spans, for the per-layer metric
files that read them (PR 28).  Three of them read what the context already
carries (see layer_readers.py): ``spans`` (the ring's spans of the undisturbed
part of the window), ``trace`` with its host planes (every executed span is
also a ``TraceAnnotation`` of its own name, so it lies on the profiler's
clock) and ``window_ns`` (the whole launch-to-launch cycles of the traced
slice).  The fourth, for set-up, reads the live flight ring, since ``spans``
holds the window alone.  Each returns None where it finds nothing to read: a
program that lacks the spans, as the parent of PR 28 does, leaves the metric
out.

How a metric file finds its spans (benchmarks/README.md is the accepted
benchmark's file, which this PR may not edit, so the paragraph is here): the
file names them (``SPANS``, or ``PARENT`` / ``CHILDREN``, or an idle ``CLASS``)
and calls one reducer below.  Every span of one block carries ``block``, of one
sidecar request ``req_id`` (a child inherits the identifier from its parent in
fabobs), so no reader walks parent links: a mean "per block" is the summed
durations over the distinct identifiers seen.  The span names are listed in the
root README's obs section and, beside the metric that reads each, in PERF.md
section 3.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import trace_reduce as tr

Interval = Tuple[float, float]


# ---------------------------------------------------------------------------
# spans of the ring
# ---------------------------------------------------------------------------


def _units(event: Dict) -> List:
    """The blocks or requests a span belongs to: every span of one block
    carries ``block``, of one sidecar request ``req_id``, of one coalesced
    launch ``req_ids``."""
    args = event.get("args") or {}
    for key in ("block", "req_id"):
        if key in args:
            return [(key, args[key])]
    return [("req_id", r) for r in args.get("req_ids") or []]


def mean_ms_per_unit(ctx: Dict, names: Sequence[str]) -> Optional[float]:
    """The summed durations of the spans named, per block or request: the
    sum over all of them, over the number of distinct blocks (requests)
    they belong to.  None unless every name is there: a program that has
    some of the spans only (the parent of PR 28 has ``serve.decode`` and no
    ``serve.reply``) must not report a part under the whole's name."""
    wanted = set(names)
    seen = set()
    total_us = 0.0
    units = set()
    for event in ctx.get("spans") or []:
        if event["name"] in wanted:
            seen.add(event["name"])
            total_us += event["dur"]
            units.update(_units(event))
    if seen != wanted or not units:
        return None
    return total_us / len(units) / 1e3


def self_ms_per_unit(ctx: Dict, parent: str,
                     children: Sequence[str]) -> Optional[float]:
    """A parent span's mean per block less its named children's: the
    parent's own time.  The children named lie inside the parent."""
    whole = mean_ms_per_unit(ctx, [parent])
    inside = mean_ms_per_unit(ctx, children)
    if whole is None or inside is None:
        return None
    return whole - inside


# ---------------------------------------------------------------------------
# device-idle time under the program's annotations
# ---------------------------------------------------------------------------

# Annotations of different threads overlap (the client waits in
# `client.roundtrip` while the server works), so every instant of a gap goes to
# ONE class, the first in this order that has an annotation open at that
# instant; what none covers is "unattributed".  The classes therefore
# partition the idle time.
IDLE_CLASSES = (
    ("host_prep", ("tpu.prep", "tpu.dispatch")),
    ("wire", ("client.encode", "client.decode", "serve.decode", "serve.reply")),
)
UNATTRIBUTED = "unattributed"


def _union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Of two sorted disjoint lists."""
    out: List[Interval] = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """What of the sorted disjoint `a` the sorted disjoint `b` leaves."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append((lo, b[k][0]))
            lo = max(lo, b[k][1])
            k += 1
        if hi > lo:
            out.append((lo, hi))
    return out


def _length(intervals: Sequence[Interval]) -> float:
    return sum(end - start for start, end in intervals)


def _cycles(trace: tr.Trace, plane: str, window: Interval) -> int:
    """Launch-to-launch cycles inside `window`, which trace_reduce.whole_cycles
    cut from a first start to a last start of the program that holds most of
    the device's time: its starts in [lo, hi)."""
    seconds: Dict[str, float] = {}
    starts: Dict[str, int] = {}
    lo, hi = window
    for name, start, dur in trace[plane].get(tr.MODULES_LINE, []):
        end = min(start + dur, hi)
        if end <= max(start, lo):
            continue
        program = tr.program_of(name)
        seconds[program] = seconds.get(program, 0.0) + end - max(start, lo)
        if lo <= start < hi:
            starts[program] = starts.get(program, 0) + 1
    if not seconds:
        return 0
    return starts.get(max(seconds, key=seconds.get), 0)


def idle_ms_by_class(ctx: Dict) -> Optional[Dict[str, float]]:
    """{class: device-idle ms per launch-to-launch cycle} over the whole
    cycles of the traced slice, each instant of a gap given to one class
    (IDLE_CLASSES, then UNATTRIBUTED).  The values add up to the idle time of
    one cycle: device_idle_pct x the traced cycle.  None where the trace has
    no device plane, no whole cycle, or none of the program's annotations
    (the program then has no span on the profiler's clock to attribute
    to)."""
    trace, window = ctx.get("trace"), ctx.get("window_ns")
    if trace is None or window is None:
        return None
    planes = tr.device_planes(trace)
    if not planes:
        return None
    cycles = _cycles(trace, planes[0], window)
    if cycles < 1:
        return None
    gaps = _subtract([tuple(window)], tr.busy_intervals(trace, planes[0], window))
    found = False
    out: Dict[str, float] = {}
    for label, names in IDLE_CLASSES:
        notes = tr.host_annotations(trace, names)
        found = found or bool(notes)
        covered = _intersect(gaps, _union([(s, s + d) for _, s, d in notes]))
        out[label] = _length(covered) / cycles / 1e6
        gaps = _subtract(gaps, covered)
    if not found:
        return None
    out[UNATTRIBUTED] = _length(gaps) / cycles / 1e6
    return out


def idle_ms_per_cycle(ctx: Dict, label: str) -> Optional[float]:
    by_class = idle_ms_by_class(ctx)
    return None if by_class is None else by_class[label]


# ---------------------------------------------------------------------------
# set-up: JAX's own durations, as the program's `program.*` spans
# ---------------------------------------------------------------------------

WINDOW_SPAN = "bench.window"  # both drivers' span around the window


def setup_seconds_of(events: Sequence[Dict], names: Sequence[str],
                     marker: str = WINDOW_SPAN) -> Optional[float]:
    """Seconds covered by the spans named that ended before the span
    `marker` opened.  Covered, not summed: a jit traced inside another's
    trace reports a duration of its own, inside the outer one.  None where
    there is no such span, or not exactly one marker."""
    opened = [e["ts"] for e in events if e["name"] == marker]
    if len(opened) != 1:
        return None
    wanted = set(names)
    before = [
        (e["ts"], e["ts"] + e["dur"]) for e in events
        if e["name"] in wanted and e.get("ph") == "X"
        and e["ts"] + e["dur"] <= opened[0]
    ]
    if not before:
        return None
    return _length(_union(before)) / 1e6


def setup_seconds(names: Sequence[str]) -> Optional[float]:
    """`setup_seconds_of` over the process's live fabobs ring (the harness
    enables it before the first program is traced)."""
    from fabric_tpu.common import fabobs

    registry = fabobs.active()
    if registry is None:
        return None
    return setup_seconds_of(registry.trace_events(), names)
