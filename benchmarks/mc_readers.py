"""Reducers for the multi-channel path's per-layer metric files (PR 34): the
``mc.*`` fabobs spans, which carry ``step=`` (one ``validate()`` call) where
the commit path's carry ``block=`` and the sidecar's ``req_id=``, and the
device planes of a trace in which one launch runs on several chips at once.
Each returns None where it finds nothing to read: a program without the spans,
as the parent of PR 34 is, leaves the metric out.

The context is layer_readers.py's: ``spans`` (the ring's spans of the
undisturbed part of the window), ``trace``, ``slice_ns`` (the traced slice:
whole launches are counted in it)."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from benchmarks import trace_reduce as tr

# the XLA program of ShardedVerify.verify_channels,
# jax.jit(jax.vmap(verify_batch_device)): the name its module events carry on
# every device plane (read off a trace: my chip run, PR 34)
PROGRAM = "jit_verify_batch_device"


def ms_per_step(ctx: Dict, names: Sequence[str]) -> Optional[float]:
    """The summed durations of the spans named, per step: the sum over all
    of them over the number of distinct ``step`` values they carry (a step's
    four ``mc.prepare`` add up).  None unless every name is there."""
    wanted = set(names)
    seen, steps = set(), set()
    total_us = 0.0
    for event in ctx.get("spans") or []:
        step = (event.get("args") or {}).get("step")
        if event["name"] in wanted and step is not None:
            seen.add(event["name"])
            steps.add(step)
            total_us += event["dur"]
    if seen != wanted or not steps:
        return None
    return total_us / len(steps) / 1e3


def launches_by_plane(trace: tr.Trace, program: str,
                      window: Optional[Tuple[float, float]]
                      ) -> Dict[str, List[Tuple[float, float]]]:
    """{device plane: [start, end) of every WHOLE run of `program` on it}
    (trace_reduce.program_events reads the first plane that ran it; this
    reads them all, by the same rule for a run the window cuts)."""
    out: Dict[str, List[Tuple[float, float]]] = {}
    for plane in tr.device_planes(trace):
        if window is not None:
            lo, hi = window
        else:
            first, last = tr.device_extent(trace, plane)
            lo, hi = first + tr.EDGE_NS, last - tr.EDGE_NS
        runs = sorted(
            (start, start + dur)
            for name, start, dur in trace[plane].get(tr.MODULES_LINE, [])
            if tr.program_of(name) == program
            and start >= lo and start + dur <= hi
        )
        if runs:
            out[plane] = runs
    return out


def launch_skew_ms(ctx: Dict, program: str = PROGRAM) -> Optional[float]:
    """Latest less earliest start of the same launch over the device planes,
    mean over the launches that every plane holds whole.  Starts are grouped
    into launches by time: a plane cannot begin its next run before this one
    has ended, so a start later than the group's first by a run's length
    opens the next launch.  None with fewer than two planes running the
    program."""
    if ctx.get("trace") is None:
        return None
    by_plane = launches_by_plane(ctx["trace"], program, ctx.get("slice_ns"))
    if len(by_plane) < 2:
        return None
    shortest = min(e - s for runs in by_plane.values() for s, e in runs)
    starts = sorted(
        (s, plane) for plane, runs in by_plane.items() for s, _ in runs
    )
    groups: List[List[Tuple[float, str]]] = []
    for start, plane in starts:
        if groups and start - groups[-1][0][0] < shortest:
            groups[-1].append((start, plane))
        else:
            groups.append([(start, plane)])
    skews = [
        group[-1][0] - group[0][0] for group in groups
        if {plane for _, plane in group} == set(by_plane)
        and len(group) == len(by_plane)
    ]
    if not skews:
        return None
    return sum(skews) / len(skews) / 1e6
