"""From a profiler trace to numbers: device busy and idle time, the device
time of one XLA program, the longest idle gaps and what the host was doing in
them — and the yardstick the kernel's roofline share is read against (the
peaks table and the operation count of one ECDSA-P256 verification).

A trace is reduced from a plain structure, so that tests can hand-make one:

    {plane name: {line name: [(event name, start_ns, duration_ns), ...]}}

``load_xplane`` builds it from the ``.xplane.pb`` the JAX profiler writes.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns
Trace = Dict[str, Dict[str, List[Event]]]

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

# ---------------------------------------------------------------------------
# the yardstick
# ---------------------------------------------------------------------------

# Published peaks of one chip, keyed by jax's device_kind.  A device that is
# not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 393 TOP/s int8, "
                  "819 GB/s HBM per chip",
    },
}


def peaks_for(device_kind: str) -> Dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}: add it to "
            "benchmarks/trace_reduce.py PEAKS with its source"
        )
    return PEAKS[device_kind]


# One ECDSA-P256 verification, done the textbook way.  It depends on the
# algorithm alone: not on the kernel's window width, variant, CIOS form,
# limb radix, padded bucket or per-lane tables, so no kernel PR makes it
# stale and none can move it.
SCALAR_BITS = 256
DOUBLING_FIELD_MULS = 8        # Jacobian doubling, a = -3: 4 M + 4 S
MIXED_ADD_FIELD_MULS = 11      # Jacobian + affine addition: 8 M + 3 S
JOINT_ADD_SHARE = 0.75         # bits of (u1, u2) that are not both zero
FERMAT_INVERSION_MULS = 255 + 128  # square-and-multiply, a 256-bit exponent
BYTES_PER_FIELD_ELEMENT = 32
MACS_PER_FIELD_MUL = 2 * BYTES_PER_FIELD_ELEMENT ** 2  # product + reduction
OPS_PER_MAC = 2                # a peak in OP/s counts the multiply and the add


def field_muls_per_verify() -> int:
    """Field multiplications of one verification: w = s^-1 mod n (Fermat),
    u1 = e w, u2 = r w, then u1 G + u2 Q by one joint double-and-add over
    256 bits (Shamir: a doubling per bit, an addition of G, Q or G+Q for
    three bits in four, G+Q made once), then x = X Z^-2 (one more
    inversion, a squaring and a multiplication) compared with r."""
    joint = (
        SCALAR_BITS * DOUBLING_FIELD_MULS
        + int(SCALAR_BITS * JOINT_ADD_SHARE) * MIXED_ADD_FIELD_MULS
        + MIXED_ADD_FIELD_MULS  # G + Q
    )
    scalars = FERMAT_INVERSION_MULS + 2
    to_affine = FERMAT_INVERSION_MULS + 2
    return joint + scalars + to_affine


def ops_per_verify() -> int:
    """Integer operations (8-bit multiply and add counted apart) of one
    verification: each field multiplication is a 256x256-bit product, 32 x
    32 byte-by-byte multiply-accumulates, plus a reduction of the same
    size."""
    return field_muls_per_verify() * MACS_PER_FIELD_MUL * OPS_PER_MAC


def bytes_per_verify() -> int:
    """Bytes one lane moves through HBM at the least: digest, r and s in,
    a key index and a mask bit in, a verdict out.  The key table of a launch
    (a few KB) is shared by its lanes and left out: the bytes bound is three
    orders of magnitude under the operations bound either way."""
    return 3 * BYTES_PER_FIELD_ELEMENT + 4 + 1 + 1


def least_seconds(lanes: float, device_kind: str) -> Tuple[float, str]:
    """(the least time the chip could take for `lanes` verifications, which
    bound gives it)."""
    peak = peaks_for(device_kind)
    by_ops = lanes * ops_per_verify() / peak["int8_ops_per_s"]
    by_bytes = lanes * bytes_per_verify() / peak["hbm_bytes_per_s"]
    return (by_ops, "operations") if by_ops >= by_bytes else (by_bytes, "bytes")


def roofline_share_pct(lanes: float, kernel_seconds: float,
                       device_kind: str) -> float:
    """The least time for the useful lanes over the kernel's device time.
    Over 100% is an error of the run (lanes counted too high, or kernel time
    that leaves out part of the work), never clipped."""
    if kernel_seconds <= 0:
        raise ValueError("roofline share of no kernel time")
    least, _ = least_seconds(lanes, device_kind)
    share = 100.0 * least / kernel_seconds
    if share > 100.0:
        raise ValueError(
            f"roofline share {share:.1f}% > 100%: {lanes} lanes in "
            f"{kernel_seconds} s of kernel time"
        )
    return share


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData

    trace: Trace = {}
    for plane in ProfileData.from_file(path).planes:
        lines = trace.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                events.append((ev.name, float(ev.start_ns), float(ev.duration_ns)))
    return trace


def device_planes(trace: Trace) -> List[str]:
    return sorted(p for p in trace if p.startswith(DEVICE_PLANE_PREFIX))


def _clip(events: Sequence[Event], window: Optional[Tuple[float, float]]):
    for name, start, dur in events:
        end = start + dur
        if window is not None:
            start, end = max(start, window[0]), min(end, window[1])
        if end > start:
            yield name, start, end


def busy_intervals(trace: Trace, plane: str,
                   window: Optional[Tuple[float, float]] = None
                   ) -> List[Tuple[float, float]]:
    """The union, as sorted disjoint [start, end) in ns, of the intervals
    in which any operation ran on the device plane: the op-level line where
    the trace has one, else the module-level line."""
    lines = trace[plane]
    events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
    spans = sorted((s, e) for _, s, e in _clip(events, window))
    merged: List[Tuple[float, float]] = []
    for start, end in spans:
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


DROPPED = "Trace Buffers Dropped"
EDGE_NS = 1000.0  # a module event this close to the trace's edge was cut by it


def device_extent(trace: Trace, plane: str) -> Tuple[float, float]:
    """[first event start, last event end] of a device plane's module and
    op lines: the time over which the device was being traced."""
    starts, ends = [], []
    for line in (MODULES_LINE, OPS_LINE):
        for _, start, dur in trace[plane].get(line, []):
            starts.append(start)
            ends.append(start + dur)
    if not starts:
        raise ValueError(f"no device event on {plane}")
    return min(starts), max(ends)


def traced_slice(trace: Trace, annotation: str) -> Tuple[float, float]:
    """The span of the harness's slice annotation on the trace's clock, cut
    short where the device's trace buffer overflowed (the profiler then
    records an event named DROPPED on the device plane, and nothing the
    device did from there on).  The profiler starts and stops around the
    annotation, so the device is traced all through it."""
    marks = host_annotations(trace, [annotation])
    if len(marks) != 1:
        raise ValueError(f"{len(marks)} spans named {annotation!r}, not one")
    lo, hi = marks[0][1], marks[0][1] + marks[0][2]
    for plane in device_planes(trace):
        for events in trace[plane].values():
            for name, start, _ in events:
                if name == DROPPED and lo < start < hi:
                    hi = start
    return lo, hi


def program_of(event_name: str) -> str:
    """A module event's program: the profiler appends the module's id in
    brackets."""
    return event_name.split("(", 1)[0]


def whole_cycles(trace: Trace, within: Tuple[float, float]
                 ) -> Tuple[float, float]:
    """The window that busy and idle time are read over: from the first
    start to the last start, inside `within`, of the program that holds most
    of the device's time there.  It holds whole cycles, each one launch and
    the gap that follows it, wherever the tracer started and stopped: a
    window cut to the events' own extent would hold one gap fewer than
    launches, and one cut by the clock a part of a gap, so the idle share
    would move with the slice's length and with the number of launches
    that fit into it.  A launch in flight when the slice opens has no start
    in it and opens no cycle.  Where `within` holds fewer than two starts (launches
    longer than the slice), the window is `within` itself."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no device plane")
    plane = planes[0]
    seconds: Dict[str, float] = {}
    starts: Dict[str, List[float]] = {}
    for name, start, end in _clip(trace[plane].get(MODULES_LINE, []), within):
        program = program_of(name)
        seconds[program] = seconds.get(program, 0.0) + (end - start)
        if start > within[0]:  # not clipped: a true start
            starts.setdefault(program, []).append(start)
    if not seconds:
        return within
    begun = sorted(starts.get(max(seconds, key=seconds.get), []))
    if len(begun) < 2:
        return within
    return begun[0], begun[-1]


def busy_seconds(trace: Trace,
                 window: Optional[Tuple[float, float]] = None) -> float:
    """Device-busy seconds, averaged over the device planes."""
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no device plane")
    total = sum(
        end - start
        for plane in planes
        for start, end in busy_intervals(trace, plane, window)
    )
    return total / len(planes) / 1e9


def idle_share_pct(busy_s: float, window_s: float) -> float:
    if window_s <= 0:
        raise ValueError("idle share of no window")
    return 100.0 * (1.0 - busy_s / window_s)


def program_events(trace: Trace, program: str,
                   window: Optional[Tuple[float, float]] = None
                   ) -> List[Tuple[float, float]]:
    """[start, end) of every WHOLE device run of the XLA program `program`,
    on the first device plane that ran it.  Part of a launch is not a
    launch: a run that the window cuts is left out, and so is one that was
    in flight when the device's tracer started or stopped (the profiler
    records it from, or up to, that moment only).  Inside the harness's
    slice the tracer cuts nothing (it starts before the annotation opens and
    stops after it closes); with no window given, a run that touches the
    edge of what the plane recorded counts as cut."""
    for plane in device_planes(trace):
        if window is None:
            first, last = device_extent(trace, plane)
            window = (first + EDGE_NS, last - EDGE_NS)
        events = [
            (start, start + dur)
            for name, start, dur in trace[plane].get(MODULES_LINE, [])
            if program_of(name) == program
            and start >= window[0] and start + dur <= window[1]
        ]
        if events:
            return sorted(events)
    return []


def program_seconds_per_launch(trace: Trace, program: str,
                               window: Optional[Tuple[float, float]] = None
                               ) -> Optional[Tuple[float, int]]:
    """(mean device seconds of one launch of `program`, launches counted),
    or None when the trace holds no launch of it."""
    runs = program_events(trace, program, window)
    if not runs:
        return None
    return sum(e - s for s, e in runs) / len(runs) / 1e9, len(runs)


# ---------------------------------------------------------------------------
# the breakdown
# ---------------------------------------------------------------------------


def op_name(event_name: str) -> str:
    """The profiler names a device op by its whole HLO line (`%fusion.3 =
    u32[19,2048]{...} fusion(...)`, thousands of characters for a `while`):
    keep what stands before the ` = `."""
    return event_name.split(" = ", 1)[0][:80]


def top_device_ops(trace: Trace,
                   window: Optional[Tuple[float, float]] = None,
                   limit: int = 10) -> List[List]:
    """[[op name, seconds]] of the device operations that took most time,
    summed by name over the first device plane's op-level line.  A `while`
    holds the ops of its body, so the rows overlap."""
    planes = device_planes(trace)
    if not planes:
        return []
    lines = trace[planes[0]]
    totals: Dict[str, float] = {}
    events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
    for name, start, end in _clip(events, window):
        name = op_name(name)
        totals[name] = totals.get(name, 0.0) + (end - start)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:limit]
    return [[name, dur / 1e9] for name, dur in ranked]


def host_annotations(trace: Trace, names: Sequence[str]) -> List[Event]:
    """The harness's own TraceAnnotation spans, off the host planes."""
    wanted = set(names)
    found: List[Event] = []
    for plane, lines in trace.items():
        if not plane.startswith(HOST_PLANE_PREFIX):
            continue
        for events in lines.values():
            found.extend(ev for ev in events if ev[0] in wanted)
    return sorted(found, key=lambda ev: ev[1])


def longest_idle_gaps(trace: Trace, annotation_names: Sequence[str],
                      window: Tuple[float, float],
                      limit: int = 10) -> List[List]:
    """[[what the host was doing, seconds]] for the longest gaps between
    device-busy intervals on the first device plane.  A gap is labelled with
    the harness annotation that covers most of it ("unattributed" where none
    does); gaps with one label are listed one by one, longest first."""
    planes = device_planes(trace)
    if not planes:
        return []
    busy = busy_intervals(trace, planes[0], window)
    lo, hi = window
    edges = [lo] + [t for span in busy for t in span] + [hi]
    gaps = [
        (edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
        if edges[i + 1] > edges[i]
    ]
    gaps.sort(key=lambda g: g[0] - g[1])
    notes = host_annotations(trace, annotation_names)
    out: List[List] = []
    for start, end in gaps[:limit]:
        cover: Dict[str, float] = {}
        for name, a_start, a_dur in notes:
            overlap = min(end, a_start + a_dur) - max(start, a_start)
            if overlap > 0:
                cover[name] = cover.get(name, 0.0) + overlap
        label = max(cover, key=cover.get) if cover else "unattributed"
        out.append([label, (end - start) / 1e9])
    return out
