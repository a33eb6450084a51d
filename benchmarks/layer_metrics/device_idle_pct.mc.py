"""device_idle_pct.mc: 1 - (union of the intervals in which any operation ran
on a device plane) / the whole launch-to-launch cycles of the traced slice,
mean over the four device planes (trace_reduce.busy_seconds averages over the
planes).  Layer: device.  Moves: verdict_lanes_per_s."""

from benchmarks import layer_readers as readers

MOVES = "verdict_lanes_per_s"


def read(ctx):
    return readers.device_idle_pct(ctx)
