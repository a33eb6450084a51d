"""device_idle_pct.serve: 1 - (union of the intervals in which any operation
ran on the device plane) / the traced window, from the profiler's trace.
Layer: device.  Moves: verdict_lanes_per_s."""

from benchmarks import layer_readers as readers

MOVES = "verdict_lanes_per_s"


def read(ctx):
    return readers.device_idle_pct(ctx)
