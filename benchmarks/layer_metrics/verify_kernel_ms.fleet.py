"""verify_kernel_ms.fleet: device time of one launch of the XLA program
``jit_verify_batch_bytes_device`` (ops/p256_kernel.py), whatever its width
(2,048, 4,096 or 8,192 lanes), from the profiler's device plane: the sum of
its whole module events in the traced slice over their count, a launch the
tracer cut at the trace's edge left out (fleet_readers.whole_runs).
Layer: kernel.  Moves: verdict_lanes_per_s."""

from benchmarks import fleet_readers

MOVES = "verdict_lanes_per_s"


def read(ctx):
    return fleet_readers.kernel_ms_per_launch(ctx)
