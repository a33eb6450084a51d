"""verify_kernel_ms.mc: device time of one launch of the sharded XLA program
``jit_verify_batch_device`` (parallel/sharded.py: jit(vmap(verify_batch_device)),
one channel's 2,048-lane bucket on each chip) on ONE device plane, from the
profiler's trace: the sum of its whole module events in the slice over their
count.  Layer: kernel.  Moves: verdict_lanes_per_s."""

from benchmarks import layer_readers as readers
from benchmarks import mc_readers

MOVES = "verdict_lanes_per_s"


def read(ctx):
    return readers.program_ms_per_launch(ctx, mc_readers.PROGRAM)
