"""verify_roofline.mc: the least time ONE chip could take for its own useful
lanes of a launch (one channel's 1,498, not the bucket's 2,048 and not the
step's 5,992; trace_reduce.ops_per_verify over the chip's int8 peak, the
operations bound), over the device time of one launch of
``jit_verify_batch_device`` on one device plane.
Layer: kernel.  Moves: verdict_lanes_per_s."""

from benchmarks import layer_readers as readers
from benchmarks import mc_readers

MOVES = "verdict_lanes_per_s"


def read(ctx):
    return readers.program_roofline_pct(ctx, mc_readers.PROGRAM)
