"""verify_roofline.serve: the least time the chip could take for the useful
lanes of one launch (trace_reduce.ops_per_verify over the int8 peak; the
operations bound, the bytes bound is far below it), over the device time of
one launch of ``jit_verify_batch_bytes_device``.
Layer: kernel.  Moves: verdict_lanes_per_s."""

from benchmarks import layer_readers as readers

PROGRAM = "jit_verify_batch_bytes_device"
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return readers.program_roofline_pct(ctx, PROGRAM)
