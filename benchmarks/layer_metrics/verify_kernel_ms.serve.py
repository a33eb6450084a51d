"""verify_kernel_ms.serve: device time of one launch of the XLA program
``jit_verify_batch_bytes_device`` (ops/p256_kernel.py), from the profiler's
device plane: the sum of its module events in the window over their count.
Layer: kernel.  Moves: verdict_lanes_per_s."""

from benchmarks import layer_readers as readers

PROGRAM = "jit_verify_batch_bytes_device"
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return readers.program_ms_per_launch(ctx, PROGRAM)
