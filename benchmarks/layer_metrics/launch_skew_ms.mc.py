"""launch_skew_ms.mc: latest less earliest start of the same launch of
``jit_verify_batch_device`` over the four device planes, mean over the whole
launches of the traced slice (mc_readers.launch_skew_ms): how far apart the
four chips begin one sharded step.  The step's filters wait for the last chip.
Layer: device.  Moves: verdict_p95_ms."""

from benchmarks import mc_readers

MOVES = "verdict_p95_ms"


def read(ctx):
    return mc_readers.launch_skew_ms(ctx)
