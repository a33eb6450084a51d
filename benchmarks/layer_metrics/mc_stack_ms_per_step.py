"""mc_stack_ms_per_step: mean per step of the fabobs span ``mc.stack``
(parallel/multichannel.py: every channel's limb arrays padded to the bucket and
stacked on the channel axis, five (4, 20, 2048) uint32 arrays and the mask).
Layer: multi-channel validator.  Moves: verdict_lanes_per_s."""

from benchmarks import mc_readers

SPANS = ("mc.stack",)
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return mc_readers.ms_per_step(ctx, SPANS)
