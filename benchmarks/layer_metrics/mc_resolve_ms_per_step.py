"""mc_resolve_ms_per_step: mean per step of the fabobs span ``mc.resolve``
(parallel/multichannel.py: the wait for the four devices and the copy back of
the (4, 2048) mask; nothing overlaps it, so it holds the kernel's time).
Layer: multi-channel validator.  Moves: verdict_lanes_per_s."""

from benchmarks import mc_readers

SPANS = ("mc.resolve",)
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return mc_readers.ms_per_step(ctx, SPANS)
