"""mc_dispatch_ms_per_step: mean per step of the fabobs span ``mc.dispatch``
(parallel/multichannel.py: the jitted sharded call entered -> returned; the
transfer of the 3.3 MB stack from the host to the four devices is in here).
Layer: multi-channel validator.  Moves: verdict_lanes_per_s."""

from benchmarks import mc_readers

SPANS = ("mc.dispatch",)
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return mc_readers.ms_per_step(ctx, SPANS)
