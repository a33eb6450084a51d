"""mc_epilogue_ms_per_step: the fabobs spans ``mc.epilogue`` of one step summed
(parallel/multichannel.py: per channel the mask's slice, finish_sig_results and
the policy stage, BlockValidator.validate), mean over the steps.
Layer: multi-channel validator.  Moves: verdict_lanes_per_s."""

from benchmarks import mc_readers

SPANS = ("mc.epilogue",)
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return mc_readers.ms_per_step(ctx, SPANS)
