"""mc_prepare_ms_per_step: the fabobs spans ``mc.prepare`` of one step summed
(parallel/multichannel.py: per channel block parse, collect_sig_jobs and
prep_limbs, the DER parse and byte-to-limb conversion on the host; four
channels one after another on one Python thread), mean over the steps of the
undisturbed part of the window.
Layer: multi-channel validator.  Moves: verdict_lanes_per_s."""

from benchmarks import mc_readers

SPANS = ("mc.prepare",)
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return mc_readers.ms_per_step(ctx, SPANS)
