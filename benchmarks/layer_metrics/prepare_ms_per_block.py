"""prepare_ms_per_block: mean duration of the fabobs span ``pipeline.prepare``
(peer/pipeline.py: data-hash check, block parse, collect_sig_jobs, the
provider's host prep and the dispatch to the device) over the window.
Layer: pipeline - prepare stage.  Moves: commit_tx_per_s."""

from benchmarks import layer_readers as readers

SPAN = "pipeline.prepare"
MOVES = "commit_tx_per_s"


def read(ctx):
    return readers.span_mean_ms(ctx, SPAN)
