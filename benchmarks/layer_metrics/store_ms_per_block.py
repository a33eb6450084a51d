"""store_ms_per_block: mean duration of the fabobs span ``pipeline.commit``
(channel.store_block: resolve the verdicts, policy, MVCC, kvledger.commit
with the block store's fsync) over the window.
Layer: policy + MVCC + commit.  Moves: commit_tx_per_s."""

from benchmarks import layer_readers as readers

SPAN = "pipeline.commit"
MOVES = "commit_tx_per_s"


def read(ctx):
    return readers.span_mean_ms(ctx, SPAN)
