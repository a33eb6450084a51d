"""mvcc_ms_per_block: mean per block of the summed durations of the fabobs span
``ledger.mvcc``: kvledger.commit's state_validation: flags, MVCC, private-data
batch, commit hash (ledger/kvledger.py).
Layer: policy + MVCC + commit.  Moves: commit_tx_per_s."""

from benchmarks import span_readers as spans

SPANS = ("ledger.mvcc",)
MOVES = "commit_tx_per_s"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
