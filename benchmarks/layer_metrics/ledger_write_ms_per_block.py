"""ledger_write_ms_per_block: mean per block of the summed durations of the fabobs
spans ``ledger.block_append`` + ``ledger.state_commit``: pvt store + block
append with its fsync, then the state db commit (ledger/kvledger.py).
Layer: policy + MVCC + commit.  Moves: commit_tx_per_s."""

from benchmarks import span_readers as spans

SPANS = ("ledger.block_append", "ledger.state_commit")
MOVES = "commit_tx_per_s"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
