"""policy_ms_per_block: mean per block of the summed durations of the fabobs span
``commit.validate``: finish_sig_results + validator.validate: identity and
endorsement policy (peer/channel.py store_block).
Layer: policy + MVCC + commit.  Moves: commit_tx_per_s."""

from benchmarks import span_readers as spans

SPANS = ("commit.validate",)
MOVES = "commit_tx_per_s"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
