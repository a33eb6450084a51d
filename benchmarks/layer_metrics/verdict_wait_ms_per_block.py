"""verdict_wait_ms_per_block: mean per block of the summed durations of the fabobs
span ``commit.await_verdicts``: the wait for the kernel at the head of
store_block (peer/channel.py; holds ``tpu.resolve``).
Layer: policy + MVCC + commit.  Moves: commit_tx_per_s."""

from benchmarks import span_readers as spans

SPANS = ("commit.await_verdicts",)
MOVES = "commit_tx_per_s"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
