"""store_host_ms_per_block: mean per block of the fabobs span ``pipeline.commit``
less that of ``commit.await_verdicts`` inside it: stage B less its wait for the
kernel: the host work a kernel PR uncovers (policy, MVCC, sqlite, fsync).
Layer: policy + MVCC + commit.  Moves: commit_tx_per_s."""

from benchmarks import span_readers as spans

PARENT = "pipeline.commit"
CHILDREN = ("commit.await_verdicts",)
MOVES = "commit_tx_per_s"


def read(ctx):
    return spans.self_ms_per_unit(ctx, PARENT, CHILDREN)
