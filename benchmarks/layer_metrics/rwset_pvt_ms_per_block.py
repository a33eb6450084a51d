"""rwset_pvt_ms_per_block: mean per block of the summed durations of the fabobs
spans ``commit.rwsets`` + ``commit.assemble_pvt``: materializing every
transaction's rwset with the refilter loop, then the private-data assembly,
between policy and kvledger.commit (peer/channel.py store_block).
Layer: policy + MVCC + commit.  Moves: commit_tx_per_s."""

from benchmarks import span_readers as spans

SPANS = ("commit.rwsets", "commit.assemble_pvt")
MOVES = "commit_tx_per_s"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
