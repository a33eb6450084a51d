"""parse_ms_per_block: mean per block of the summed durations of the fabobs spans
``prepare.content_check`` + ``prepare.parse`` + ``prepare.collect_sig_jobs``:
data hash and orderer signature, parse_block, collect_sig_jobs (peer/channel.py
prepare_block).
Layer: pipeline - prepare stage.  Moves: commit_tx_per_s."""

from benchmarks import span_readers as spans

SPANS = ("prepare.content_check", "prepare.parse", "prepare.collect_sig_jobs")
MOVES = "commit_tx_per_s"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
