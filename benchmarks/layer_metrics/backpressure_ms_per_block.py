"""backpressure_ms_per_block: mean per block of the summed durations of the fabobs
span ``pipeline.backpressure``: the submitter held by the full queue: first put
attempt -> put succeeded (peer/pipeline.py submit).
Layer: pipeline - hand-off queue.  Moves: block_commit_p90_ms."""

from benchmarks import span_readers as spans

SPANS = ("pipeline.backpressure",)
MOVES = "block_commit_p90_ms"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
