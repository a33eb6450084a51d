"""queue_wait_ms_per_block: mean per block of the summed durations of the fabobs
span ``pipeline.queue_wait``: a prepared block waiting for the committer: put
succeeded -> get returned (peer/pipeline.py).
Layer: pipeline - hand-off queue.  Moves: block_commit_p90_ms."""

from benchmarks import span_readers as spans

SPANS = ("pipeline.queue_wait",)
MOVES = "block_commit_p90_ms"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
