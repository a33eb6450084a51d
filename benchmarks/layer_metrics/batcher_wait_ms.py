"""batcher_wait_ms: mean per request of the summed durations of the fabobs span
``batcher.queue_wait``: admission, the dispatcher's pick-up and the linger
window: _Request.t_submit -> its launch begins (parallel/batcher.py).
Layer: serving plane.  Moves: verdict_lanes_per_s."""

from benchmarks import span_readers as spans

SPANS = ("batcher.queue_wait",)
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
