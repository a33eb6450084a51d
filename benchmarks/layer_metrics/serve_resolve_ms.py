"""serve_resolve_ms: mean per request of the summed durations of the fabobs span
``tpu.resolve``: the wait for the device and the copy back under
``batcher.settle`` (holds the whole kernel: nothing overlaps it here).
Layer: provider - host prep and resolve.  Moves: verdict_lanes_per_s."""

from benchmarks import span_readers as spans

SPANS = ("tpu.resolve",)
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
