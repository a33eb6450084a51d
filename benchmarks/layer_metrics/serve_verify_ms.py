"""serve_verify_ms: mean duration of the fabobs span ``serve.verify``
(serve/server.py: submitted to the batcher -> mask) over the window.
Layer: serving plane.  Moves: verdict_lanes_per_s."""

from benchmarks import layer_readers as readers

SPAN = "serve.verify"
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return readers.span_mean_ms(ctx, SPAN)
