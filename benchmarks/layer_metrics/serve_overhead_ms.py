"""serve_overhead_ms: mean client-side request wall less the mean
``serve.verify`` span: encode, socket, decode, admission, mask back.
Layer: serving plane - wire.  Moves: verdict_lanes_per_s."""

from benchmarks import layer_readers as readers

SPAN = "serve.verify"
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return readers.client_overhead_ms(ctx, SPAN)
