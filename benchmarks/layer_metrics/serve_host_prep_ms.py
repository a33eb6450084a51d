"""serve_host_prep_ms: mean per request of the summed durations of the fabobs
spans ``tpu.prep`` + ``tpu.dispatch``: the provider's host prep and dispatch
under ``batcher.launch`` (crypto/tpu_provider.py).
Layer: provider - host prep and resolve.  Moves: verdict_lanes_per_s."""

from benchmarks import span_readers as spans

SPANS = ("tpu.prep", "tpu.dispatch")
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
