"""serve_codec_ms: mean per request of the summed durations of the fabobs spans
``serve.decode`` + ``serve.reply``: request frame -> lanes; mask -> reply frame
-> socket (serve/server.py).
Layer: serving plane - wire.  Moves: verdict_lanes_per_s."""

from benchmarks import span_readers as spans

SPANS = ("serve.decode", "serve.reply")
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
