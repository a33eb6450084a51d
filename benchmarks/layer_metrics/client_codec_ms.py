"""client_codec_ms: mean per request of the summed durations of the fabobs spans
``client.encode`` + ``client.decode``: lanes -> request frame, reply bytes ->
mask (serve/client.py).
Layer: serving plane - wire.  Moves: verdict_lanes_per_s."""

from benchmarks import span_readers as spans

SPANS = ("client.encode", "client.decode")
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
