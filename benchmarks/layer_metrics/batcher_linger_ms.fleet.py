"""batcher_linger_ms.fleet: the mean duration of the fabobs span
``batcher.linger`` (parallel/batcher.py: the dispatcher's wait for company
after a launch's first request), one a launch, over the undisturbed part of
the window.
Layer: serving plane.  Moves: verdict_lanes_per_s."""

from benchmarks import layer_readers as readers

SPAN = "batcher.linger"
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return readers.span_mean_ms(ctx, SPAN)
