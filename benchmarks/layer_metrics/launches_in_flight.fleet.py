"""launches_in_flight.fleet: the mean ``inflight`` of the fabobs span
``batcher.launch`` (parallel/batcher.py: the launches still unsettled when
this one was dispatched), over the launches of the undisturbed part of the
window.
Layer: serving plane.  Moves: verdict_lanes_per_s."""

from benchmarks import fleet_readers

MOVES = "verdict_lanes_per_s"


def read(ctx):
    return fleet_readers.mean_attr(ctx, "batcher.launch", "inflight")
