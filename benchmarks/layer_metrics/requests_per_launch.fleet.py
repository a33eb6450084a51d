"""requests_per_launch.fleet: the mean ``requests`` of the fabobs span
``batcher.launch`` (parallel/batcher.py): how many requests one launch of the
sidecar's batcher coalesced, over the launches of the undisturbed part of the
window.
Layer: serving plane.  Moves: verdict_lanes_per_s."""

from benchmarks import fleet_readers

MOVES = "verdict_lanes_per_s"


def read(ctx):
    return fleet_readers.mean_attr(ctx, "batcher.launch", "requests")
