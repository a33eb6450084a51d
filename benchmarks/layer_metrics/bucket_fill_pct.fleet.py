"""bucket_fill_pct.fleet: the useful lanes of the provider's launches over the
lanes they were padded to, summed over the fabobs spans ``tpu.dispatch``
(crypto/tpu_provider.py: ``lanes``, ``bucket``) of the undisturbed part of the
window.
Layer: provider - host prep and resolve.  Moves: verdict_lanes_per_s."""

from benchmarks import fleet_readers

MOVES = "verdict_lanes_per_s"


def read(ctx):
    return fleet_readers.bucket_fill_pct(ctx)
