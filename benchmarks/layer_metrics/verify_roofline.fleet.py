"""verify_roofline.fleet: the least time the chip could take for the useful
lanes of the traced slice's whole launches of ``jit_verify_batch_bytes_device``
(trace_reduce.ops_per_verify over the int8 peak; the operations bound), over
their device time.  Each launch's useful lanes are its ``batcher.launch``'s
on the ring, matched to its run on the trace (fleet_readers.runs_with_lanes):
the padding of a 2,048-, 4,096- or 8,192-lane bucket does not count.
Layer: kernel.  Moves: verdict_lanes_per_s."""

from benchmarks import fleet_readers

MOVES = "verdict_lanes_per_s"


def read(ctx):
    return fleet_readers.roofline_pct(ctx)
