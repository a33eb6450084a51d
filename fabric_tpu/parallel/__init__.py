"""Mesh-sharded execution (SURVEY.md §2.13 P3/P6).

The reference scales by channel-level process parallelism
(core/peer/peer.go:337-408: independent Channel objects) and per-tx
goroutines. The TPU-native equivalents here:

- `mesh`: device-mesh construction ("data" and "channel" axes).
- `sharded.ShardedVerify`: the batched ECDSA kernel jitted over a mesh —
  batch lanes sharded over "data" (P2/P6), whole channels sharded over
  "channel" (P3), masks all-gathered over ICI.
- `multichannel.MultiChannelValidator`: validates one block per channel
  in a single device step (BASELINE config #5: 4 channels x 2k tx).
- `batcher.VerifyBatcher`: cross-channel verify coalescing with bounded
  backpressure (P7) — few large launches instead of many small ones.
"""

from fabric_tpu.parallel.mesh import (
    CHANNEL_AXIS,
    DATA_AXIS,
    flat_mesh,
    grid_mesh,
)
from fabric_tpu.parallel.sharded import ShardedVerify
from fabric_tpu.parallel.multichannel import MultiChannelValidator
from fabric_tpu.parallel.batcher import BatchingProvider, VerifyBatcher

# CHANNEL_AXIS/DATA_AXIS dropped from __all__: mesh-internal axis
# names nothing outside this package references (fabdep dead-export)
__all__ = [
    "BatchingProvider",
    "flat_mesh",
    "grid_mesh",
    "ShardedVerify",
    "MultiChannelValidator",
    "VerifyBatcher",
]
