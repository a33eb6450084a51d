"""host_prep_ms_per_block: mean per block of the summed durations of the fabobs
spans ``tpu.prep`` + ``tpu.dispatch``: DER parse, digest pack, key-column
dedup; pad, jit call, host-to-device enqueue (crypto/tpu_provider.py
batch_verify_async).
Layer: provider - host prep and resolve.  Moves: commit_tx_per_s."""

from benchmarks import span_readers as spans

SPANS = ("tpu.prep", "tpu.dispatch")
MOVES = "commit_tx_per_s"


def read(ctx):
    return spans.mean_ms_per_unit(ctx, SPANS)
