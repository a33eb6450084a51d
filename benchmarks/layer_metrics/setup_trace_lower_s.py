"""setup_trace_lower_s: seconds of set-up covered by the fabobs spans
``program.trace_lower`` (serve/registry.py _CompileCounters: JAX's own durations of
tracing to a jaxpr and lowering it to MLIR (``jaxpr_trace`` + ``jaxpr_to_mlir_module``),
each of 0.1 s or more) that ended before the window opened.  Read from the live
flight ring (span_readers.setup_seconds): ``ctx["spans"]`` holds the window alone.
Layer: set-up.  Moves: setup_s."""

from benchmarks import span_readers as spans

SPANS = ("program.trace_lower",)
MOVES = "setup_s"


def read(ctx):
    return spans.setup_seconds(SPANS)
