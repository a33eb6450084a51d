"""setup_compile_or_load_s: seconds of set-up covered by the fabobs spans
``program.compile_or_load`` (serve/registry.py _CompileCounters: JAX's own durations of
``backend_compile``: a cold XLA compile, or the load of a cached executable,
each of 0.1 s or more) that ended before the window opened.  Read from the live
flight ring (span_readers.setup_seconds): ``ctx["spans"]`` holds the window alone.
Layer: set-up.  Moves: setup_s."""

from benchmarks import span_readers as spans

SPANS = ("program.compile_or_load",)
MOVES = "setup_s"


def read(ctx):
    return spans.setup_seconds(SPANS)
