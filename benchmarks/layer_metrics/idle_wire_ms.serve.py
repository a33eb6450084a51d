"""idle_wire_ms.serve: device-idle ms per launch-to-launch cycle of the traced
slice lying under the program's annotations ``client.encode``,
``client.decode``, ``serve.decode``, ``serve.reply``
(span_readers.idle_ms_by_class: every instant of a gap goes to one class, so
the three idle_*_ms.serve add up to device_idle_pct.serve x the traced cycle).
Layer: device.  Moves: verdict_lanes_per_s."""

from benchmarks import span_readers as spans

CLASS = "wire"
MOVES = "verdict_lanes_per_s"


def read(ctx):
    return spans.idle_ms_per_cycle(ctx, CLASS)
