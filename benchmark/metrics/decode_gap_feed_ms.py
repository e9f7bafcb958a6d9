"""Decode: device idle per step while the loop thread was in ``decode_gather``
or ``decode_h2d`` (building the step's seven host arrays and landing them):
the trace's idle gaps intersected with the loop's own leaf spans
(``harness/stepgaps.py``)."""
from benchmark.harness import stepgaps


def read(ctx):
    return stepgaps.gap_ms(ctx, ("decode_gather", "decode_h2d"))
