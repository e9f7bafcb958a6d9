"""Decode: device idle per step while the loop thread was in
``decode_readback`` (before the program starts and after it ends, until its
tokens are on the host): the trace's idle gaps intersected with the loop's
own leaf spans (``harness/stepgaps.py``)."""
from benchmark.harness import stepgaps


def read(ctx):
    return stepgaps.gap_ms(ctx, ("decode_readback",))
