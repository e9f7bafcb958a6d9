"""Decode: device idle per step while the loop thread was in ``decode_commit``
or ``decode_admit`` (committing positions and tokens, retiring, seating the
next requests): the trace's idle gaps intersected with the loop's own leaf
spans (``harness/stepgaps.py``)."""
from benchmark.harness import stepgaps


def read(ctx):
    return stepgaps.gap_ms(ctx, ("decode_commit", "decode_admit"))
