"""Decode: share of the traced window's device idle that lies under any of
the loop's six leaf spans: the completeness of the split the four
``decode_gap_*`` metrics give (``harness/stepgaps.py``)."""
from benchmark.harness import stepgaps


def read(ctx):
    return stepgaps.covered(ctx)
