"""Ops: the decode step's share of its roofline (see
``harness/decode_work.py``); one reader per cell because the two cells move
different end-to-end metrics."""
from benchmark.harness import decode_work


def read(ctx):
    return decode_work.step_roofline(ctx)
