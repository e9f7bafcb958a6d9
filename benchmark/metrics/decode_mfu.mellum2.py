"""Ops: the whole decode step's share of the chip's peak FLOP/s (see
``harness/decode_work.py``) in the ``mellum2-12b-a2.5b-ep4`` cell."""
from benchmark.harness import decode_work


def read(ctx):
    return decode_work.step_mfu(ctx)
