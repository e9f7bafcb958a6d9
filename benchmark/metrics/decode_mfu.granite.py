"""Ops: the whole decode step's share of the chip's peak FLOP/s (see
``harness/decode_work.py``) in the ``granite-4.0-h-micro`` cell."""
from benchmark.harness import decode_work


def read(ctx):
    return decode_work.step_mfu(ctx)
