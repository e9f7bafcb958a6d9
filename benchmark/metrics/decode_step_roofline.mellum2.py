"""Ops: the decode step's share of its roofline (see
``harness/decode_work.py``) in the ``mellum2-12b-a2.5b-ep4`` cell: the
weights once, held experts whole, and each position's K/V rows: all it has
in the full layers, the last ``sliding_window`` in the window layers."""
from benchmark.harness import decode_work


def read(ctx):
    return decode_work.step_roofline(ctx)
