"""Ops: the decode step's share of its roofline (see
``harness/decode_work.py``) in the ``granite-4.0-h-micro`` cell: the
weights once, each position's K/V rows in the 4 attention layers, and in
the 36 mamba layers its float32 state read and written whole (4 bytes an
element, 2 MiB a layer) and its conv state."""
from benchmark.harness import decode_work


def read(ctx):
    return decode_work.step_roofline(ctx)
