"""Ops: the decode step's share of its roofline (see
``harness/decode_work.py``) in the ``lfm2-24b-a2b-ep8`` cell: the weights
once, held experts whole, the positions' K/V rows in the attention layers
and their two-row conv state in the conv layers."""
from benchmark.harness import decode_work


def read(ctx):
    return decode_work.step_roofline(ctx)
