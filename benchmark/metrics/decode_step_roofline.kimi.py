"""Ops: the decode step's share of its roofline (see
``harness/decode_work.py``) in the ``kimi-k2-ep32`` cell: the weights once,
held experts whole, and the positions' latent rows."""
from benchmark.harness import decode_work


def read(ctx):
    return decode_work.step_roofline(ctx)
