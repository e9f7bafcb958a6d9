"""Decode: prompt positions one prefill pass carried, mean over the passes
the traced window's steps dispatched, by the ``decode_step`` spans' own
``prefill`` (``harness/prefill.py``): what one read of the weights by a
pass is shared among."""
from benchmark.harness import prefill


def read(ctx):
    return prefill.positions_per_pass(ctx)
