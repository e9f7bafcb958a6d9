"""Decode: share of the cache positions the traced window's steps
committed that emitted no token (a prompt being fed), by the steps' own
count (``n`` and ``emit`` of each ``decode_step`` span)."""
from benchmark.harness import stepgaps


def read(ctx):
    return stepgaps.prompt_position_share(ctx)
