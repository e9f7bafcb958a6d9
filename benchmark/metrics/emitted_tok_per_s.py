"""Decode: tokens the steps of the traced window handed to their requests,
by the steps' own count (``emit`` of each ``decode_step`` span), over the
window: finished and unfinished requests alike."""
from benchmark.harness import stepgaps


def read(ctx):
    return stepgaps.emitted_per_s(ctx)
