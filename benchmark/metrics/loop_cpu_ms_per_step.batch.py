"""Decode: CPU time of the loop thread itself over one step (``cpu_us`` of
each ``decode_step`` span, ``time.thread_time_ns``), mean over the traced
window's steps (the clock moves in ticks: ``harness/stepgaps.py``); one
reader per cell because the two cells move different end-to-end metrics."""
from benchmark.harness import stepgaps


def read(ctx):
    return stepgaps.loop_cpu_ms(ctx)
