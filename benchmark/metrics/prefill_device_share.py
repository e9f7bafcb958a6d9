"""Ops: share of the device's busy time, in the traced window, that the
prefill passes took: the runs of the module ``loop_program`` names for the
pass over the window's ``busy_s``."""
from benchmark.harness import requests


def read(ctx):
    runs = requests.prefill_runs(ctx)
    t0, t1 = ctx["window_ns"]
    busy_s = ctx["trace"].busy_seconds(t0, t1) if runs else 0.0
    if not busy_s:
        return None
    return 100.0 * sum(e - s for s, e in runs) / 1e9 / busy_s
