"""Ops: device time of one prefill pass, mean over the runs, inside the
traced window, of the module that the loop's ``loop_program`` span names
for the pass (``prefill_program``; ``harness/requests.py``)."""
from benchmark.harness import requests


def read(ctx):
    runs = requests.prefill_runs(ctx)
    if not runs:
        return None
    return sum(e - s for s, e in runs) / len(runs) / 1e6
