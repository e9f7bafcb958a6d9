"""Expert layer: the busiest (expert layer, held expert) over the mean of
all of them, in pairs served over the traced window, by the loop's device
counters (``loop_counters`` spans: see ``harness/scopes.py``). 1 is even."""
from benchmark.harness import scopes


def read(ctx):
    served = scopes.counter_delta(ctx, "moe_served")
    if served is None or not sum(served):
        return None
    return max(served) * len(served) / float(sum(served))
