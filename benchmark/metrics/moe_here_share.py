"""Expert layer: (token, choice) pairs that chose an expert held here, over
the pairs the expert layers routed, by the loop's device counters over the
traced window (``loop_counters`` spans: see ``harness/scopes.py``). Under
even routing it is held / router_width."""
from benchmark.harness import scopes


def read(ctx):
    served = scopes.counter_delta(ctx, "moe_served")
    routed = scopes.counter_delta(ctx, "moe_routed")
    if served is None or routed is None:
        return None
    return 100.0 * sum(served) / sum(routed)
