"""State-space layer: the share of the step program's device time that
the Mamba-2 mixers take, the union of the intervals of the operations
under the ``layer/ssm`` and ``cache_write/ssm`` scopes (see
``harness/scopes.py``) over the program's runs in the traced window. It
says whether the cell's premise (the state is most of a step) holds on the
chip; ``None`` where the step has no such scope."""
from benchmark.harness import scopes, tracered

PREFIXES = ("layer/ssm", "cache_write/ssm")


def read(ctx):
    table = scopes.table(ctx)
    if not table or not any(v.startswith(PREFIXES) for v in table.values()):
        return None
    t0, t1 = ctx["window_ns"]
    runs = ctx["trace"].whole_runs(ctx["cfg"]["program"], t0, t1)
    if not runs:
        return None
    lo, hi = runs[0][0], runs[-1][1]
    devices = ctx["trace"].devices
    ops = devices[sorted(devices)[0]]["ops"]
    under = [(s, e) for name, s, e in ops
             if lo <= s and e <= hi
             and table.get(name, "").startswith(PREFIXES)]
    step_s = sum(e - s for s, e in runs) / 1e9
    return 100.0 * tracered.union_seconds(under) / step_s
