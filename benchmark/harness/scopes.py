"""Device time by ``jax.named_scope``, for the readers of a layer's share
of its roofline (``moe_layer_roofline``, ``mla_layer_roofline``).

The reduced trace (``tracered.Trace``) names every device operation by its
HLO instruction (``fusion.111``) and keeps no statistic that says which
scope it was traced under. ``DecodeLoop`` says it once a trace: the span
``loop_program`` carries ``scopes``, ``{instruction: scope path}`` read from
the step executable's own metadata, and ``program``, the module's name. A
program that sends no such span (any commit before it was added) gives the
readers here nothing to read: ``None``.
"""
from . import decode_work, tracered


def table(ctx):
    """The step program's ``{instruction: scope}``, or ``None``."""
    for name, _, _, args in ctx["spans"]:
        if name == "loop_program" \
                and args.get("program") == ctx["cfg"]["program"]:
            return args.get("scopes") or None
    return None


def layer_roofline(ctx, prefixes, work):
    """Sum over the traced window's steps of the least time the layers
    under ``prefixes`` could take (``work(row) -> (flops, bytes)`` for one
    step's ``[(record, position)]``), over the device time of the
    operations traced under those scopes in the step program's runs there
    (the union of their intervals: a copy that runs beside a product is
    not counted twice)."""
    scopes = table(ctx)
    if not scopes:
        return None
    t0, t1 = ctx["window_ns"]
    runs = ctx["trace"].whole_runs(ctx["cfg"]["program"], t0, t1)
    steps = decode_work._steps_in_window(ctx)
    if not runs or not steps:
        return None
    peaks = ctx["run"].peaks
    least = 0.0
    for _, _, row in steps:
        flops, nbytes = work(row)
        least += max(flops / peaks["flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    n = min(len(runs), len(steps))
    least *= n / len(steps)
    lo, hi = runs[0][0], runs[n - 1][1]
    devices = ctx["trace"].devices
    ops = devices[sorted(devices)[0]]["ops"] if devices else []
    under = [(s, e) for name, s, e in ops
             if lo <= s and e <= hi
             and scopes.get(name, "").startswith(prefixes)]
    device_s = tracered.union_seconds(under)
    if not device_s:
        return None
    return 100.0 * least / device_s


def counter_delta(ctx, name):
    """What the loop's device counter ``name`` grew by over the traced
    window, as a flat list: the difference between the last ``loop_counters``
    span before the window's end and the last before its start (the first
    of the run where none came that early). ``None`` without two."""
    snaps = sorted((e, args[name]) for n, _, e, args in ctx["spans"]
                   if n == "loop_counters" and name in args)
    t0, t1 = ctx["window_ns"]
    before = [v for e, v in snaps if e <= t0]
    inside = [v for e, v in snaps if e <= t1]
    if not inside or (not before and len(inside) < 2):
        return None
    first = before[-1] if before else inside[0]
    last = inside[-1]

    def flat(v):
        return [x for row in v for x in flat(row)] \
            if isinstance(v, list) else [v]

    delta = [b - a for a, b in zip(flat(first), flat(last))]
    return delta if any(delta) else None
