"""Train program: device time of the K-step scan program, per step, from
the profiler trace; the program is found by its module name."""


def read(ctx):
    t0, t1 = ctx["window_ns"]
    runs = ctx["trace"].whole_runs(ctx["cfg"]["program"], t0, t1)
    if not runs:
        return None
    return sum(e - s for s, e in runs) / len(runs) / ctx["k"] / 1e6
