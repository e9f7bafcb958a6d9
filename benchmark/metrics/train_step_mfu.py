"""Train program: the whole step's share of the chip's peak. Forward and
backward FLOPs the model requires per sample (from the configuration's
shapes, ``reference/<config>.py::flops_per_sample``) times the samples the
traced window retired, over the window, the chips and the peak."""


def read(ctx):
    t0, t1 = ctx["window_ns"]
    runs = ctx["trace"].whole_runs(ctx["cfg"]["program"], t0, t1)
    if len(runs) < 2:
        return None
    # from the first run's start to the last run's end: whole dispatches
    span_s = (runs[-1][1] - runs[0][0]) / 1e9
    samples = len(runs) * ctx["k"] * ctx["batch"]
    flops = ctx["ref"].flops_per_sample(ctx["cfg"]) * samples
    peak = ctx["run"].peaks["flops_per_s"] * ctx["chips"]
    return 100.0 * flops / span_s / peak
