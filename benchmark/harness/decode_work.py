"""Roofline and utilisation of the decode step, shared by the readers of
each cell's ``decode_step_roofline.*`` and ``decode_mfu.*``: the work a
step REQUIRES comes from the configuration's shapes and the step's
positions (``reference/<config>.py::step_work``), never from what a kernel
does; the time is the device time of the whole step program, found by the
program's name."""
from . import spans as _spans


def _steps_in_window(ctx):
    t0, t1 = ctx["window_ns"]
    return [(s, e, row) for s, e, row in
            _spans.step_positions(ctx["spans"], ctx["records"])
            if t0 <= s and e <= t1 and row]


def step_roofline(ctx):
    """Sum over the traced window's steps of the least time each could
    take, over the device time of the step program's runs there."""
    t0, t1 = ctx["window_ns"]
    runs = ctx["trace"].whole_runs(ctx["cfg"]["program"], t0, t1)
    steps = _steps_in_window(ctx)
    if not runs or not steps:
        return None
    peaks = ctx["run"].peaks
    least = 0.0
    for _, _, row in steps:
        flops, nbytes = ctx["ref"].step_work(
            ctx["cfg"], [pos + 1 for _, pos in row])
        least += max(flops / peaks["flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    # as many program runs as steps counted, so both sides cover the same
    # work even where a span straddles the window's edge
    n = min(len(runs), len(steps))
    device_s = sum(e - s for s, e in runs[:n]) / 1e9
    least *= n / len(steps)
    return 100.0 * least / device_s


def step_mfu(ctx):
    """FLOPs the positions processed in the traced window required, over
    the window and the chip's peak."""
    t0, t1 = ctx["window_ns"]
    steps = _steps_in_window(ctx)
    if not steps:
        return None
    flops = sum(ctx["ref"].step_work(
        ctx["cfg"], [pos + 1 for _, pos in row])[0] for _, _, row in steps)
    return 100.0 * flops / ((t1 - t0) / 1e9) / ctx["run"].peaks["flops_per_s"]
