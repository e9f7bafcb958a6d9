"""Serve entry: share of the traced window in which the loop stood EMPTY
(no slot seated, no step in flight): the ``loop_idle`` spans cut to the
window, over the window. The device's idle share less this one is what
the loop itself leaves idle while it has work."""


def read(ctx):
    idle = [(s, e) for n, s, e, _ in ctx["spans"] if n == "loop_idle"]
    if not idle:
        return None
    t0, t1 = ctx["window_ns"]
    inside = sum(max(0, min(e, t1) - max(s, t0)) for s, e in idle)
    return 100.0 * inside / (t1 - t0)
