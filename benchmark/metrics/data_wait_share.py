"""Input feed: share of the traced window the training loop spent waiting
for the next superbatch (``data_wait`` spans)."""


def read(ctx):
    t0, t1 = ctx["window_ns"]
    waits = [(max(s, t0), min(e, t1)) for n, s, e, _ in ctx["spans"]
             if n == "data_wait" and e > t0 and s < t1]
    if not any(n == "dispatch" for n, _, _, _ in ctx["spans"]):
        return None
    return 100.0 * sum(e - s for s, e in waits) / (t1 - t0)
