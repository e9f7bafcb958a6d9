"""Decode: share of slot-steps that carried a request, over the traced
window's ``decode_step`` spans."""
from benchmark.harness import spans


def read(ctx):
    t0, t1 = ctx["window_ns"]
    steps = spans.named(ctx["spans"], "decode_step", t0, t1)
    if not steps:
        return None
    used = sum(len(a.get("reqs", ())) for _, _, _, a in steps)
    return 100.0 * used / (len(steps) * int(ctx["cfg"]["serve"]["slots"]))
