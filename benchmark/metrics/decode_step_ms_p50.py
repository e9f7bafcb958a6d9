"""Decode: duration of one ``decode_step`` span (dispatch, device step and
the token readback), median over the traced window."""
from benchmark.harness import spans, window


def read(ctx):
    t0, t1 = ctx["window_ns"]
    durs = [(e - s) / 1e6 for _, s, e, _ in
            spans.named(ctx["spans"], "decode_step", t0, t1)]
    return window.percentile(durs, 50) if durs else None
