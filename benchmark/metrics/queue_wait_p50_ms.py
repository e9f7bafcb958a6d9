"""Serve entry: from the time a request was due to the start of the first
``decode_step`` span that lists it, median over the window's requests."""
from benchmark.harness import window


def read(ctx):
    first = {}
    for n, s, e, args in ctx["spans"]:
        if n == "decode_step":
            for rid in args.get("reqs", ()):
                first.setdefault(rid, s)
    run = ctx["run"]
    waits = [(first[r["rid"]] - run.perf_to_trace_ns(r["due"])) / 1e6
             for r in ctx["inside"]
             if r.get("due") is not None and r["rid"] in first]
    return window.percentile(waits, 50) if waits else None
