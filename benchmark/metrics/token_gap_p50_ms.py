"""Decode: gap between consecutive generated tokens of one request, median
over all such gaps: between the ends of the ``decode_step`` spans that
produced them."""
from benchmark.harness import spans, window


def read(ctx):
    last, gaps = {}, []
    inside = {r["index"] for r in ctx["inside"]}
    for s, e, row in spans.step_positions(ctx["spans"], ctx["records"]):
        for rec, pos in row:
            if pos < rec["prompt_len"] - 1 or rec["index"] not in inside:
                continue
            if rec["index"] in last:
                gaps.append((e - last[rec["index"]]) / 1e6)
            last[rec["index"]] = e
    return window.percentile(gaps, 50) if gaps else None
