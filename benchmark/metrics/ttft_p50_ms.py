"""Decode: time to first token, median: from when a request was due to the
end of the ``decode_step`` span that processed its last prompt position
(which samples its first token)."""
from benchmark.harness import spans, window


def read(ctx):
    run = ctx["run"]
    ttft = {}
    for s, e, row in spans.step_positions(ctx["spans"], ctx["records"]):
        for rec, pos in row:
            if pos == rec["prompt_len"] - 1 and rec.get("due") is not None:
                ttft[rec["index"]] = (e - run.perf_to_trace_ns(rec["due"])) \
                    / 1e6
    values = [ttft[r["index"]] for r in ctx["inside"] if r["index"] in ttft]
    return window.percentile(values, 50) if values else None
