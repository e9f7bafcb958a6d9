"""Decode: share of occupied slot-steps that fed a prompt position and
produced no token, over the traced window."""
from benchmark.harness import spans


def read(ctx):
    t0, t1 = ctx["window_ns"]
    prompt = total = 0
    for s, e, row in spans.step_positions(ctx["spans"], ctx["records"]):
        if not t0 <= s < t1:
            continue
        total += len(row)
        prompt += sum(1 for rec, pos in row if pos < rec["prompt_len"] - 1)
    return 100.0 * prompt / total if total else None
