"""Helpers the per-layer readers share for the program's host spans:
``(name, start_ns, end_ns, args)`` tuples on the trace's clock."""


def named(spans, name, t0=None, t1=None):
    """Spans of one name that START inside ``[t0, t1)``."""
    return [s for s in spans if s[0] == name
            and (t0 is None or s[1] >= t0) and (t1 is None or s[1] < t1)]


def step_positions(spans, records):
    """For every ``decode_step`` span, in order, the requests it carried
    and the cache position each was at: a request's n-th step processes
    its n-th position (prompt first, then generated tokens). Returns
    ``[(start_ns, end_ns, [(record, position), ...])]``; requests the
    generator did not send (none today) are left out."""
    by_rid = {r["rid"]: r for r in records if r.get("rid") is not None}
    seen = {}
    out = []
    for name, s, e, args in sorted(named(spans, "decode_step"),
                                   key=lambda x: x[1]):
        row = []
        for rid in args.get("reqs", ()):
            pos = seen.get(rid, 0)
            seen[rid] = pos + 1
            if rid in by_rid:
                row.append((by_rid[rid], pos))
        out.append((s, e, row))
    return out
