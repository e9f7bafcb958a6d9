"""Train entry: device idle per dispatch during which the host was inside a
``dispatch`` or ``readback_stall`` span, from the trace's idle gaps and the
program's spans on one clock."""


def read(ctx):
    t0, t1 = ctx["window_ns"]
    spans = [(n, s, e) for n, s, e, _ in ctx["spans"]
             if n in ("dispatch", "readback_stall")]
    n_dispatch = sum(1 for n, s, e in spans
                     if n == "dispatch" and t0 <= s < t1)
    if not n_dispatch:
        return None
    idle = sum(sec for name, sec in
               ctx["trace"].attribute_gaps(spans, t0, t1) if name != "none")
    return idle * 1e3 / n_dispatch
