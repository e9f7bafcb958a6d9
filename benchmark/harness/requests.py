"""What ``DecodeLoop`` records about each request, for the readers of
``first_token_p50_ms`` and ``token_gap_p99_ms``.

Where the loop ends a request it emits ONE ``decode_request`` record, an
async pair keyed by the request's id (``rid``: the id every ``decode_step``
span's ``reqs`` and ``prefill`` carry, and the generator's records' ``rid``).
Its arguments are the loop's own stamps: ``submit``, the submission on
``time.perf_counter()`` in seconds (the clock of the generator's ``due`` and
of ``Run.perf_to_trace_ns``), and microseconds from it: ``seat_us`` (seated
in a slot), ``token_us`` (one a token, when the host had it; ``[0]`` is the
time to first token); besides ``slot``, ``prompt_len``, ``emitted``,
``outcome``, ``steps``, ``prefill``, ``prefix_hit``. An async pair is no
complete span, so ``Run.spans()`` does not carry it: it is read from the
tracer's buffer. A program that emits no such record (any commit before it
was added) gives every reader here nothing to read: ``None``.
"""
NAME = "decode_request"


def by_rid(ctx):
    """``{rid: the record's arguments}`` of the run, read once a ``ctx``."""
    if "_requests" not in ctx:
        from mxnet_tpu.obs import trace as obs
        ctx["_requests"] = {
            e["id"]: e["args"] for e in obs.events()
            if e.get("name") == NAME and e.get("ph") == "b"}
    return ctx["_requests"]


def inside(ctx):
    """``[(generator's record, loop's record)]`` of the window's requests
    that the loop recorded with at least one token."""
    recs = by_rid(ctx)
    return [(r, recs[r["rid"]]) for r in ctx["inside"]
            if r.get("rid") in recs and recs[r["rid"]]["token_us"]]


def token_gaps_ms(ctx):
    """Every gap between consecutive tokens of one request, over the
    window's requests, in ms."""
    return [(b - a) / 1e3 for _, rec in inside(ctx)
            for a, b in zip(rec["token_us"], rec["token_us"][1:])]


def prefill_runs(ctx):
    """``[(start_ns, end_ns)]`` of the runs, inside the traced window, of
    the module the loop's ``loop_program`` span names for its prefill
    pass; ``None`` where it names none."""
    for name, _, _, args in ctx["spans"]:
        if name == "loop_program" and args.get("prefill_program"):
            t0, t1 = ctx["window_ns"]
            return ctx["trace"].whole_runs(args["prefill_program"], t0, t1)
    return None
