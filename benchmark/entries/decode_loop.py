"""Entry ``decode_loop``: requests through ``serving.DecodeLoop.generate``,
closed loop (a fixed number of clients, each with a request always waiting)
or open loop (requests due on a seeded schedule, timed from when they were
due), as the traffic mix says.

The timed path is ``generate`` -> slot join -> the compiled step program ->
``GenerateFuture`` settling; ``correct`` compares the tokens that path
served, once the window has closed and the loop is freed, with one full
forward of the plain reference over each sampled prompt and its served
tokens.
"""
import gc
import threading
import time

import numpy as np

from benchmark.harness import runner, traffic, window

WAIT_PAST_CLOSE_S = 60.0
#: the controls: the reference in bfloat16, and in bfloat16 with the
#: operands of every weight product rounded through fp8 (name -> operand)
CONTROLS = {"bf16": None, "fp8": "float8_e4m3fn"}


class Client(object):
    """Submits requests and records, per request, when it was due, sent
    and settled, and what it produced. ``on_complete(rec)`` runs on the
    thread that settled the request."""

    def __init__(self, loop, seed, vocab):
        self.loop, self.seed, self.vocab = loop, seed, vocab
        self.records = []
        self.on_complete = None
        self._lock = threading.Lock()

    def submit(self, index, prompt_len, new_tokens, due, measured):
        rec = {"index": index, "prompt_len": prompt_len, "new": new_tokens,
               "due": due, "measured": measured, "t_done": None,
               "tokens": None, "error": None, "rid": None,
               "prompt": traffic.prompt_ids(self.seed, index, prompt_len,
                                            self.vocab)}
        with self._lock:
            self.records.append(rec)
        rec["t_submit"] = time.perf_counter()
        try:
            fut = self.loop.generate(rec["prompt"], new_tokens)
        except Exception as e:   # a dead or closed loop: a failed request
            self._settle(rec, None, e)
            return rec
        rec["rid"] = fut.rid
        fut.on_done = lambda f, rec=rec: self._settle(rec, f.value, f.error)
        if fut.done():           # settled before the callback was in place
            self._settle(rec, fut.value, fut.error)
        return rec

    def _settle(self, rec, tokens, error):
        with self._lock:
            if rec["t_done"] is not None:
                return
            rec["t_done"] = time.perf_counter()
            rec["tokens"], rec["error"] = tokens, error
        if self.on_complete is not None:
            self.on_complete(rec)


def warm_slots(client, slots):
    """Fill every slot once with a tiny request, so that the first measured
    step is not the step program's first execution."""
    recs = [client.submit(-1 - i, 4, 2, None, False) for i in range(slots)]
    deadline = time.perf_counter() + 300.0
    while any(r["t_done"] is None for r in recs):
        if time.perf_counter() > deadline:
            raise RuntimeError("warm-up requests did not finish")
        time.sleep(0.005)
    bad = [r["error"] for r in recs if r["error"] is not None]
    if bad:
        raise RuntimeError("warm-up request failed: %r" % (bad[0],))


def closed_loop(run, client, mix, tracer):
    seq = traffic.closed_sequence(mix, run.seed)
    state = {"next": 0, "done": 0, "t_open": None, "stop": False}
    opened = threading.Event()
    lead = int(mix["lead_completions"])

    def send_next():
        i = state["next"]
        state["next"] = i + 1
        p, k = seq[i % len(seq)]
        client.submit(i, p, k, None, True)

    def on_complete(rec):
        if rec["index"] < 0:
            return
        state["done"] += 1
        if state["done"] == lead:
            state["t_open"] = rec["t_done"]
            opened.set()
        if not state["stop"]:
            send_next()

    client.on_complete = on_complete
    for _ in range(int(mix["clients"])):
        send_next()
    if not opened.wait(600.0):
        raise RuntimeError("the window never opened: %d completions"
                           % state["done"])
    t_open = state["t_open"]
    tracer.window_opened()
    time.sleep(max(0.0, t_open + run.seconds / 2 - time.perf_counter()))
    tracer.window_half()
    time.sleep(max(0.0, t_open + run.seconds - time.perf_counter()))
    tracer.window_closed()
    state["stop"] = True
    done = sorted((r["t_done"], r) for r in client.records
                  if r["index"] >= 0 and r["t_done"] is not None)
    completions = [(t, len(r["tokens"] or [])) for t, r in done]
    rate, tokens, t_close, n = window.event_aligned_rate(
        completions, t_open, run.seconds)
    inside = [r for t, r in done if t_open < t <= t_close]
    return {"t_open": t_open, "t_close": t_close, "inside": inside,
            "metrics": {"decode_tok_per_s": rate},
            "counts": {"requests_completed": n, "tokens_generated": tokens}}


def open_loop(run, client, mix, tracer):
    lead, measured = traffic.open_schedule(mix, run.seconds, run.seed)
    lead_s = float(mix.get("lead_s", 0.0))
    t_open = time.perf_counter() + lead_s + 0.05
    plan = [(t, p, k, False) for t, p, k in lead] \
        + [(t, p, k, True) for t, p, k in measured]
    late, opened = [], False
    for i, (due_rel, p, k, is_measured) in enumerate(plan):
        due = t_open + due_rel
        if is_measured and not opened:
            opened = True
            time.sleep(max(0.0, t_open - time.perf_counter()))
            tracer.window_opened()
        time.sleep(max(0.0, due - time.perf_counter()))
        rec = client.submit(i, p, k, due, is_measured)
        late.append(rec["t_submit"] - due)
    t_close = t_open + run.seconds
    time.sleep(max(0.0, t_close - time.perf_counter()))
    tracer.window_closed()
    inside = [r for r in client.records if r["measured"]]
    deadline = t_close + WAIT_PAST_CLOSE_S
    while (any(r["t_done"] is None for r in inside)
           and time.perf_counter() < deadline):
        time.sleep(0.01)
    per_token = [window.ms_per_token(
        r["due"], r["t_done"] if r["error"] is None else None,
        len(r["tokens"] or [])) for r in inside]
    run.notes["generator_late_ms"] = {
        "p50": window.percentile(late, 50) * 1e3,
        "max": max(late) * 1e3}
    return {"t_open": t_open, "t_close": t_close, "inside": inside,
            "per_token_ms": per_token,
            "metrics": {"req_ms_per_token_p50":
                        window.percentile(per_token, 50)},
            "counts": {"requests_due": len(inside),
                       "requests_completed": sum(
                           1 for r in inside if r["error"] is None
                           and r["t_done"] is not None)}}


LOOPS = {"closed": closed_loop, "open": open_loop}


def host_state(loop):
    """The loop's step count and what the host has spent so far (this
    process's CPU seconds and involuntary context switches), so that a run
    whose process sits on a slow core can be told from a slow program."""
    import resource
    return {"t": time.perf_counter(), "cpu_s": time.process_time(),
            "steps": loop.health.report()["decode_steps"],
            "switched_out": resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw}


class WindowTracer(object):
    """Marks the window's two ends (``host_state``), and in a traced run
    puts its first seconds under the profiler, from a thread of its own so
    that the load generator is not held up."""

    def __init__(self, run, loop):
        self.run, self.loop, self.thread = run, loop, None
        self.at_open = self.at_half = self.at_close = None

    def window_half(self):
        self.at_half = host_state(self.loop)

    def window_closed(self):
        self.at_close = host_state(self.loop)

    def host_note(self):
        a, h, b = self.at_open, self.at_half, self.at_close
        dt, steps = b["t"] - a["t"], b["steps"] - a["steps"]
        note = {"seconds": dt, "loop_steps_per_s": steps / dt,
                "process_cpu_ms_per_step":
                    (b["cpu_s"] - a["cpu_s"]) * 1e3 / max(steps, 1),
                "switched_out_per_s": (b["switched_out"]
                                       - a["switched_out"]) / dt}
        if h is not None:   # a slow host for the whole run, or a part?
            note["loop_steps_per_s_halves"] = [
                (h["steps"] - a["steps"]) / (h["t"] - a["t"]),
                (b["steps"] - h["steps"]) / (b["t"] - h["t"])]
        return note

    def window_opened(self):
        self.at_open = host_state(self.loop)
        if not self.run.trace_on:
            return
        self.thread = threading.Thread(target=self._trace, daemon=True)
        self.thread.start()

    def _trace(self):
        self.run.start_trace()
        time.sleep(min(runner.TRACE_SECONDS, self.run.seconds))
        self.run.stop_trace()

    def join(self):
        if self.thread is not None:
            self.thread.join(300.0)
            if self.thread.is_alive():
                raise RuntimeError("the profiler did not stop")


def sample_requests(inside, seed, n):
    """A sample, drawn from the seed, of the requests the window finished,
    with the longest in it."""
    ok = [r for r in inside if r["error"] is None and r["tokens"]]
    if not ok:
        return []
    ok.sort(key=lambda r: r["index"])
    longest = max(ok, key=lambda r: (r["prompt_len"] + len(r["tokens"]),
                                     -r["index"]))
    rest = [r for r in ok if r is not longest]
    rng = np.random.default_rng([int(seed), 4])
    picks = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[int(i)] for i in sorted(picks)]


def gap_readings(gaps):
    """The widest gap, the mean gap and the mean squared gap over all the
    tokens compared. A token that is the reference's best has gap 0; noise
    of size s in the logits flips a share ~s of the tokens by ~s each, so
    the mean grows as s^2 and the mean square as s^3: they separate a lower
    precision from the stated one more widely than the widest gap does."""
    if not gaps:
        return {"gap_max": float("nan"), "gap_mean": float("nan"),
                "gap_sq_mean": float("nan")}
    g = np.asarray(gaps, np.float64)
    return {"gap_max": float(g.max()), "gap_mean": float(g.mean()),
            "gap_sq_mean": float(np.mean(g * g))}


def check_tokens(run, ref, cfg, params, sample, pad_to, control):
    """The gaps by which each served token's logit lies below the
    reference's best, over every served token of the sample, reduced by
    ``gap_readings``. With ``control`` the same, at each position of the
    same prompts and tokens, for the token a lower-precision forward puts
    first. ``control`` naming one of ``CONTROLS`` puts ITS readings in the
    program's place (the program's are noted); any other true value notes
    every control's readings beside the program's."""
    import jax
    import jax.numpy as jnp
    vocab = int(cfg["vocab_size"])
    dev = {k: jnp.asarray(v) for k, v in params.items()}

    def gaps(p, toks, served):
        logits = ref.forward(p, toks, cfg, "float32")
        best = jnp.max(logits, axis=-1)
        out = [best - jnp.take_along_axis(
            logits, served[:, None], axis=-1)[:, 0]]
        for operand in CONTROLS.values() if control else ():
            low = jnp.argmax(ref.forward(p, toks, cfg, "bfloat16",
                                         operand=operand), axis=-1)
            out.append(best - jnp.take_along_axis(
                logits, low[:, None], axis=-1)[:, 0])
        return out

    gaps = jax.jit(gaps)
    served_gaps, bad = [], 0
    control_gaps = {name: [] for name in CONTROLS}
    for rec in sample:
        toks = rec["tokens"]
        if (len(toks) != rec["new"]
                or any(not 0 <= int(t) < vocab for t in toks)):
            bad += 1
            continue
        seq = (rec["prompt"] + [int(t) for t in toks])[:-1]
        ids = np.zeros(pad_to, np.int32)
        ids[:len(seq)] = seq
        # position plen-1+j predicts served token j
        served = np.zeros(pad_to, np.int32)
        lo = rec["prompt_len"] - 1
        served[lo:lo + len(toks)] = toks
        out = [np.asarray(g) for g in gaps(dev, jnp.asarray(ids),
                                           jnp.asarray(served))]
        served_gaps.extend(out[0][lo:lo + len(toks)].tolist())
        for name, g in zip(CONTROLS, out[1:]):
            control_gaps[name].extend(g[lo:lo + len(toks)].tolist())
    del dev
    readings = dict(gap_readings(served_gaps), bad_requests=bad)
    run.notes["tokens_compared"] = len(served_gaps)
    for name, g in control_gaps.items():
        if g and name == control:
            run.notes["program"] = readings
            readings = dict(gap_readings(g), bad_requests=bad)
        elif g:
            run.notes["control." + name] = gap_readings(g)
    return readings


def serve_window(run, ref):
    """Set-up and window: build the loop over the seed's weights, warm every
    slot, drive the mix. Returns the window's record, with the loop closed
    and freed, and the host copy of the weights for the reference."""
    from mxnet_tpu import tracecheck
    cell, cfg, mix = run.cell, run.cell.config, run.cell.traffic
    params = ref.make_params(cfg, run.seed)
    t_weights = time.perf_counter()
    loop = cell.builder().build(cfg, params)
    t_loop = time.perf_counter()
    client = Client(loop, run.seed, int(cfg["vocab_size"]))
    tracer = WindowTracer(run, loop)
    try:
        warm_slots(client, int(cfg["serve"]["slots"]))
        t_warm = time.perf_counter()
        if run.trace_on:
            run.arm_spans()
        retraces0 = tracecheck.retrace_count()
        res = LOOPS[mix["loop"]](run, client, mix, tracer)
        tracer.join()
        health = loop.health.report()
        run.notes["host"] = tracer.host_note()
    finally:
        client.on_complete = None
        loop.close()
    run.notes["setup_phases_s"] = {
        "import_and_weights": t_weights - run.t_process,
        "build_loop": t_loop - t_weights, "warm_slots": t_warm - t_loop,
        "lead_in": res["t_open"] - t_warm}
    n_comp, comp_s = run.meter.between(res["t_open"], res["t_close"])
    retraced = tracecheck.retrace_count() - retraces0
    run.notes["window_compiles"] = {"programs": n_comp, "seconds": comp_s,
                                    "retraces": retraced}
    run.notes["health"] = {k: health.get(k) for k in
                           ("decode_steps", "joined", "retired", "errors",
                            "shed")}
    if run.peaks is not None:    # rates: never in a CPU rehearsal's line
        for name in ("loop_steps_per_s", "process_cpu_ms_per_step"):
            res["counts"][name] = run.notes["host"][name]
    res.update(compiles=n_comp + retraced, records=client.records,
               params=params, memory_peak=run.memory_peak_bytes(),
               spans=run.spans() if run.trace_on else [])
    client.loop = None          # the loop goes with this frame: state freed
    return res


def run(run, out=None, err=None):
    cell, cfg = run.cell, run.cell.config
    ref = cell.reference()
    res = serve_window(run, ref)
    gc.collect()
    inside = res["inside"]
    failed = res["compiles"] + sum(
        1 for r in inside if r["error"] is not None or r["t_done"] is None)
    check = cfg["check"]
    pad_to = int(check["pad_to"].get(cell.traffic_name,
                                     check["pad_to"]["default"]))
    sample = sample_requests(inside, run.seed, int(check["requests"]))
    readings = check_tokens(run, ref, cfg, res["params"], sample, pad_to,
                            run.control)
    readings["requests_failed"] = failed
    correct, compared = runner.compare(readings, cell.limits())

    end_to_end = dict(res["metrics"], setup_s=res["t_open"] - run.t_process)
    ctx = {"cfg": cfg, "ref": ref, "records": res["records"],
           "inside": inside, "spans": res["spans"], "result": res,
           "setup_compile_s": run.meter.between(0.0, res["t_open"])[1]}
    return runner.finish(run, correct and bool(sample),
                         len(inside) + res["compiles"], failed, end_to_end,
                         res["memory_peak"], compared, ctx, res["counts"],
                         out=out, err=err)
