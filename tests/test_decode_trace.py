"""``DecodeLoop`` tells its own step (docs/observability.md "Span
catalogue", docs/serving.md "Reading a request's token times"): the six
leaves of the step's host round trip (five laps of ``decode_step`` and
``decode_admit``), what each ``decode_step`` span
says it processed (``pos``/``n``/``emit``/``sampled``/``ahead``/``cpu_us``), the counters an
operator reads with tracing off (``tokens_emitted``,
``prompt_positions``, ``sampled_steps``, ``steps_ahead``), ``GenerateFuture.token_times``, the profiler's
clock sync, and the stable scope names inside the compiled programs.

The loop runs one step ahead of its readback (PR 33): of the five laps of
span n, ``decode_gather``, ``decode_h2d`` and ``decode_dispatch`` belong to
step n, the step the span's arguments describe, and ``decode_readback`` and
``decode_commit`` to step n-1, whose tokens are handed over there (of
length 0 where no step was in flight). The last step of a busy stretch is
read back in a ``loop_drain`` span, outside any ``decode_step``.
"""
import json
import os
import re
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import models, obs, serving  # noqa: E402
from mxnet_tpu.obs import flight as obs_flight  # noqa: E402
from mxnet_tpu.obs import trace as obs_trace  # noqa: E402
from mxnet_tpu.serving import decode as decode_mod  # noqa: E402
from mxnet_tpu.serving.health import (  # noqa: E402
    SERVING_HEALTH, ServingHealth)

from benchmark.harness import spans as bench_spans  # noqa: E402
from benchmark.harness import stepgaps  # noqa: E402

_LM = dict(vocab_size=17, embed=16, num_heads=2, num_layers=2, seq_len=16)
INNER = ("decode_gather", "decode_h2d", "decode_dispatch",
         "decode_readback", "decode_commit")


def _lm_params(seed=3, num_layers=None, seq_len=None):
    cfg = dict(_LM, num_layers=num_layers or _LM["num_layers"],
               seq_len=seq_len or _LM["seq_len"])
    sym = models.transformer(**cfg)
    s = cfg["seq_len"]
    arg_shapes, _, _ = sym.infer_shape(data=(1, s), softmax_label=(1, s))
    rs = np.random.RandomState(seed)
    return {n: (rs.randn(*shp) * 0.3).astype(np.float32)
            for n, shp in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}


def _loop(params=None, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("prefix_cache", False)
    return serving.DecodeLoop(params if params is not None else _lm_params(),
                              num_layers=_LM["num_layers"],
                              num_heads=_LM["num_heads"],
                              max_len=_LM["seq_len"], **kw)


@pytest.fixture(autouse=True)
def _clean_tracer():
    obs_trace.stop()
    obs_trace.clear()
    yield
    obs_trace.stop()
    obs_trace.clear()


def _serve(loop, requests, **kw):
    """Run ``(prompt, new)`` pairs (or ``(prompt, new, its own arguments)``)
    to their end with tracing armed; returns ``(futures, complete events,
    growth of the process-wide counters)``."""
    before = SERVING_HEALTH.report()
    obs_trace.start()
    try:
        futs = [loop.generate(p, n, **dict(kw, **(own[0] if own else {})))
                for p, n, *own in requests]
        for f in futs:
            f.result(timeout=120.0)
    finally:
        loop.close()        # joins the loop thread: its last step is in
        obs_trace.stop()
    after = SERVING_HEALTH.report()
    grown = {k: after[k] - before[k]
             for k in ("decode_steps", "tokens_emitted", "prompt_positions",
                       "sampled_steps", "steps_ahead")}
    # the laps written out as child spans, as the trace file has them
    evs = obs_trace.expand_laps(obs_trace.events())
    return futs, [e for e in evs if e["ph"] == "X"], grown


def _steps(evs):
    return sorted((e for e in evs if e["name"] == "decode_step"),
                  key=lambda e: e["args"]["step"])


PLAIN = [([1, 2, 3], 4), ([4, 5], 3), ([6], 5), ([7, 8, 9, 10], 2)]


@pytest.fixture(scope="module")
def plain_run():
    """Four requests over two slots, so that requests join mid-stream."""
    obs_trace.clear()
    loop = _loop()
    futs, evs, grown = _serve(loop, PLAIN)
    return loop, futs, evs, grown, obs_trace.events()


def _spans_of(evs):
    """What the benchmark's ``run.spans()`` makes of the buffer: complete
    events as ``(name, start_ns, end_ns, args)``."""
    return [(e["name"], e["ts"] * 1000, (e["ts"] + e["dur"]) * 1000,
             e["args"]) for e in evs if e["ph"] == "X"]


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------

def test_span_set_adds_arguments_known_at_the_end():
    obs_trace.start()
    with obs_trace.span("region", step=3) as sp:
        sp.set(n=[1, 2], emit=[1, 0])
        sp.set(cpu_us=7)
    (ev,) = [e for e in obs_trace.events() if e["ph"] == "X"]
    assert ev["args"] == {"step": 3, "n": [1, 2], "emit": [1, 0],
                          "cpu_us": 7}


def test_span_set_on_the_noop_span_is_a_noop():
    was = obs_flight.enabled()
    obs_flight.set_enabled(False)
    try:
        assert not obs_trace.active()
        with obs_trace.span("region", step=3) as sp:
            assert sp is obs_trace._NOOP
            assert sp.set(n=[1]) is None
        assert not hasattr(sp, "args") and obs_trace.events() == []
    finally:
        obs_flight.set_enabled(was)
    # the default process: the recorder keeps spans live with tracing off
    assert obs_trace.active() == was


def test_laps_are_contiguous_phases_kept_inside_their_span():
    obs_trace.start()
    with obs_trace.span("outer", step=4, reqs=[1, 2]) as sp:
        sp.lap("first")
        sp.lap("second", **{"pass": "draft"})
        sp.set(n=[1, 1])
    (ev,) = obs_trace.events()[-1:]
    (a, a_off, a_dur), (b, b_off, b_dur, b_args) = ev["args"]["laps"]
    assert (a, a_off, b, b_off) == ("first", 0, "second", a_dur)
    assert b_off + b_dur <= ev["dur"] and b_args == {"pass": "draft"}
    # ONE event in the buffer; the trace file gets the children, on the
    # parent's thread, with its scalar args and the lap's own
    out = obs_trace.expand_laps([ev])
    assert [e["name"] for e in out] == ["outer", "first", "second"]
    assert out[1]["ts"] == ev["ts"] and out[2]["ts"] == ev["ts"] + a_dur
    assert out[1]["args"] == {"step": 4}
    assert out[2]["args"] == {"step": 4, "pass": "draft"}
    assert all(e["tid"] == ev["tid"] for e in out)
    assert obs_trace.nest_check(out) == []
    # and a lap on the no-op span is a no-op
    assert obs_trace.NOOP.lap("x", k=1) is None


def test_saved_trace_file_holds_the_laps_as_child_spans(tmp_path):
    obs_trace.start()
    with obs_trace.span("outer", step=1) as sp:
        sp.lap("phase")
    path = obs_trace.save(str(tmp_path / "t.json"))
    with open(path) as f:
        names = [e["name"] for e in json.load(f)["traceEvents"]
                 if e.get("ph") == "X"]
    assert names == ["outer", "phase"]
    assert [e["name"] for e in obs_trace.events()
            if e["ph"] == "X"] == ["outer"]


def test_nested_spans_nest_in_whole_microseconds():
    """Both ends of a span are rounded on one grid: a child that ends
    less than a microsecond before its parent never sticks out of it."""
    obs_trace.start()
    for _ in range(300):
        with obs_trace.span("outer"):
            with obs_trace.span("inner"):
                pass
    assert obs_trace.nest_check(obs_trace.events()) == []


# ---------------------------------------------------------------------------
# leaves
# ---------------------------------------------------------------------------

def test_every_step_holds_its_five_leaves_in_order(plain_run):
    """Gather, h2d and dispatch of the span's own step, then readback and
    commit of the step before it: five laps, in that order, in EVERY span
    (the two last empty where no step was in flight)."""
    _, _, evs, _, raw = plain_run
    assert obs_trace.nest_check(evs) == []
    steps = _steps(evs)
    assert len(steps) >= 8
    # one event a step in the buffer, however many phases it has: what
    # the benchmark's readers walk does not grow with the leaves
    assert sum(1 for e in raw if e["ph"] == "X"
               and e["name"].startswith("decode_")) \
        == len(steps) + sum(1 for e in evs if e["name"] == "decode_admit")
    for st in steps:
        lo, hi = st["ts"], st["ts"] + st["dur"]
        inner = sorted((e for e in evs if e["name"] in INNER
                        and e["args"]["step"] == st["args"]["step"]),
                       key=lambda e: e["ts"])
        assert [e["name"] for e in inner] == list(INNER)
        assert all(e["tid"] == st["tid"] for e in inner)
        # contiguous from the step's start, and inside it
        assert inner[0]["ts"] == lo
        assert inner[-1]["ts"] + inner[-1]["dur"] <= hi
        for a, b in zip(inner, inner[1:]):
            assert a["ts"] + a["dur"] == b["ts"]
    # the leaves cover the step but for what follows the commit (the
    # step's arguments, the CPU clock); judged over the run, since a
    # loaded host can stall any one step there
    covered = sum(e["dur"] for e in evs if e["name"] in INNER) \
        / sum(st["dur"] for st in steps)
    assert covered >= 0.9


def test_admit_is_the_leaf_outside_the_step(plain_run):
    _, _, evs, _, _ = plain_run
    admits = [e for e in evs if e["name"] == "decode_admit"]
    # written only where it seated someone
    assert all(e["args"]["joined"] > 0 for e in admits)
    assert sum(e["args"]["joined"] for e in admits) == len(PLAIN)
    by_step = {st["args"]["step"]: st for st in _steps(evs)}
    for e in admits:
        # it names the step it precedes, and ends before that step begins
        nxt = by_step.get(e["args"]["step"])
        if nxt is not None:
            assert e["ts"] + e["dur"] <= nxt["ts"]


# ---------------------------------------------------------------------------
# what a step says it did, and the counters
# ---------------------------------------------------------------------------

def test_emitted_tokens_agree_across_spans_counters_and_futures(plain_run):
    """``pos``/``n``/``emit``/``sampled`` describe the step DISPATCHED in
    the span; ``tokens_emitted`` counts a step's tokens when they are read
    back, a span later. Over a finished run the sums agree."""
    loop, futs, evs, grown, _ = plain_run
    steps = _steps(evs)
    for st in steps:
        a = st["args"]
        assert len(a["reqs"]) == len(a["pos"]) == len(a["n"]) \
            == len(a["emit"]) > 0
    # the loop thread's CPU over each loop cycle, from the end of the step
    # before: the first traced step has none
    assert "cpu_us" not in steps[0]["args"]
    assert all(st["args"]["cpu_us"] >= 0 for st in steps[1:])
    returned = sum(len(f.tokens) for f in futs)
    assert returned == sum(n for _, n in PLAIN)
    emitted = sum(sum(st["args"]["emit"]) for st in steps)
    positions = sum(sum(st["args"]["n"]) for st in steps)
    h = loop.health.report()
    assert emitted == h["tokens_emitted"] == returned
    assert positions - emitted == h["prompt_positions"] \
        == sum(len(p) - 1 for p, _ in PLAIN)
    assert h["decode_steps"] == len(steps)
    # mirrored into the process-wide aggregate
    # every request is greedy: no step took the sampler's branch
    assert [st["args"]["sampled"] for st in steps] == [0] * len(steps)
    assert h["sampled_steps"] == 0
    assert grown == {"decode_steps": len(steps), "tokens_emitted": returned,
                     "prompt_positions": positions - emitted,
                     "sampled_steps": 0,
                     "steps_ahead": h["steps_ahead"]}


def test_ahead_marks_the_steps_dispatched_before_the_last_was_read(plain_run):
    """``ahead`` is 1 where the step before was still unread at dispatch:
    every step but a start from an empty loop. ``steps_ahead`` counts
    them; a span that ran ahead holds step n-1's readback, one that did
    not holds none, and the busy stretch's last step is read in a
    ``loop_drain`` span that no reader takes for a step."""
    loop, _, evs, _, _ = plain_run
    steps = _steps(evs)
    ahead = [st["args"]["ahead"] for st in steps]
    assert ahead[0] == 0 and set(ahead) == {0, 1}
    h = loop.health.report()
    assert h["steps_ahead"] == sum(ahead) and h["trash_slot_steps"] == 0
    drains = [e for e in evs if e["name"] == "loop_drain"]
    assert 1 <= len(drains) and ahead.count(0) <= len(drains)
    assert all("reqs" not in e["args"] for e in drains)
    assert "loop_drain" not in stepgaps.LEAVES
    # the last drain follows the last step: nothing is left in flight
    assert max(e["ts"] for e in drains) >= steps[-1]["ts"] + steps[-1]["dur"]
    assert loop._inflight is None


@pytest.mark.parametrize("spec_k", [0, 2])
def test_sampled_steps_counts_the_steps_a_sampled_request_was_seated(spec_k):
    """One ``temperature=0.8`` request among greedy ones, more requests
    than slots: ``sampled_steps`` is the number of ``decode_step`` spans
    whose ``sampled`` > 0, which are exactly the steps (rounds, in a
    speculative loop) that list the request, prompt positions included."""
    params = _lm_params()
    kw = dict(spec_k=2, draft_params=params,
              draft_num_layers=_LM["num_layers"]) if spec_k else {}
    loop = _loop(params, **kw)
    futs, evs, grown = _serve(loop, [
        ([1, 2, 3], 4), ([4, 5], 3),
        ([6, 7, 8], 5, dict(temperature=0.8, seed=11)), ([9], 2)])
    steps = _steps(evs)
    seated = [st for st in steps if futs[2].rid in st["args"]["reqs"]]
    sampling = [st for st in steps if st["args"]["sampled"] > 0]
    assert 0 < len(seated) < len(steps)
    assert sampling == seated
    assert {st["args"]["sampled"] for st in sampling} == {1}
    assert loop.health.sampled_steps == grown["sampled_steps"] == len(seated)


@pytest.mark.parametrize("spec_k", [0, 2])
def test_rows_read_agree_across_spans_counters_and_positions(spec_k):
    """``rows`` of a ``decode_step`` span is the prefix of the cache's rows
    that the dispatched step's attention covered: the rung of the ladder
    above the deepest position it was fed (pass j of a speculative window
    stands j deeper; the sum over the passes). ``cache_rows_read`` is the
    spans' sum, ``cache_rows_allocated`` what the steps would have read
    of a cache attended whole. A prompt of 200 carries one request over
    the first rung's edge (208 rows) while the others stay shallow."""
    params = _lm_params(seq_len=400)
    kw = dict(spec_k=2, draft_params=params,
              draft_num_layers=_LM["num_layers"]) if spec_k else {}
    loop = serving.DecodeLoop(params, num_layers=_LM["num_layers"],
                              num_heads=_LM["num_heads"], max_len=400,
                              slots=2, prefix_cache=False, **kw)
    ladder = serving.blocks.rows_ladder(loop._rows)
    assert ladder == (208, 408 if spec_k else 400) == loop._ladder
    long_prompt = [1 + i % 16 for i in range(200)]
    futs, evs, _ = _serve(loop, [(long_prompt, 12), ([4, 5], 3), ([6], 5)])
    steps = _steps(evs)
    passes = spec_k + 1
    for st in steps:
        top = max(st["args"]["pos"])
        assert st["args"]["rows"] == sum(
            serving.blocks.rows_covered(ladder, top + j)
            for j in range(passes))
    rows = [st["args"]["rows"] for st in steps]
    assert min(rows) == passes * ladder[0] and max(rows) == passes * ladder[1]
    h = loop.health.report()
    assert h["cache_rows_read"] == sum(rows)
    assert h["cache_rows_allocated"] == len(steps) * passes * loop._rows
    assert 0 < h["cache_rows_read"] < h["cache_rows_allocated"]
    assert [len(f.tokens) for f in futs] == [12, 3, 5]


def test_the_benchmarks_reader_counts_what_the_counter_counted(plain_run):
    """``emitted_tok_per_s`` times the traced seconds is the growth of
    ``SERVING_HEALTH.tokens_emitted`` over the same steps."""
    _, _, _, grown, raw = plain_run
    spans = _spans_of(raw)
    t0 = min(s for _, s, _, _ in spans)
    t1 = max(e for _, _, e, _ in spans) + 1
    ctx = {"spans": spans, "window_ns": (t0, t1)}
    rate = stepgaps.emitted_per_s(ctx)
    assert rate * (t1 - t0) / 1e9 == pytest.approx(grown["tokens_emitted"])
    assert stepgaps.prompt_position_share(ctx) == pytest.approx(
        100.0 * grown["prompt_positions"]
        / (grown["prompt_positions"] + grown["tokens_emitted"]))
    assert stepgaps.loop_cpu_ms(ctx) >= 0
    # and its leaves are the laps: five a step, and the admits
    names = [n for n, _, _, _ in stepgaps.leaves(spans)]
    steps = sum(1 for n, _, _, _ in spans if n == "decode_step")
    assert names.count("decode_dispatch") == names.count("decode_commit") \
        == steps and set(names) == set(stepgaps.LEAVES)


def test_plain_traffic_agrees_with_the_inference_from_outside(plain_run):
    """One position a step and no prefix hit: a request's n-th step is at
    its n-th position, which is what ``spans.step_positions`` assumes."""
    _, futs, evs, _, _ = plain_run
    spans = [(e["name"], e["ts"], e["ts"] + e["dur"], e["args"])
             for e in evs]
    records = [{"rid": f.rid, "index": i} for i, f in enumerate(futs)]
    inferred = bench_spans.step_positions(spans, records)
    steps = _steps(evs)
    assert len(inferred) == len(steps)
    for (_, _, row), st in zip(inferred, steps):
        a = st["args"]
        assert [rec["rid"] for rec, _ in row] == a["reqs"]
        assert [pos for _, pos in row] == a["pos"]
        assert set(a["n"]) == {1}


def test_token_times_stamp_every_emitted_token(plain_run):
    """Stamped at the readback, when the host really has the token: one
    clock read per step read back, so as many stamps as emitting steps."""
    _, futs, evs, _, _ = plain_run
    for f in futs:
        assert len(f.token_times) == len(f.tokens)
        assert f.token_times == sorted(f.token_times)
    # one clock read a step: tokens of one step share their time
    stamps = {t for f in futs for t in f.token_times}
    emitting = [st for st in _steps(evs) if sum(st["args"]["emit"])]
    assert len(stamps) == len(emitting)


def test_pos_starts_at_the_prefix_length_on_a_prefix_cache_hit():
    loop = _loop(prefix_cache=True, slots=1)
    prefix = [1, 2, 3, 4, 5]
    futs, evs, _ = _serve(loop, [(prefix + [6, 7], 2), (prefix + [8], 3)],
                          prefix_len=len(prefix))
    assert loop.health.prefix_hits == 1
    first = {}
    for st in _steps(evs):
        for rid, pos in zip(st["args"]["reqs"], st["args"]["pos"]):
            first.setdefault(rid, pos)
    assert first[futs[0].rid] == 0
    assert first[futs[1].rid] == len(prefix)
    # the hit skipped the prefix: only the one position after it fed
    steps2 = [st for st in _steps(evs) if futs[1].rid in st["args"]["reqs"]]
    assert sum(sum(st["args"]["n"]) for st in steps2) == 1 + 3 - 1
    assert loop.health.prompt_positions == (len(prefix) + 2 - 1) + 0


def test_a_speculative_round_commits_several_positions():
    params = _lm_params()
    loop = _loop(params, spec_k=2, draft_params=params,
                 draft_num_layers=_LM["num_layers"])
    requests = [([1, 2, 3], 6), ([4], 5)]
    futs, evs, grown = _serve(loop, requests)
    assert obs_trace.nest_check(evs) == []
    steps = _steps(evs)
    ns = [n for st in steps for n in st["args"]["n"]]
    assert max(ns) > 1 and max(ns) <= 3
    emitted = sum(sum(st["args"]["emit"]) for st in steps)
    assert emitted == sum(len(f.tokens) for f in futs) \
        == loop.health.tokens_emitted == grown["tokens_emitted"]
    assert sum(ns) - emitted == loop.health.prompt_positions \
        == sum(len(p) - 1 for p, _ in requests)
    # pos of a request's next round is where the last one left it
    at = {}
    for st in steps:
        a = st["args"]
        for rid, pos, n in zip(a["reqs"], a["pos"], a["n"]):
            assert at.get(rid, 0) == pos
            at[rid] = pos + n
    # the same leaf names: a dispatch and a readback per draft pass that is
    # read, and one of each for the verify pass
    st = steps[0]["args"]["step"]
    mine = [e for e in evs if e["args"].get("step") == st]
    kinds = [(e["name"], e["args"].get("pass")) for e in
             sorted(mine, key=lambda e: e["ts"])
             if e["name"] in ("decode_dispatch", "decode_readback")]
    assert kinds == [("decode_dispatch", "draft"),
                     ("decode_readback", "draft")] * 2 \
        + [("decode_dispatch", "draft"), ("decode_dispatch", "verify"),
           ("decode_readback", "verify")]
    for f in futs:
        assert len(f.token_times) == len(f.tokens)


def test_with_tracing_and_recorder_off_the_step_builds_nothing(monkeypatch):
    """No span is live: the ``decode_step`` site asks first and builds no
    ``reqs`` list and no span (its phases lap the shared no-op); the
    counters still count."""
    opened = []
    real = decode_mod._obs.span

    def spy(name, **args):
        opened.append((name, args))
        return real(name, **args)

    was = obs_flight.enabled()
    obs_flight.set_enabled(False)
    monkeypatch.setattr(decode_mod._obs, "span", spy)
    loop = _loop()
    try:
        toks = loop.generate([1, 2, 3], 4).result(timeout=120.0)
    finally:
        loop.close()
        obs_flight.set_enabled(was)
    assert opened == []
    assert obs_trace.events() == []
    assert loop.health.tokens_emitted == len(toks) == 4
    assert loop.health.prompt_positions == 2


def test_the_recorder_alone_keeps_the_spans_live():
    """The default process: tracing off, flight recorder on. The step's
    spans land in its ring (and cost what a live span costs)."""
    if not obs_flight.enabled():
        pytest.skip("flight recorder disabled in this environment")
    obs_flight.FLIGHT.clear()
    loop = _loop()
    try:
        loop.generate([1, 2], 3).result(timeout=120.0)
    finally:
        loop.close()
    assert obs_trace.events() == []
    with obs_flight.FLIGHT._lock:
        ring = [e for e in obs_flight.FLIGHT._spans
                if e["name"] == "decode_step"]
    assert ring and all(
        [lap[0] for lap in e["args"]["laps"]] == list(INNER) for e in ring)
    # the CPU clock is read for the trace file only: it is a system call
    assert not any("cpu_us" in e["args"] for e in ring)


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

def test_record_decode_step_moves_four_counts_and_mirrors_them():
    parent = ServingHealth()
    h = ServingHealth(parent=parent)
    h.record_decode_step(3, 5)
    h.record_decode_step()
    h.record_decode_step(2, 0, sampled=7)    # seven rows, ONE sampled step
    h.record_decode_step(rows=96, allocated=768)
    for x in (h, parent):
        r = x.report()
        assert (r["decode_steps"], r["tokens_emitted"],
                r["prompt_positions"], r["sampled_steps"]) == (4, 5, 5, 1)
        assert (r["cache_rows_read"], r["cache_rows_allocated"]) == (96, 768)
    h.reset()
    assert h.tokens_emitted == h.prompt_positions == h.decode_steps \
        == h.sampled_steps == h.cache_rows_read == h.cache_rows_allocated == 0
    assert parent.tokens_emitted == 5 and parent.sampled_steps == 1


def test_new_counters_reach_the_registry_and_prometheus():
    snap = obs.REGISTRY.snapshot()
    assert "serving_health.tokens_emitted" in snap
    assert "serving_health.prompt_positions" in snap
    assert "serving_health.sampled_steps" in snap
    assert "serving_health.steps_ahead" in snap
    assert "serving_health.trash_slot_steps" in snap
    assert "serving_health.cache_rows_read" in snap
    assert "serving_health.cache_rows_allocated" in snap
    prom = obs.REGISTRY.to_prometheus()
    assert "serving_health_tokens_emitted" in prom
    assert "serving_health_prompt_positions" in prom
    assert "serving_health_sampled_steps" in prom
    assert "serving_health_steps_ahead" in prom
    assert "serving_health_trash_slot_steps" in prom


# ---------------------------------------------------------------------------
# the operator's side: clock sync and scope names
# ---------------------------------------------------------------------------

def test_profiler_run_writes_one_clock_sync_on_both_timelines(monkeypatch):
    from mxnet_tpu import profiler
    annotated = []

    class _Annotation(object):
        def __init__(self, name):
            annotated.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

    monkeypatch.setattr(profiler.jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(profiler.jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(profiler.jax.profiler, "TraceAnnotation",
                        _Annotation)
    obs_trace.start()
    t0 = time.perf_counter_ns()
    profiler.profiler_set_state("run")
    t1 = time.perf_counter_ns()
    try:
        profiler.profiler_set_state("run")      # already running: no second
    finally:
        profiler.profiler_set_state("stop")
    assert annotated == [profiler.CLOCK_SYNC] == ["mxtpu_clock_sync"]
    (ev,) = [e for e in obs_trace.events()
             if e["name"] == "mxtpu_clock_sync"]
    assert ev["ph"] == "i"
    assert t0 <= ev["args"]["perf_counter_ns"] <= t1
    # the instant's ts is the same moment on the host trace's clock
    assert abs(ev["args"]["perf_counter_ns"] - obs_trace._EPOCH_NS
               - ev["ts"] * 1000) < 5_000_000


def _scopes(text, names):
    """Which of ``names`` occur as scope components of an op's name."""
    locs = set(re.findall(r'loc\("([^"]*)"', text))
    return {n for n in names
            if any("/%s/" % n in "/%s/" % loc for loc in locs)}


def test_decode_programs_carry_stable_scope_names_and_keep_their_module():
    loop = _loop()
    try:
        (name,) = [n for n in loop._programs if "/step[" in n]
        _, structs, _ = loop._programs[name]
        text = loop._jfn.lower(*structs).as_text(debug_info=True)
    finally:
        loop.close()
    assert "module @jit_decode_fn" in text
    want = {"embed", "layer/attn", "layer/mlp", "cache_write", "head",
            "sample"}
    assert _scopes(text, want) == want


def test_scan_body_carries_forward_backward_update_and_keeps_its_module():
    from mxnet_tpu.train_step import TrainStep
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    step = TrainStep(net, optimizer="sgd", learning_rate=0.05)
    state = step.init({"data": (4, 6)}, {"softmax_label": (4,)}, seed=1)
    batch = {"data": jnp.zeros((2, 4, 6), jnp.float32),
             "softmax_label": jnp.zeros((2, 4), jnp.float32)}
    state, _ = step.run_steps(state, batch)
    fn = step._jit_scan[(4, 2)]
    text = fn.lower(state, batch, step._dispatch_key(),
                    jnp.zeros((2,), jnp.float32)).as_text(debug_info=True)
    assert "module @jit_scan_fn" in text
    want = {"forward", "backward", "update"}
    assert _scopes(text, want) == want
