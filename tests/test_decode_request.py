"""``DecodeLoop`` records a request's life, its empty stretches and its
device counters itself (docs/observability.md "Span catalogue"; PERF.md,
PR 38), beside ``tests/test_decode_trace.py``, which holds the step's own
spans:

* ONE ``decode_request`` async pair a request, where the loop ends it:
  ``token_us`` is ``GenerateFuture.token_times`` less ``submit`` to the
  microsecond, ``steps`` names ``decode_step`` spans that list the request,
  ``prefill`` is the sum of those spans' ``prefill`` arguments for it,
  ``outcome`` says how it ended;
* ``ServingHealth``'s six latency counters are the records' sums;
* ``loop_idle`` covers an empty loop's wait, one span a stretch;
* ``loop_counters`` carries the device counters as of its ``step`` through
  a copy program built with the loop's others, read a span later.

Tiny loops on the CPU; nothing here judges how long anything took.
"""
import hashlib
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

from mxnet_tpu import faults, models, obs, serving  # noqa: E402
from mxnet_tpu.obs import flight as obs_flight  # noqa: E402
from mxnet_tpu.obs import trace as obs_trace  # noqa: E402
from mxnet_tpu.obs.registry import Window  # noqa: E402
from mxnet_tpu.serving import decode  # noqa: E402
from mxnet_tpu.serving.health import ServingHealth  # noqa: E402

_LM = dict(vocab_size=17, embed=16, num_heads=2, num_layers=2, seq_len=48)
C = 16      # PREFILL_CHUNK for the loops below: a prompt of 30 is two passes
RECORD_ARGS = {"req", "slot", "prompt_len", "emitted", "outcome", "submit",
               "seat_us", "token_us", "steps", "prefill", "prefix_hit"}


@pytest.fixture(scope="module")
def lm_params():
    sym = models.transformer(**_LM)
    s = _LM["seq_len"]
    arg_shapes, _, _ = sym.infer_shape(data=(1, s), softmax_label=(1, s))
    rs = np.random.RandomState(3)

    def scale(name):    # embeddings that tell tokens apart: streams vary
        if name.endswith(("bias", "beta")):
            return 0.05
        return 1.0 if "embed" in name else 0.3

    return {n: (rs.randn(*shp) * scale(n)).astype(np.float32)
            for n, shp in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}


@pytest.fixture(scope="module")
def kimi():
    """The tiny Kimi-K2 loop of ``tests/test_deepseek_v3_decode.py``: an
    architecture with device counters (and, from PR 39, a packed prefill
    pass that none of the short prompts here is due for)."""
    import test_deepseek_v3_decode as mod
    import test_lfm2_arch
    return mod, test_lfm2_arch._load("kimi-k2-ep32").make_params(mod.TINY, 7)


@pytest.fixture(autouse=True)
def _clean_tracer():
    obs_trace.stop()
    obs_trace.clear()
    yield
    obs_trace.stop()
    obs_trace.clear()


def _prompt(n, seed):
    return [int(t) for t in
            np.random.RandomState(seed).randint(1, _LM["vocab_size"], n)]


def _opt_loop(params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("prefix_cache", False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(decode, "PREFILL_CHUNK", C)
        return serving.DecodeLoop(params, num_layers=_LM["num_layers"],
                                  num_heads=_LM["num_heads"],
                                  max_len=_LM["seq_len"], **kw)


def _serve(loop, requests, **kw):
    """``(prompt, new)`` pairs to their end through ``loop`` under the
    tracer, the loop closed on the way out: ``(futures, the buffer's
    events)``."""
    obs_trace.start()
    try:
        futs = [loop.generate(p, n, **kw) for p, n in requests]
        for f in futs:
            f.result(timeout=120.0)
    finally:
        loop.close()
        obs_trace.stop()
    return futs, obs_trace.events()


def _hold_the_first_step(loop, until):
    """The loop's first dispatch waits for ``until()``: what the test
    queues meanwhile is there when the loop next looks."""
    real = loop._step_c

    def held(*args):
        while not until():
            time.sleep(0.001)
        return real(*args)

    loop._step_c = held


def _records(evs):
    """``{rid: args}`` of the ``decode_request`` records, holding each to
    one begin and one end under its id."""
    begins = [e for e in evs if e["name"] == "decode_request"
              and e["ph"] == "b"]
    ends = [e for e in evs if e["name"] == "decode_request"
            and e["ph"] == "e"]
    assert sorted(e["id"] for e in begins) == sorted(e["id"] for e in ends)
    assert len({e["id"] for e in begins}) == len(begins)     # none twice
    for b in begins:
        assert set(b["args"]) == RECORD_ARGS and b["args"]["req"] == b["id"]
        assert b["cat"] == "async"
    return {b["id"]: b["args"] for b in begins}


def _steps(evs):
    return sorted((e for e in evs if e["name"] == "decode_step"),
                  key=lambda e: e["args"]["step"])


def _held_to_its_spans(rec, evs, left_by_itself=True):
    """``steps`` and ``prefill`` of one record against the ``decode_step``
    spans that list the request (a request the loop failed never left by
    itself: its two later ids stay 0)."""
    rid = rec["req"]
    mine = [st["args"] for st in _steps(evs) if rid in st["args"]["reqs"]]
    first, prompt_done, last = rec["steps"]
    assert first == mine[0]["step"]
    emitting = [a["step"] for a in mine
                if a["emit"][a["reqs"].index(rid)] > 0]
    if left_by_itself:
        assert (prompt_done, last) == (emitting[0], emitting[-1])
        assert sum(a["emit"][a["reqs"].index(rid)] for a in mine) \
            == rec["emitted"]
    else:
        assert (prompt_done, last) == (0, 0)
    passes = [p for a in mine for p in a.get("prefill", ()) if p[0] == rid]
    assert rec["prefill"] == [len(passes), sum(p[3] for p in passes)]
    return mine


# ---------------------------------------------------------------------------
# the record
# ---------------------------------------------------------------------------

PLAIN = [(_prompt(30, 1), 5), (_prompt(3, 2), 4), (_prompt(9, 3), 6),
         (_prompt(1, 4), 3)]


@pytest.fixture(scope="module")
def plain_run(lm_params):
    """Four requests over two slots of the OPT loop, whose prompts go in by
    prefill passes where they are long enough."""
    obs_trace.clear()
    health = ServingHealth(parent=ServingHealth())
    loop = _opt_loop(lm_params, health=health)
    futs, evs = _serve(loop, PLAIN)
    return loop, futs, evs, health


def test_one_record_a_settled_request_and_none_twice(plain_run):
    _, futs, evs, _ = plain_run
    recs = _records(evs)
    assert sorted(recs) == sorted(f.rid for f in futs)
    for f, (prompt, new) in zip(futs, PLAIN):
        r = recs[f.rid]
        assert (r["prompt_len"], r["emitted"], r["outcome"]) \
            == (len(prompt), new, "done") and f.outcome == "done"
        assert r["slot"] in (0, 1) and r["prefix_hit"] == 0
    # an async pair is no complete span: what reads the loop thread's
    # spans (the benchmark's Run.spans, nest_check) meets none of them
    assert not any(e["name"] == "decode_request" for e in evs
                   if e["ph"] == "X")
    assert obs_trace.nest_check(obs_trace.expand_laps(evs)) == []
    # the instants the record replaced are gone; the caller's mark stays
    names = {e["name"] for e in evs}
    assert "decode_submit" in names
    assert not names & {"decode_join", "decode_retire"}


def test_token_us_is_token_times_less_submit_to_the_microsecond(plain_run):
    _, futs, evs, _ = plain_run
    recs = _records(evs)
    for f in futs:
        r = recs[f.rid]
        assert r["submit"] == f.t_submit
        assert len(r["token_us"]) == r["emitted"] == len(f.token_times)
        assert r["token_us"] == sorted(r["token_us"])
        for us, t in zip(r["token_us"], f.token_times):
            assert isinstance(us, int)
            assert abs(us - (t - f.t_submit) * 1e6) <= 0.5 + 1e-6
        assert 0 <= r["seat_us"] <= r["token_us"][0]
    # the record's end is the moment it was settled, on the tracer's clock
    ends = {e["id"]: e["ts"] for e in evs
            if e["name"] == "decode_request" and e["ph"] == "e"}
    for b in (e for e in evs if e["name"] == "decode_request"
              and e["ph"] == "b"):
        assert ends[b["id"]] - b["ts"] >= b["args"]["token_us"][-1] - 2


def test_steps_and_prefill_are_those_of_the_spans_that_list_it(plain_run):
    _, futs, evs, health = plain_run
    recs = _records(evs)
    for f in futs:
        _held_to_its_spans(recs[f.rid], evs)
    # the prompt of 30 took two passes (16, a position on the step between
    # them, 12), the prompt of 9 one of 8, the short ones rode the steps
    assert [recs[f.rid]["prefill"] for f in futs] \
        == [[2, 28], [0, 0], [1, 8], [0, 0]]
    h = health.report()
    assert [h["prefill_passes"], h["prefill_positions"]] == [3, 36]


def test_a_speculative_loop_records_rounds_and_no_pass(lm_params):
    loop = _opt_loop(lm_params, spec_k=2, draft_params=lm_params,
                     draft_num_layers=_LM["num_layers"])
    futs, evs = _serve(loop, [(_prompt(12, 5), 6), (_prompt(2, 6), 5)])
    recs = _records(evs)
    for f in futs:
        r = recs[f.rid]
        _held_to_its_spans(r, evs)
        assert r["prefill"] == [0, 0] and r["outcome"] == "done"
        assert len(r["token_us"]) == len(f.tokens)
        # the tokens of one round share their stamp
        assert len(set(r["token_us"])) <= r["steps"][2] - r["steps"][1] + 1


def test_an_architecture_without_a_pass_records_none(kimi):
    mod, params = kimi
    futs, evs = _serve(mod._loop(params), [(mod.PROMPTS[0], 6),
                                           (mod.PROMPTS[2], 4)])
    recs = _records(evs)
    for f in futs:
        mine = _held_to_its_spans(recs[f.rid], evs)
        assert recs[f.rid]["prefill"] == [0, 0]
        # a position a step: first to last, one span each
        assert len(mine) == len(f.prompt) + len(f.tokens) - 1


def test_a_prefix_hit_is_in_the_record(lm_params):
    loop = _opt_loop(lm_params, prefix_cache=True, slots=1)
    prefix = _prompt(5, 7)
    futs, evs = _serve(loop, [(prefix + [6, 7], 2), (prefix + [8], 3)],
                       prefix_len=len(prefix))
    recs = _records(evs)
    assert [recs[f.rid]["prefix_hit"] for f in futs] == [0, len(prefix)]
    for f in futs:
        _held_to_its_spans(recs[f.rid], evs)
        assert recs[f.rid]["prefill"] == [0, 0]
    # the hit's first span finds it at the prefix's end
    hit = recs[futs[1].rid]
    (st,) = [s for s in _steps(evs) if s["args"]["step"] == hit["steps"][0]]
    assert st["args"]["pos"][st["args"]["reqs"].index(futs[1].rid)] \
        == len(prefix)


def test_eos_is_learned_a_step_late_and_says_so(lm_params):
    prompt = _prompt(6, 8)
    loop = _opt_loop(lm_params, slots=1)
    try:
        free = loop.generate(prompt, 10).result(timeout=120.0)
    finally:
        loop.close()
    cut = next(j for j in range(1, 10) if free[j] not in free[:j])
    loop = _opt_loop(lm_params, slots=1, eos_id=free[cut])
    (fut,), evs = _serve(loop, [(prompt, 10)])
    assert fut.tokens == free[:cut + 1]
    rec = _records(evs)[fut.rid]
    assert (rec["outcome"], rec["emitted"]) == ("eos", cut + 1)
    mine = _held_to_its_spans(rec, evs)
    # the slot-step dispatched before the eos was read is trash: the span
    # after the record's last lists the request and commits nothing
    assert [a["step"] for a in mine][-1] == rec["steps"][2] + 1
    assert mine[-1]["n"] == [0] and mine[-1]["emit"] == [0]
    assert loop.health.report()["trash_slot_steps"] == 1


@pytest.mark.faults
def test_a_dying_loop_sheds_with_one_record_each_and_dumps_them(lm_params):
    if not obs_flight.enabled():
        pytest.skip("flight recorder disabled in this environment")
    obs_flight.FLIGHT.clear()
    loop = _opt_loop(lm_params, slots=2)
    obs_trace.start()
    try:
        faults.inject("serve.decode_die", nth=4, kind="die")
        # three are queued before the second step: two seated, one waits
        futs = []
        _hold_the_first_step(loop, lambda: len(futs) == 3)
        for i in range(3):
            futs.append(loop.generate(_prompt(4, 9 + i), 20))
        for f in futs:
            with pytest.raises(serving.ServingClosedError, match="died"):
                f.result(timeout=60)
    finally:
        faults.clear("serve.decode_die")
        loop.close()
        obs_trace.stop()
    evs = obs_trace.events()
    recs = _records(evs)
    assert sorted(recs) == sorted(f.rid for f in futs)
    assert {r["outcome"] for r in recs.values()} == {"shed"}
    assert all(f.outcome == "shed" for f in futs)
    assert loop.health.shed == 3
    seated = [r for r in recs.values() if r["slot"] >= 0]
    assert len(seated) == 2
    for r in seated:
        _held_to_its_spans(r, evs, left_by_itself=False)
        assert r["seat_us"] >= 0
    for r in recs.values():
        if r["slot"] < 0:       # never seated: nothing but its wait
            assert (r["seat_us"], r["steps"], r["emitted"]) \
                == (-1, [0, 0, 0], 0)
    # the post-mortem shows the dead loop's last requests
    dump = obs_flight.FLIGHT.last_dump
    assert "decode loop died" in dump["reason"]
    assert {e["id"] for e in dump["spans"] if e["name"] == "decode_request"
            and e["ph"] == "b"} == set(recs)


def test_a_close_with_a_request_queued_fails_it_with_a_record(lm_params):
    loop = _opt_loop(lm_params, slots=1)
    _hold_the_first_step(loop, lambda: loop._closed)
    obs_trace.start()
    try:
        futs = [loop.generate(_prompt(3, i), 30) for i in (1, 2)]
        while loop._slots[0] is None:   # the first seated, its step held
            time.sleep(0.001)
    finally:
        loop.close()
        obs_trace.stop()
    recs = _records(obs_trace.events())
    assert sorted(recs) == sorted(f.rid for f in futs)
    assert {r["outcome"] for r in recs.values()} == {"failed"}
    assert sorted(r["slot"] for r in recs.values()) == [-1, 0]
    for f in futs:
        assert f.outcome == "failed"
        with pytest.raises(serving.ServingClosedError, match="closed"):
            f.result(timeout=10)
    # closing twice records nothing twice
    loop.close()
    assert _records(obs_trace.events()) == recs


# ---------------------------------------------------------------------------
# the operator's counters
# ---------------------------------------------------------------------------

LATENCY = ("first_tokens", "first_token_us_sum", "queue_wait_us_sum",
           "token_gaps", "token_gap_us_sum", "token_gap_us_max")


def test_the_latency_counters_are_the_records_sums(plain_run):
    _, futs, evs, health = plain_run
    recs = list(_records(evs).values())
    gaps = [b - a for r in recs
            for a, b in zip(r["token_us"], r["token_us"][1:])]
    want = {"first_tokens": len(recs),
            "first_token_us_sum": sum(r["token_us"][0] for r in recs),
            "queue_wait_us_sum": sum(r["seat_us"] for r in recs),
            "token_gaps": sum(r["emitted"] - 1 for r in recs),
            "token_gap_us_sum": sum(gaps), "token_gap_us_max": max(gaps)}
    for h in (health, health._parent):      # mirrored, like every counter
        rep = h.report()
        assert {k: rep[k] for k in LATENCY} == want
    # and the stamps are the futures' own
    assert want["first_token_us_sum"] == sum(
        int(round((f.token_times[0] - f.t_submit) * 1e6)) for f in futs)
    health_twin = ServingHealth()
    health_twin.record_request_latency(5, [10])     # one token: no gap
    assert [health_twin.report()[k] for k in LATENCY] == [1, 10, 5, 0, 0, 0]
    health_twin.reset()
    assert [health_twin.report()[k] for k in LATENCY] == [0] * 6


def test_the_latency_counters_ride_the_registry_and_a_window(lm_params):
    snap = obs.REGISTRY.snapshot()
    prom = obs.REGISTRY.to_prometheus()
    for k in LATENCY:
        assert "serving_health." + k in snap
        assert "serving_health_" + k in prom
    # a window turns the sums into means over what it saw
    health = ServingHealth()
    window = Window(health.report)
    loop = _opt_loop(lm_params, health=health)
    _serve(loop, [(_prompt(3, 1), 4)])
    first = window.delta()
    assert (first["first_tokens"], first["token_gaps"]) == (1, 3)
    assert first["first_token_us_sum"] / first["first_tokens"] > 0
    assert window.delta()["first_tokens"] == 0


def test_with_the_recorder_alone_the_record_reaches_its_ring(kimi,
                                                             monkeypatch):
    """Tracing off, the default process: the record is in the flight
    recorder's ring, the counters count, and the device counters are
    neither copied nor read."""
    if not obs_flight.enabled():
        pytest.skip("flight recorder disabled in this environment")
    mod, params = kimi
    monkeypatch.setattr(decode, "COUNTER_SPAN_STEPS", 4)
    obs_flight.FLIGHT.clear()
    loop = mod._loop(params)
    copies = []
    real = loop._snapshot_c
    loop._snapshot_c = lambda *a: copies.append(a) or real(*a)
    try:
        fut = loop.generate(mod.PROMPTS[0], 9)
        fut.result(timeout=120.0)
    finally:
        loop.close()
    assert obs_trace.events() == []
    with obs_flight.FLIGHT._lock:
        ring = list(obs_flight.FLIGHT._spans)
    (rec,) = [e["args"] for e in ring if e["name"] == "decode_request"
              and e["ph"] == "b"]
    assert (rec["req"], rec["emitted"], rec["outcome"]) \
        == (fut.rid, 9, "done")
    assert loop.health.report()["first_tokens"] == 1
    assert copies == [] and loop._counter_snap is None
    assert not any(e["name"] in ("loop_counters", "loop_program")
                   for e in ring)


# ---------------------------------------------------------------------------
# the empty loop
# ---------------------------------------------------------------------------

def test_loop_idle_covers_the_stretch_between_two_bursts(lm_params):
    loop = _opt_loop(lm_params)
    obs_trace.start()
    try:
        first = [loop.generate(_prompt(3, i), 4) for i in range(2)]
        for f in first:
            f.result(timeout=120.0)
        while loop._inflight is not None:       # the drain, then the wait
            time.sleep(0.005)
        time.sleep(0.12)        # more than two polls of 50 ms: still ONE
        second = loop.generate(_prompt(2, 5), 3)
        second.result(timeout=120.0)
    finally:
        loop.close()
        obs_trace.stop()
    evs = obs_trace.expand_laps(obs_trace.events())
    assert obs_trace.nest_check(evs) == []
    spans = sorted((e for e in evs if e["ph"] == "X" and e["name"] in (
        "loop_idle", "decode_step", "decode_admit", "loop_drain")),
        key=lambda e: (e["ts"], e["name"] != "loop_idle"))
    idles = [e for e in spans if e["name"] == "loop_idle"]
    # before the first burst, between the two, and until the close
    assert len(idles) == 3
    for idle in idles:
        lo, hi = idle["ts"], idle["ts"] + idle["dur"]
        for e in spans:
            if e is not idle:   # it overlaps no step, admit or drain
                assert e["ts"] + e["dur"] <= lo or e["ts"] >= hi, e["name"]
    between = idles[1]
    steps = _steps(evs)
    last_of_first = max(st["args"]["step"] for st in steps
                        if {f.rid for f in first} & set(st["args"]["reqs"]))
    assert between["args"]["step"] == last_of_first
    assert between["dur"] >= 100_000
    # it ends before the admit that seats the newcomer, which names the
    # step that follows
    (admit,) = [e for e in spans if e["name"] == "decode_admit"
                and e["args"]["step"] == last_of_first + 1]
    assert between["ts"] + between["dur"] <= admit["ts"]
    # and a drain came before it: the idle is not the last step's readback
    assert any(e["name"] == "loop_drain"
               and e["ts"] + e["dur"] <= between["ts"] for e in spans)


# ---------------------------------------------------------------------------
# the device counters, off the critical path
# ---------------------------------------------------------------------------

def test_loop_counters_are_the_totals_as_of_their_step(kimi, monkeypatch):
    """A copy enqueued behind step n, read in span n + 1: ``loop_counters``
    keeps its name, its ``step`` and its arguments, and what it carries is
    ``counter_totals()`` as it stood after step n, though the state's own
    arrays were donated to step n + 1 before it was read. Nothing is
    compiled for it once the loop is built."""
    mod, params = kimi
    monkeypatch.setattr(decode, "COUNTER_SPAN_STEPS", 4)
    loop = mod._loop(params)
    compiled = []

    def on_compile(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(seconds)

    assert "%s/counters[moe_served,moe_routed]" % loop.name in loop._programs
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        futs, evs = _serve(loop, [(mod.PROMPTS[0], 13), (mod.PROMPTS[1], 7),
                                  (mod.PROMPTS[3], 9), (mod.PROMPTS[2], 5)])
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    totals = loop.counter_totals()
    assert compiled == []
    steps = _steps(evs)
    snaps = sorted((e for e in evs if e["name"] == "loop_counters"),
                   key=lambda e: e["args"]["step"])
    assert [s["args"]["step"] for s in snaps] \
        == [n for n in range(4, len(steps) + 1, 4)]
    top_k, layers = mod.TINY["num_experts_per_tok"], 2
    for snap in snaps:
        n = snap["args"]["step"]
        rows = sum(len(st["args"]["reqs"]) for st in steps[:n])
        assert snap["args"]["moe_routed"] == [top_k * rows] * layers
        assert np.shape(snap["args"]["moe_served"]) == (layers, 4)
        # delivered where step n's tokens were read back: after span n + 1
        # (or, where the loop went empty at n, after its drain)
        after = [st for st in steps if st["args"]["step"] == n + 1] or [
            e for e in evs if e["name"] == "loop_drain"
            and e["args"]["step"] == n]
        assert snap["ts"] >= after[0]["ts"] + after[0]["dur"] - 1
    # the last of them is what the health pull reads when all is done
    if len(steps) % 4 == 0:
        assert snaps[-1]["args"]["moe_routed"] == totals["moe_routed"].tolist()
        assert snaps[-1]["args"]["moe_served"] == totals["moe_served"].tolist()
    assert totals["moe_routed"].tolist() == [top_k * sum(
        len(st["args"]["reqs"]) for st in steps)] * layers
    # the scope table of the trace is still the step's, once
    (prog,) = [e for e in evs if e["name"] == "loop_program"]
    assert prog["args"]["program"] == "jit_decode_fn"
    # and, from PR 39, the packed pass's beside it (no prompt here is long
    # enough to be due: the counts above are the steps' alone)
    assert prog["args"]["prefill_program"] == "jit_prefill_fn"
    assert loop.health.report()["prefill_passes"] == 0


def test_loop_program_names_the_pass_beside_the_step(plain_run):
    loop, _, evs, _ = plain_run
    (prog,) = [e for e in evs if e["name"] == "loop_program"]
    assert prog["args"]["program"] == "jit_decode_fn" and prog["args"]["scopes"]
    assert (prog["args"]["prefill_program"], prog["args"]["prefill_chunk"]) \
        == ("jit_prefill_fn", C)
    (name,) = [n for n in loop._programs if "/prefill[" in n]
    jfn = loop._jfns[list(loop._programs).index(name)]
    assert "module @jit_prefill_fn" in jfn.lower(
        *loop._programs[name][1]).as_text()


#: sha256 of the lowered text of the step and pass programs as PR 38 left
#: them (``chip_smoke.lm_params(48, 128, 2, 2, 64, seed=3)`` at 2 slots and
#: 64 rows; the tiny loops of the three other architectures' own tests):
#: PR 39's packed pass shares its layers' text with Kimi's token pass and
#: adds an argument to ``blocks.over_filled_rows``, which every step runs.
#: A PR that changes a program on purpose refreshes its line
PARENTS_TEXT = {
    "opt": {
        "step":
        "3296fd79f95aa5a66b4f6c71af51427e3936b939addb2e60c7b72d99c7b0ce2f",
        "prefill":
        "8157de028112d85d8e9467edec3d6fa58ca8eb28d28e409b90fd21ae13acb057"},
    "kimi": {
        "step":
        "cbe382bd78699426f7cd4b2427ede4c09ca5ee307a872b0fcf9b5125772592d3"},
    "lfm2": {
        "step":
        "1d3c6c770b19927bf9dfda6019e37bd2a37be11772a8c012f39130df86ba9d36"},
    "mellum": {
        "step":
        "f440e12d9a430e8b2a942f7e67c21764005dd6bc18d639cca67d44fe3d6587c1"},
}
#: the tiny loop of each architecture's own tests, and its reference
TINY_LOOPS = {"kimi": ("test_deepseek_v3_decode", "kimi-k2-ep32"),
              "lfm2": ("test_lfm2_arch", "lfm2-24b-a2b-ep8"),
              "mellum": ("test_mellum_arch", "mellum2-12b-a2.5b-ep4")}


@pytest.mark.parametrize("tag", sorted(PARENTS_TEXT))
def test_the_step_and_the_pass_lower_to_the_parents_text(tag):
    import importlib
    import chip_smoke
    import test_lfm2_arch
    if tag == "opt":
        loop = serving.DecodeLoop(
            chip_smoke.lm_params(48, 128, 2, 2, 64, seed=3), 2, 2,
            max_len=64, slots=2, prefix_cache=False, spec_k=0)
    else:
        mod = importlib.import_module(TINY_LOOPS[tag][0])
        loop = mod._loop(test_lfm2_arch._load(TINY_LOOPS[tag][1])
                         .make_params(mod.TINY, 7))
    got = {}
    try:
        for jfn, (name, (_, structs, _)) in zip(loop._jfns,
                                                loop._programs.items()):
            kind = name.split("/")[1].split("[")[0]
            if kind in ("step", "prefill"):
                got[kind] = hashlib.sha256(
                    jfn.lower(*structs).as_text().encode()).hexdigest()
    finally:
        loop.close()
    # Kimi's pass is new with PR 39: no parent's text to hold it to
    assert sorted(got) == ["prefill"] * (tag in ("opt", "kimi")) + ["step"]
    assert {k: got[k] for k in PARENTS_TEXT[tag]} == PARENTS_TEXT[tag]
