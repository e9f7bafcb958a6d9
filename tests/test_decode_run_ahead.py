"""``DecodeLoop`` keeps one step in flight (docs/serving.md "What a step
is"): step n+1 is fed and dispatched before step n's tokens are read, the
fed-back token stays on the device, the host schedules by count, and
``eos_id`` is learned one step late. Whatever the order of dispatch and
readback, a request gets the tokens the full re-forward gives it."""
import time

import numpy as np
import pytest

from mxnet_tpu import models, serving
from mxnet_tpu.obs import trace as obs_trace
from mxnet_tpu.serving import decode

_LM = dict(vocab_size=17, embed=16, num_heads=2, num_layers=2, seq_len=16)
#: more requests than slots, mixed prompt lengths: slots are joined and
#: left mid-stream, and the last request fills the cache to its last row
REQUESTS = [([1, 2, 3], 5), ([4, 5], 4), ([6], 6), ([7, 8, 9, 10, 11], 3),
            ([12, 13], 7), ([3], 2), ([14, 15, 16, 1, 2, 3, 4, 5], 8)]


@pytest.fixture(scope="module")
def lm():
    sym = models.transformer(**_LM)
    s = _LM["seq_len"]
    arg_shapes, _, _ = sym.infer_shape(data=(1, s), softmax_label=(1, s))
    rs = np.random.RandomState(3)
    params = {n: (rs.randn(*shp) * 0.3).astype(np.float32)
              for n, shp in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    eng = serving.ServingEngine(sym, params, {"data": (s,)}, buckets=(1,))
    return params, eng


def _loop(params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("prefix_cache", False)
    return serving.DecodeLoop(params, num_layers=_LM["num_layers"],
                              num_heads=_LM["num_heads"],
                              max_len=_LM["seq_len"], **kw)


def _ref_greedy(eng, prompt, max_new):
    """Greedy decode by full re-forward through the AOT engine."""
    s = _LM["seq_len"]
    seq, out = list(prompt), []
    for _ in range(max_new):
        x = np.zeros((1, s), np.float32)
        x[0, :len(seq)] = seq
        tok = int(np.argmax(eng.infer({"data": x})[0][len(seq) - 1]))
        out.append(tok)
        seq.append(tok)
    return out


@pytest.fixture(scope="module")
def reference(lm):
    _, eng = lm
    return [_ref_greedy(eng, p, n) for p, n in REQUESTS]


def _idle(loop, timeout=10.0):
    """Wait until the loop has read back the last step it dispatched (a
    trash slot-step is counted when it is READ, after its request has its
    tokens; a loop closed before that sheds the step, uncounted)."""
    deadline = time.monotonic() + timeout
    while loop._inflight is not None and time.monotonic() < deadline:
        time.sleep(0.001)
    assert loop._inflight is None


def _traced(loop, work):
    """``work(loop)`` under the tracer; the loop closed, then its
    ``decode_step`` spans in order and its ``loop_drain`` spans."""
    obs_trace.stop()
    obs_trace.clear()
    obs_trace.start()
    try:
        out = work(loop)
    finally:
        loop.close()
        obs_trace.stop()
    evs = [e for e in obs_trace.events() if e["ph"] == "X"]
    obs_trace.clear()
    steps = sorted((e for e in evs if e["name"] == "decode_step"),
                   key=lambda e: e["args"]["step"])
    return out, steps, [e for e in evs if e["name"] == "loop_drain"]


@pytest.fixture(scope="module")
def plain(lm):
    """REQUESTS through two slots, all submitted at once."""
    params, _ = lm
    loop = _loop(params)

    def work(loop):
        futs = [loop.generate(p, n) for p, n in REQUESTS]
        return futs, [f.result(timeout=120) for f in futs]

    (futs, outs), steps, drains = _traced(loop, work)
    return {"futs": futs, "outs": outs, "steps": steps,
            "drains": drains, "health": loop.health.report()}


# ---------------------------------------------------------------------------
# the same tokens, whatever the order of dispatch and readback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("i", range(len(REQUESTS)))
def test_greedy_stream_is_the_full_reforwards(plain, reference, i):
    assert plain["outs"][i] == reference[i]
    assert len(plain["outs"][i]) == REQUESTS[i][1]


def test_a_request_that_fills_the_cache_retires_at_its_last_row(plain,
                                                                reference):
    prompt, new = REQUESTS[-1]
    assert len(prompt) + new == _LM["seq_len"]
    assert plain["outs"][-1] == reference[-1]
    h = plain["health"]
    assert h["joined"] == h["retired"] == len(REQUESTS)
    assert h["trash_slot_steps"] == 0 and h["shed"] == 0


def test_every_token_is_stamped_when_the_host_has_it(plain):
    for f in plain["futs"]:
        assert len(f.token_times) == len(f.tokens)
        assert f.token_times == sorted(f.token_times)


@pytest.mark.parametrize("sampling", [dict(temperature=0.8, seed=11),
                                      dict(temperature=1.3, top_k=5,
                                           top_p=0.9, seed=5)],
                         ids=["temperature", "top_k_top_p"])
def test_a_seeded_sampled_request_replays_a_one_slot_loops_tokens(lm,
                                                                  sampling):
    """The (seed, position) uniforms do not depend on when a step was
    dispatched, nor on who else is seated."""
    params, _ = lm
    alone = _loop(params, slots=1)
    try:
        want = alone.generate([6, 7, 8], 9, **sampling).result(timeout=120)
    finally:
        alone.close()
    loop = _loop(params, slots=3)
    try:
        futs = [loop.generate(p, n) for p, n in REQUESTS[:4]]
        mine = loop.generate([6, 7, 8], 9, **sampling)
        futs += [loop.generate(p, n) for p, n in REQUESTS[4:]]
        assert mine.result(timeout=120) == want
        for f in futs:
            f.result(timeout=120)
        assert loop.health.sampled_steps > 0
    finally:
        loop.close()
    assert len(set(want)) > 1


# ---------------------------------------------------------------------------
# eos is learned one step late
# ---------------------------------------------------------------------------

def _eos_of(reference):
    """A token that ends some reference stream early and not all."""
    for eos in range(_LM["vocab_size"]):
        cut = [r.index(eos) + 1 if eos in r else len(r) for r in reference]
        early = sum(c < len(r) for c, r in zip(cut, reference))
        if 2 <= early < len(reference):
            return eos, cut
    raise AssertionError("no usable eos among the reference streams")


@pytest.fixture(scope="module")
def eos_run(lm, reference):
    params, _ = lm
    eos, cut = _eos_of(reference)
    loop = _loop(params, eos_id=eos)

    def work(loop):
        futs = [loop.generate(p, n) for p, n in REQUESTS]
        outs = [f.result(timeout=120) for f in futs]
        _idle(loop)
        return outs

    outs, steps, _ = _traced(loop, work)
    return {"eos": eos, "cut": cut, "outs": outs, "steps": steps,
            "health": loop.health.report()}


@pytest.mark.parametrize("i", range(len(REQUESTS)))
def test_nothing_after_eos_is_delivered(eos_run, reference, i):
    """And the NEXT occupant of a slot that ran a trash slot-step decodes
    the reference's tokens: every request here follows some other."""
    want = reference[i][:eos_run["cut"][i]]
    assert eos_run["outs"][i] == want
    assert eos_run["eos"] not in want[:-1]


def test_trash_slot_steps_counts_exactly_the_late_slot_steps(eos_run,
                                                             reference):
    """A request ended by ``eos`` before its count was dispatched once
    more; one ended by its count (its last token ``eos`` or not) never."""
    late = sum(c < len(r) for c, r in zip(eos_run["cut"], reference))
    h = eos_run["health"]
    assert late >= 2 and h["trash_slot_steps"] == late
    assert h["tokens_emitted"] == sum(len(o) for o in eos_run["outs"])
    assert h["joined"] == h["retired"] == len(REQUESTS)
    # the spans: a slot-step known to be trash when its span ends commits
    # no position and emits no token; the sums are the counters'
    steps = eos_run["steps"]
    assert sum(sum(st["args"]["emit"]) for st in steps) \
        == h["tokens_emitted"]
    assert sum(sum(st["args"]["n"]) for st in steps) \
        == h["tokens_emitted"] + h["prompt_positions"]
    listed = sum(len(st["args"]["reqs"]) for st in steps)
    assert listed - sum(sum(st["args"]["n"]) for st in steps) == late


def test_an_eos_on_the_last_counted_token_wastes_no_slot_step(lm,
                                                              reference):
    params, _ = lm
    prompt, _ = REQUESTS[0]
    eos = reference[0][2]
    first = reference[0].index(eos)
    loop = _loop(params, eos_id=eos, slots=1)
    try:
        out = loop.generate(prompt, first + 1).result(timeout=120)
        assert out == reference[0][:first + 1] and out[-1] == eos
        assert loop.health.report()["trash_slot_steps"] == 0
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# how often the mechanism engages
# ---------------------------------------------------------------------------

def test_steps_ahead_is_the_steps_less_the_starts_from_empty(plain):
    h, steps = plain["health"], plain["steps"]
    assert h["decode_steps"] == len(steps)
    ahead = [st["args"]["ahead"] for st in steps]
    assert set(ahead) <= {0, 1} and ahead[0] == 0
    assert h["steps_ahead"] == sum(ahead) == len(steps) - ahead.count(0)
    # a start from empty is the first step, or the step after a drain
    assert ahead.count(0) <= 1 + len(plain["drains"])
    # a start from empty has no step to read back: its last two laps are
    # there, and empty but for the clock reads between them (microseconds)
    for st in steps:
        laps = {lap[0]: lap[2] for lap in st["args"]["laps"]}
        if not st["args"]["ahead"]:
            assert laps["decode_readback"] + laps["decode_commit"] < 2000


def test_the_loop_drains_goes_idle_and_starts_again(lm, reference):
    """One request at a time: each start is a start from empty, each end
    a drain outside any ``decode_step`` span."""
    params, _ = lm
    loop = _loop(params)

    def work(loop):
        outs = []
        for p, n in REQUESTS[:3]:
            outs.append(loop.generate(p, n).result(timeout=120))
            _idle(loop)
            assert all(s is None for s in loop._slots)
            assert loop._thread.is_alive()
        return outs

    outs, steps, drains = _traced(loop, work)
    assert outs == reference[:3]
    h = loop.health.report()
    assert len(drains) == 3
    assert h["decode_steps"] == len(steps) \
        == sum(len(p) + n - 1 for p, n in REQUESTS[:3])
    assert h["steps_ahead"] == h["decode_steps"] - 3
    assert [st["args"]["step"] for st in steps
            if not st["args"]["ahead"]] \
        == [1, 1 + len(REQUESTS[0][0]) + REQUESTS[0][1] - 1,
            1 + sum(len(p) + n - 1 for p, n in REQUESTS[:2])]


def test_close_sheds_the_request_whose_last_step_is_in_flight(lm):
    """A request that has left its slot and not been handed its tokens is
    failed by ``close()``, not left hanging."""
    params, _ = lm
    loop = _loop(params, slots=1)
    loop._closed = True          # the loop thread leaves at its next turn
    loop._thread.join(timeout=10.0)
    fut = decode.GenerateFuture(loop, [1, 2], 3)
    slot = decode._Slot(fut)
    loop._inflight = [np.zeros(1, np.int32), [(0, slot, True, True)]]
    loop.close()
    assert loop._inflight is None
    with pytest.raises(serving.ServingClosedError):
        fut.result(timeout=5.0)
    assert loop.health.report()["shed"] == 1


# ---------------------------------------------------------------------------
# the prefix cache under run-ahead: implant before, harvest after
# ---------------------------------------------------------------------------

def test_prefix_cache_hit_and_harvest_emit_the_plain_stream(lm):
    params, eng = lm
    prefix = [1, 2, 3, 4, 5]
    requests = [(prefix + [6, 7], 4), (prefix + [8], 5), ([9, 10], 4),
                (prefix + [11, 12, 13], 3)]
    want = [_ref_greedy(eng, p, n) for p, n in requests]
    loop = _loop(params, prefix_cache=True, slots=2)
    try:
        first = loop.generate(*requests[0], prefix_len=len(prefix))
        assert first.result(timeout=120) == want[0]     # harvested by now
        futs = [loop.generate(p, n, prefix_len=len(prefix)
                              if p[:len(prefix)] == prefix else 0)
                for p, n in requests[1:]]
        assert [f.result(timeout=120) for f in futs] == want[1:]
        h = loop.health.report()
        assert h["prefix_prefills"] == 1 and h["prefix_hits"] == 2
        assert h["trash_slot_steps"] == 0
    finally:
        loop.close()


def test_the_fed_back_token_is_the_devices_own(lm):
    """The step program takes the host's token where it supplies one and
    the token it sampled the step before where it marks the slot."""
    import jax.numpy as jnp
    params, _ = lm

    def step(loop, tokens, pos):
        feed = [np.asarray(tokens, np.int32), np.asarray(pos, np.int32),
                np.zeros(2, np.float32), np.zeros(2, np.int32),
                np.ones(2, np.float32), np.zeros(2, np.uint32),
                np.zeros(2, np.bool_)]
        with loop._state_lock:
            loop._state, toks = loop._step_c(
                loop._state, loop._params, *[jnp.asarray(a) for a in feed])
        return np.asarray(toks).tolist()

    fed_back, host_fed = _loop(params), _loop(params)
    try:
        assert decode.FED_BACK < 0 and "tok" in fed_back._state
        first = step(fed_back, [3, 4], [0, 0])
        assert np.asarray(fed_back._state["tok"]).tolist() == first
        assert step(host_fed, [3, 4], [0, 0]) == first
        # slot 0 takes the device's token, slot 1 the host's: the same
        # second position as a loop handed both by the host
        assert step(fed_back, [decode.FED_BACK, first[1]], [1, 1]) \
            == step(host_fed, first, [1, 1])
    finally:
        fed_back.close()
        host_fed.close()
