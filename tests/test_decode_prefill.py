"""``DecodeLoop`` feeds a prompt in CHUNKS (docs/serving.md "The prefill
pass"): a prefill pass writes up to ``PREFILL_CHUNK`` positions of one
slot into its rows between two decode steps, and only the prompt's last
token goes through the step. Held here:

* the PROGRAM against the frozen PR 28 token pass fed one position at a
  time (``tests/test_decode_opt_program_frozen.py``): the rows a chunk
  writes and the logits of the step behind it are those of one-token
  feeding within float32 rounding, for float32, bfloat16 and int8 trees,
  from position 0, over a prefix that was there, padded, and where ``pos0 +
  C`` passes the cache's depth; a padded chunk moves NOTHING outside its
  own rows;
* the LOOP: greedy and sampled tokens are those of the full re-forward and
  of one-token feeding request for request, at every edge of the chunk's
  rule; the counters and the spans say what was fed how; the prefix cache,
  ``eos_id`` and the cache's last row behave as before; a speculative loop
  and a sharded one keep today's feeding.
"""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import models, serving
from mxnet_tpu.obs import trace as obs_trace
from mxnet_tpu.serving import decode
from mxnet_tpu.serving.quantize import dequant_tree

from test_decode_opt_program_frozen import (
    HEADS, LAYERS, ROWS, SLOTS, VOCAB, _build_token_pass, _inputs)

CHUNK = 32
#: (slot, pos0, n): a whole chunk from 0; a padded one over a prefix that
#: was there; one whose ``pos0 + CHUNK`` passes the depth and whose last
#: row is the cache's last; a single position
CASES = [(1, 0, CHUNK), (0, 40, 17), (2, ROWS - 24, 24), (1, 5, 1)]


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

def _one_at_a_time(state, params, tokens, slot, pos0, n):
    """The frozen token pass over the chunk's positions, one a call, the
    other slots parked at position 0: ``(k, v)`` as it leaves them."""
    step = jax.jit(_build_token_pass(LAYERS, HEADS))
    ck, cv = state["k"], state["v"]
    for j in range(n):
        tok = np.zeros(SLOTS, np.int32)
        pos = np.zeros(SLOTS, np.int32)
        tok[slot], pos[slot] = tokens[j], pos0 + j
        ck, cv, _ = step(ck, cv, params, jnp.asarray(tok), jnp.asarray(pos))
    return ck, cv


def _close(a, b):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(
        a, b, rtol=0,
        atol=32 * np.finfo(np.float32).eps * float(np.abs(b).max()))


@pytest.mark.parametrize("mode", ["none", "bf16", "int8"])
@pytest.mark.parametrize("slot,pos0,n", CASES)
def test_a_chunk_writes_the_rows_of_one_token_feeding(mode, slot, pos0, n):
    state, params, _ = _inputs(mode, fed_back=True)
    tokens = np.zeros(CHUNK, np.int32)
    tokens[:n] = np.random.RandomState(pos0 + n).randint(0, VOCAB, n)
    prefill = jax.jit(decode._build_prefill_fn(decode.OptArch(LAYERS, HEADS)))
    new = prefill(state, params, jnp.asarray(tokens), jnp.int32(slot),
                  jnp.int32(pos0), jnp.int32(n))
    assert sorted(new) == sorted(state)
    p = dequant_tree(params)
    rk, rv = _one_at_a_time(state, p, tokens, slot, pos0, n)
    mine = slice(pos0, pos0 + n)
    for name, ref in (("k", rk), ("v", rv)):
        got, was = np.asarray(new[name]), np.asarray(state[name])
        _close(got[:, slot, mine], np.asarray(ref)[:, slot, mine])
        # NOTHING else moved: not the slot's other rows (a padded chunk,
        # a clamped start), not another slot's, not the loop's own members
        kept = np.ones(got.shape, bool)
        kept[:, slot, mine] = False
        np.testing.assert_array_equal(got[kept], was[kept])
    for name in ("seed", "tok"):
        np.testing.assert_array_equal(np.asarray(new[name]),
                                      np.asarray(state[name]))
    # and the step behind the chunk reads what one-token feeding reads:
    # the logits of the slot's next position, live pass over the chunk's
    # rows against the frozen pass over the frozen rows
    if pos0 + n < ROWS:
        tok = jnp.asarray([3, 5, 7], np.int32)
        pos = np.zeros(SLOTS, np.int32)
        pos[slot] = pos0 + n
        live = jax.jit(decode._build_token_pass(LAYERS, HEADS))
        frozen = jax.jit(_build_token_pass(LAYERS, HEADS))
        _close(live(new["k"], new["v"], p, tok, jnp.asarray(pos))[2][slot],
               frozen(rk, rv, p, tok, jnp.asarray(pos))[2][slot])


def test_the_last_layer_stops_at_its_rows_and_nothing_is_handed_back():
    """No head, no sampler: the prefill body returns the state alone and
    never touches the head's or the last layer's later matrices."""
    state, params, _ = _inputs("none", fed_back=True)
    fn = decode._build_prefill_fn(decode.OptArch(LAYERS, HEADS))
    jaxpr = jax.make_jaxpr(fn)(state, params, jnp.zeros(CHUNK, np.int32),
                               jnp.int32(0), jnp.int32(0), jnp.int32(4))
    used = {str(v) for eqn in jaxpr.eqns for v in eqn.invars}
    flat, _ = jax.tree_util.tree_flatten_with_path((state, params))
    unused = {jax.tree_util.keystr(path) for (path, _), var in
              zip(flat, jaxpr.jaxpr.invars) if str(var) not in used}
    last = "layer%d_" % (LAYERS - 1)
    assert {"[1]['lm_head_weight']", "[1]['final_ln_gamma']",
            "[1]['%sffn_fc1_weight']" % last,
            "[1]['%sattn_out_weight']" % last} <= unused
    assert "[1]['%sattn_qkv_weight']" % last not in unused
    out = jax.eval_shape(fn, state, params, jnp.zeros(CHUNK, np.int32),
                         jnp.int32(0), jnp.int32(0), jnp.int32(4))
    assert sorted(out) == ["k", "seed", "tok", "v"]


def test_an_architecture_is_fed_one_position_a_step_unless_it_says_how():
    from mxnet_tpu.serving.arch import Architecture
    assert Architecture().build_prefill_pass() is None
    assert decode._build_prefill_fn(Architecture()) is None
    opt = decode.OptArch(LAYERS, HEADS)
    assert opt.build_prefill_pass() is not None
    # a sharded loop keeps one-token feeding: no sharded chunk yet
    assert opt.build_prefill_pass(mesh=object()) is None


@pytest.mark.parametrize("tests, reference, why", [
    ("test_lfm2_arch", "lfm2-24b-a2b-ep8",
     "a conv state wants a chunk-wide convolution that leaves its rows"),
    ("test_mellum_arch", "mellum2-12b-a2.5b-ep4",
     "a ring wants a chunk that wraps")])
def test_the_other_architectures_are_fed_one_position_a_step(tests,
                                                              reference, why):
    """Each is a PR of its own (``why``; Kimi's latent cache has a PACKED
    pass from PR 39, ``tests/test_decode_prefill_packed.py``):
    ``build_prefill_pass`` is ``None``, so the tiny loop of the architecture's own tests compiles no
    prefill program and the step with the arguments it had, and a prompt longer
    than any ``MIN_PREFILL`` takes a step a position, as before PR 37 (the
    step's lowered text was compared with the parent's once, sha for sha:
    PERF.md, PR 37)."""
    import importlib
    import test_lfm2_arch
    mod = importlib.import_module(tests)
    params = test_lfm2_arch._load(reference).make_params(mod.TINY, 7)
    prompt = [int(t) for t in np.random.RandomState(37).randint(1, 90, 21)]
    assert len(prompt) - 1 >= 2 * decode.MIN_PREFILL
    loop = mod._loop(params)
    try:
        assert loop._arch.build_prefill_pass() is None
        assert loop._prefill_c is None
        # beside the step, from PR 38, the non-donating copy of the device
        # counters that a traced run reads them through
        assert list(loop._programs) == [
            "%s/step[slots=%d,len=%d]" % (loop.name, mod.SLOTS, mod.MAX_LEN),
            "%s/counters[moe_served,moe_routed]" % loop.name]
        (_, structs, donate), (_, _, kept) = loop._programs.values()
        assert len(structs) == 9 + loop._arch.wants_live and donate == (0,)
        assert kept == ()
        out = loop.generate(prompt, 5).result(timeout=120)
        health = loop.health.report()
    finally:
        loop.close()
    assert len(out) == 5
    assert health["decode_steps"] == len(prompt) + 5 - 1
    assert health["prompt_positions"] == len(prompt) - 1
    assert health["prefill_passes"] == health["prefill_positions"] == 0


# ---------------------------------------------------------------------------
# the loop
# ---------------------------------------------------------------------------

_LM = dict(vocab_size=17, embed=16, num_heads=2, num_layers=2, seq_len=48)
C = 16      # PREFILL_CHUNK for the loops below: prompts of several chunks
M = decode.MIN_PREFILL
NEVER = 10 ** 9


def _prompt(n, seed):
    return [int(t) for t in
            np.random.RandomState(seed).randint(1, _LM["vocab_size"], n)]


#: (prompt, new tokens): every edge of the rule, and the request that
#: ends on the cache's last row (its last chunk's window is clamped)
EDGES = [(_prompt(n, n), 3) for n in (1, 2, M, M + 1, C, C + 1, 2 * C + 3)] \
    + [(_prompt(40, 40), 8)]


@pytest.fixture(scope="module")
def lm():
    sym = models.transformer(**_LM)
    s = _LM["seq_len"]
    arg_shapes, _, _ = sym.infer_shape(data=(1, s), softmax_label=(1, s))
    rs = np.random.RandomState(3)

    def scale(name):    # embeddings that tell tokens apart, and no bias
        # large enough to decide the argmax alone: at 0.3 throughout, every
        # stream is one token repeated and parity proves nothing
        if name.endswith(("bias", "beta")):
            return 0.05
        return 1.0 if "embed" in name else 0.3

    params = {n: (rs.randn(*shp) * scale(n)).astype(np.float32)
              for n, shp in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    eng = serving.ServingEngine(sym, params, {"data": (s,)}, buckets=(1,))
    return params, eng


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


@contextlib.contextmanager
def _loop(params, min_prefill=M, **kw):
    """A loop whose chunk is ``C`` positions, closed on the way out;
    ``min_prefill=NEVER`` makes it feed one token a step, as every loop
    did before."""
    kw.setdefault("slots", 3)
    kw.setdefault("prefix_cache", False)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(decode, "PREFILL_CHUNK", C)
        mp.setattr(decode, "MIN_PREFILL", min_prefill)
        loop = serving.DecodeLoop(params, num_layers=_LM["num_layers"],
                                  num_heads=_LM["num_heads"],
                                  max_len=_LM["seq_len"], **kw)
        try:
            yield loop
        finally:
            loop.close()


def _traced(params, requests, loop_kw=None, **kw):
    """``requests`` to their end through a loop of ``loop_kw`` under the
    tracer: ``(futures, decode_step events in order, health)``."""
    obs_trace.stop()
    obs_trace.clear()
    obs_trace.start()
    try:
        with _loop(params, **(loop_kw or {})) as loop:
            futs = [loop.generate(p, n, **kw) for p, n in requests]
            for f in futs:
                f.result(timeout=120)
    finally:
        obs_trace.stop()
    steps = sorted((e for e in obs_trace.events()
                    if e["ph"] == "X" and e["name"] == "decode_step"),
                   key=lambda e: e["args"]["step"])
    obs_trace.clear()
    return futs, steps, loop.health.report()


@pytest.fixture(scope="module")
def chunked(lm):
    return _traced(lm[0], EDGES)


@pytest.fixture(scope="module")
def one_token(lm):
    return _traced(lm[0], EDGES, {"min_prefill": NEVER})


@pytest.fixture(scope="module")
def reference(lm):
    return [_ref_greedy(lm[1], p, n) for p, n in EDGES]


@pytest.mark.parametrize("i", range(len(EDGES)))
def test_a_chunking_loop_serves_the_full_reforwards_tokens(
        chunked, one_token, reference, i):
    assert chunked[0][i].tokens == reference[i] == one_token[0][i].tokens
    assert len(reference[i]) == EDGES[i][1]
    # the streams differ from each other: equality above is no accident
    assert len({tuple(r) for r in reference}) == len(reference)


def test_the_edges_are_fed_as_the_rule_says(chunked, one_token):
    """A prompt of ``MIN_PREFILL`` tokens has one too few before its last
    to be worth a pass; one more and it gets one; a prompt over the chunk
    takes several; every prompt's last token rides a step."""
    futs, steps, health = chunked
    fed = {f.rid: [] for f in futs}
    for st in steps:
        for rid, _, pos0, n in st["args"].get("prefill", ()):
            fed[rid].append((pos0, n))
    by_len = {len(f.prompt): fed[f.rid] for f in futs}
    assert by_len[1] == by_len[2] == by_len[M] == []
    assert by_len[M + 1] == [(0, M)]
    assert len(by_len[C]) == 1 and len(by_len[2 * C + 3]) >= 2
    for f in futs:
        chunks = fed[f.rid]
        assert all(1 <= n <= C for _, n in chunks)
        # chunks lie in order, apart, and stop before the last token
        ends = [p + n for p, n in chunks]
        assert all(e <= p for e, (p, _) in zip(ends, chunks[1:]))
        assert not ends or ends[-1] <= len(f.prompt) - 1
    assert health["prefill_passes"] == sum(len(c) for c in fed.values())
    assert health["prefill_positions"] == sum(
        n for c in fed.values() for _, n in c)
    assert one_token[2]["prefill_passes"] == 0
    assert one_token[2]["prefill_positions"] == 0
    # a one-slot pass carries one slot: the span lists one entry a pass
    assert health["prefill_slots"] == health["prefill_passes"]
    assert all(len(st["args"].get("prefill", [0])) == 1 for st in steps)
    assert not any("prefill" in st["args"] for st in one_token[1])


@pytest.mark.parametrize("run", ["chunked", "one_token"])
def test_the_counters_equal_the_spans_sums(run, request):
    """``prompt_positions`` counts EVERY prompt position committed, by a
    step or by a chunk: the spans' ``n - emit`` summed, as before."""
    futs, steps, health = request.getfixturevalue(run)
    prompt = sum(len(f.prompt) for f in futs)
    new = sum(len(f.tokens) for f in futs)
    n = sum(sum(st["args"]["n"]) for st in steps)
    emit = sum(sum(st["args"]["emit"]) for st in steps)
    assert (n, emit) == (prompt + new - len(futs), new)
    assert health["prompt_positions"] == n - emit == prompt - len(futs)
    assert health["tokens_emitted"] == emit
    assert health["decode_steps"] == len(steps)
    assert health["steps_ahead"] == sum(st["args"]["ahead"] for st in steps)
    assert health["joined"] == health["retired"] == len(futs)
    assert health["trash_slot_steps"] == health["shed"] == 0


def test_chunks_take_steps_away(chunked, one_token):
    assert chunked[2]["decode_steps"] < one_token[2]["decode_steps"]
    assert chunked[2]["prompt_positions"] == one_token[2]["prompt_positions"]


def test_a_fixed_schedule_counts_as_computed(lm):
    """ONE request on ONE slot, 40 prompt tokens and 5 new, chunks of 16
    from 8 up: [16 by a pass, 1 by its step] twice, then 6 < 8 left before
    the last: five steps feed them, the sixth feeds the last token and
    emits; four more emit. Nothing waits on a readback: every step but the
    first is dispatched with the one before unread."""
    futs, steps, h = _traced(lm[0], [(_prompt(40, 1), 5)], {"slots": 1})
    got = {k: h[k] for k in ("decode_steps", "prompt_positions",
                             "prefill_passes", "prefill_positions",
                             "tokens_emitted", "steps_ahead")}
    assert got == {"decode_steps": 12, "prompt_positions": 39,
                   "prefill_passes": 2, "prefill_positions": 32,
                   "tokens_emitted": 5, "steps_ahead": 11}
    rid = futs[0].rid
    assert [st["args"].get("prefill") for st in steps] \
        == [[[rid, 0, 0, 16]], [[rid, 0, 17, 16]]] + [None] * 10
    assert [st["args"]["pos"] for st in steps[:4]] == [[0], [17], [34], [35]]
    assert [st["args"]["n"] for st in steps] == [[17], [17]] + [[1]] * 10
    assert [st["args"]["emit"] for st in steps] == [[0]] * 7 + [[1]] * 5
    # the pass's lap stands first in its step, the five others behind it
    inner = ["decode_gather", "decode_h2d", "decode_dispatch",
             "decode_readback", "decode_commit"]
    for st in steps:
        laps = [lap[0] for lap in st["args"]["laps"]]
        assert laps == ["decode_prefill"] * ("prefill" in st["args"]) + inner


def test_one_pass_a_step_for_the_longest_seated(lm):
    """Three prompts seated together: the passes go to them in the order
    they were seated, one a step, and the ones that wait ride the steps
    meanwhile (so their chunks start past 0)."""
    reqs = [(_prompt(20, 7), 2), (_prompt(20, 8), 2), (_prompt(20, 9), 2)]
    futs, steps, h = _traced(lm[0], reqs)
    passes = [st["args"]["prefill"][0] for st in steps
              if "prefill" in st["args"]]
    seated = {}     # rid -> the first step that listed it
    for st in steps:
        for rid in st["args"]["reqs"]:
            seated.setdefault(rid, st["args"]["step"])
    assert len(passes) == h["prefill_passes"] == 3
    by_step = {st["args"]["step"]: st["args"]["prefill"][0] for st in steps
               if "prefill" in st["args"]}
    assert len(by_step) == 3            # never two in one step,
    assert all(len(st["args"].get("prefill", [0])) == 1 for st in steps)
    assert h["prefill_slots"] == 3      # nor two slots in one pass
    order = [p[0] for p in passes]
    assert order == sorted(order, key=lambda rid: (seated[rid], rid))
    for step, (rid, _, pos0, n) in by_step.items():
        # it rode a step for every step it waited, then a whole chunk
        assert pos0 == step - seated[rid] and n == min(C, 19 - pos0)
    assert [f.tokens for f in futs] == [_ref_greedy(lm[1], p, k)
                                        for p, k in reqs]


# ---------------------------------------------------------------------------
# sampling, the prefix cache, eos, speculation, a mesh
# ---------------------------------------------------------------------------

def test_sampled_requests_draw_the_same_tokens(lm):
    """The uniform of a sample is a function of (seed, position): a chunk
    moves neither."""
    reqs = [(_prompt(n, 50 + n), 6) for n in (12, 20, 37)]
    kw = dict(temperature=0.9, top_k=8, top_p=0.9, seed=42)
    a, _, ha = _traced(lm[0], reqs, **kw)
    b, _, hb = _traced(lm[0], reqs, {"min_prefill": NEVER}, **kw)
    assert [f.tokens for f in a] == [f.tokens for f in b]
    assert ha["prefill_passes"] >= 3 and hb["prefill_passes"] == 0
    assert len({tuple(f.tokens) for f in a}) == 3


def test_a_prefix_hit_is_followed_by_a_chunk_and_a_producer_harvested_after_one(
        lm):
    """The producer's declared prefix is covered by a chunk: it is
    harvested right behind it. The consumer's slot starts at the prefix's
    length and its chunk at ``pos0 > 0`` attends the implanted rows."""
    params, eng = lm
    prefix = _prompt(12, 77)
    first, second = prefix + _prompt(6, 78), prefix + _prompt(14, 79)
    obs_trace.stop()
    obs_trace.clear()
    obs_trace.start()
    try:
        with _loop(params, prefix_cache=True) as loop:
            out1 = loop.generate(first, 4, prefix_len=12).result(timeout=120)
            out2 = loop.generate(second, 4, prefix_len=12).result(timeout=120)
            h = loop.health.report()
    finally:
        obs_trace.stop()
    passes = [e["args"]["prefill"][0] for e in obs_trace.events()
              if e["ph"] == "X" and e["name"] == "decode_step"
              and "prefill" in e["args"]]
    obs_trace.clear()
    assert out1 == _ref_greedy(eng, first, 4)
    assert out2 == _ref_greedy(eng, second, 4)
    assert (h["prefix_prefills"], h["prefix_hits"]) == (1, 1)
    assert [p[2:] for p in passes] == [[0, 16], [12, 13]]


def test_eos_ends_a_chunked_request_where_it_ended_before(lm):
    params, eng = lm
    prompt = _prompt(30, 5)
    ref = _ref_greedy(eng, prompt, 10)
    cut = next(j for j in range(1, 10) if ref[j] not in ref[:j])
    outs = []
    for min_prefill in (M, NEVER):
        with _loop(params, min_prefill=min_prefill, eos_id=ref[cut]) as loop:
            outs.append(loop.generate(prompt, 10).result(timeout=120))
    assert outs[0] == outs[1] == ref[:cut + 1]


def test_a_speculative_loop_keeps_todays_feeding(lm):
    """Its window takes ``spec_k + 1`` prompt positions a round already:
    no prefill program, the same tokens."""
    params, eng = lm
    prompt = _prompt(30, 6)
    with _loop(params, spec_k=2, draft_params=params) as loop:
        assert loop._prefill_c is None
        assert not any("prefill" in name for name in loop._programs)
        out = loop.generate(prompt, 6).result(timeout=120)
        h = loop.health.report()
    assert out == _ref_greedy(eng, prompt, 6)
    assert h["prefill_passes"] == h["prefill_positions"] == 0


def test_a_sharded_loop_keeps_todays_feeding(lm):
    params, eng = lm
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs two devices")
    prompt = _prompt(30, 6)
    with _loop(params, contexts=devs[:2]) as loop:
        assert loop._prefill_c is None
        out = loop.generate(prompt, 6).result(timeout=120)
        h = loop.health.report()
    assert out == _ref_greedy(eng, prompt, 6)
    assert h["prefill_passes"] == 0 and h["decode_steps"] == 30 + 6 - 1


def test_the_chunk_is_the_cache_where_that_is_shallower(lm):
    """``PREFILL_CHUNK`` is 128; a cache of 48 rows takes chunks of 48."""
    loop = serving.DecodeLoop(lm[0], num_layers=_LM["num_layers"],
                              num_heads=_LM["num_heads"],
                              max_len=_LM["seq_len"], slots=2,
                              prefix_cache=False)
    try:
        assert decode.PREFILL_CHUNK == 128 and loop._chunk == 48
        assert [n for n in loop._programs if "prefill" in n] \
            == ["%s/prefill[chunk=48,len=48]" % loop.name]
        assert loop.check(memory=True) == []
        out = loop.generate(_prompt(45, 2), 3).result(timeout=120)
        h = loop.health.report()
    finally:
        loop.close()
    assert out == _ref_greedy(lm[1], _prompt(45, 2), 3)
    assert (h["prefill_passes"], h["prefill_positions"]) == (1, 44)
    assert h["decode_steps"] == 3
