"""A PACKED prefill pass (docs/serving.md "The prefill pass"; PERF.md, PR
39): ``DeepseekV3Arch``'s pass takes rows that each NAME their slot and
position, the loop fills it from every slot that is due and holds it back
until it carries enough positions to pay for its read of the weights.
Held here, on the tiny Kimi-K2 configuration of
``tests/test_deepseek_v3_decode.py`` in float32 and in bfloat16:

* the PROGRAM against the token pass fed one position a step: a pass over
  1, 2 and 3 slots at mixed starts (0, over rows that were there, a second
  pass behind a first) leaves the named rows one-token feeding's and every
  other row of every slot bit for bit as it was; padding moves nothing,
  also where ``pos + R`` passes the cache's depth; the routing counters
  count the pass's rows as the steps counted them, in every expert layer
  the pass runs (the LAST layer stops at its latent row, so its experts
  serve no row of a pass and count none);
* the RULE by count, with no device: when a pass is held back and when it
  fires, what it carries, what it leaves of each slot;
* the LOOP: the tokens of one-token feeding, greedy and sampled; the
  counters and the spans; ``eos_id``, the prefix cache, speculation, a
  model mesh.
"""
import contextlib
import functools
import threading
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.obs import trace as obs_trace
from mxnet_tpu.serving import decode
from mxnet_tpu.serving.blocks import rows_ladder
from mxnet_tpu.serving.health import ServingHealth

import test_lfm2_arch
from test_deepseek_v3_decode import MAX_LEN, SLOTS, TINY

R = 32          # rows of a pass in the program tests
NEVER = 10 ** 9
DTYPES = ["float32", "bfloat16"]


@functools.lru_cache(maxsize=None)
def _model(dtype):
    """``(arch, params on the device)`` of the tiny configuration."""
    cfg = dict(TINY, dtype=dtype)
    params = test_lfm2_arch._load("kimi-k2-ep32").make_params(cfg, 7)
    return serving.DeepseekV3Arch(cfg), \
        {k: jnp.asarray(v) for k, v in params.items()}


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

#: name -> (rows of the cache, [pass, ...]), a pass ``[(slot, pos0, n)]`` in
#: the order its rows lie in
CASES = {
    "one_slot_from_0": (MAX_LEN, [[(1, 0, 20)]]),
    "two_slots_one_over_rows_that_were_there":
        (MAX_LEN, [[(0, 6, 11), (2, 0, 15)]]),
    "three_slots_full_then_a_second_pass":
        (MAX_LEN, [[(0, 0, 10), (1, 0, 12), (2, 0, 10)],
                   [(1, 12, 9), (0, 10, 5)]]),
    "pos_plus_R_passes_the_depth": (MAX_LEN, [[(2, 40, 8)]]),
    "all_padding": (MAX_LEN, [[]]),
    "across_a_rung_of_the_ladder": (400, [[(1, 200, 16), (0, 3, 9)]]),
}


def _base_state(arch, rows, dtype):
    """Slots whose every row holds SOMETHING (what an earlier occupant, a
    prefix hit or the steps left there), the surplus lanes zero."""
    rs = np.random.RandomState(11)
    lat = np.zeros((arch.num_layers, SLOTS, rows, arch.latent_width),
                   np.float32)
    lat[..., :arch.latent] = rs.randn(arch.num_layers, SLOTS, rows,
                                      arch.latent) * 0.5
    state = {"latent": jnp.asarray(lat).astype(dtype)}
    state.update({k: jnp.asarray(rs.randint(0, 9, s).astype(np.int32))
                  for k, s in arch.counters().items()})
    return state


def _pass_args(group, seed):
    tokens = np.zeros(R, np.int32)
    slot = np.full(R, 7, np.int32)      # padding names no slot there is
    pos = np.full(R, 10 ** 6, np.int32)
    at = 0
    for s, pos0, n in group:
        tokens[at:at + n] = np.random.RandomState(seed + s).randint(
            1, TINY["vocab_size"], n)
        slot[at:at + n], pos[at:at + n] = s, np.arange(pos0, pos0 + n)
        at += n
    return tokens, slot, pos, at


def _one_position_a_step(arch, params, state, group, tokens, rows):
    """The token pass over the pass's positions, every slot one position a
    step as a loop feeds them; a slot with nothing (more) to feed is not
    live and parked on a row the pass does not name."""
    step = jax.jit(arch.build_token_pass())
    todo, at = {}, 0
    for s, pos0, n in group:
        todo[s] = (pos0, list(tokens[at:at + n]))
        at += n
    named = {s: set(range(p, p + len(t))) for s, (p, t) in todo.items()}
    park = [max(set(range(rows)) - named.get(s, set()))
            for s in range(SLOTS)]
    for t in range(max([len(v[1]) for v in todo.values()] or [0])):
        tok = np.zeros(SLOTS, np.int32)
        pos = np.asarray(park, np.int32)
        live = np.zeros(SLOTS, bool)
        for s, (pos0, toks) in todo.items():
            if t < len(toks):
                tok[s], pos[s], live[s] = toks[t], pos0 + t, True
        state, _ = step(state, params, tok, pos, live)
    return state


@functools.lru_cache(maxsize=None)
def _ran(dtype, case):
    """Each pass of ``case`` by the packed pass and by one-token feeding,
    both from the state the pass before left: ``[(group, before, by the
    pass, by the steps)]``."""
    arch, params = _model(dtype)
    rows, groups = CASES[case]
    prefill = jax.jit(decode._build_prefill_fn(arch))
    state = dict(_base_state(arch, rows, dtype),
                 seed=jnp.zeros(SLOTS, np.uint32),
                 tok=jnp.arange(SLOTS, dtype=np.int32))
    out = []
    for k, group in enumerate(groups):
        tokens, slot, pos, n = _pass_args(group, 100 * k)
        new = prefill(state, params, tokens, slot, pos, np.int32(n))
        ref = _one_position_a_step(arch, params, decode._model_state(state),
                                   group, tokens, rows)
        out.append((group, state, new, ref))
        state = new
    return out


def _named(group, shape):
    mine = np.zeros(shape, bool)
    for s, pos0, n in group:
        mine[:, s, pos0:pos0 + n] = True
    return mine


def _close(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    eps = 2.0 ** -8 if dtype == "bfloat16" else np.finfo(np.float32).eps
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=32 * eps * float(np.abs(want).max()))


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", DTYPES)
def test_a_packed_pass_writes_the_rows_of_one_token_feeding(dtype, case):
    arch, params = _model(dtype)
    rows = CASES[case][0]
    for group, _, new, ref in _ran(dtype, case):
        mine = _named(group, new["latent"].shape[:3])
        if not mine.any():
            continue
        _close(np.asarray(new["latent"].astype(np.float32))[mine],
               np.asarray(ref["latent"].astype(np.float32))[mine], dtype)
        # and the step behind the pass reads what one-token feeding reads:
        # every slot's next position over either state
        step = jax.jit(arch.build_token_pass())
        pos = np.zeros(SLOTS, np.int32)
        for s, pos0, n in group:
            pos[s] = min(pos0 + n, rows - 1)
        tok = np.arange(3, 3 + SLOTS, dtype=np.int32)
        live = np.ones(SLOTS, bool)
        _, got = step(decode._model_state(new), params, tok, pos, live)
        _, want = step(ref, params, tok, pos, live)
        _close(got, want, dtype)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", DTYPES)
def test_a_packed_pass_moves_nothing_but_the_rows_it_names(dtype, case):
    """Not a slot's other rows, not a slot the pass does not name, not the
    loop's own members: padding rows (which name slot 7 at position 10^6
    here) are dropped, also where ``pos + R`` passes the depth."""
    for group, before, new, _ in _ran(dtype, case):
        assert sorted(new) == sorted(before)
        got, was = np.asarray(new["latent"]), np.asarray(before["latent"])
        kept = ~_named(group, got.shape[:3])
        np.testing.assert_array_equal(got[kept], was[kept])
        for name in ("seed", "tok"):
            np.testing.assert_array_equal(np.asarray(new[name]),
                                          np.asarray(before[name]))
        # the surplus lanes of the rows it wrote stay zero
        arch = _model(dtype)[0]
        assert not np.asarray(new["latent"][..., arch.latent:]
                              .astype(np.float32)).any()


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", DTYPES)
def test_a_packed_pass_counts_its_rows_as_the_steps_counted_them(dtype,
                                                                 case):
    """``moe_served`` / ``moe_routed`` by either feeding, in every expert
    layer the pass runs; its last layer stops at its latent row, so the
    last expert layer serves and counts none of a pass's rows."""
    top_k = TINY["num_experts_per_tok"]
    for group, before, new, ref in _ran(dtype, case):
        n = sum(g[2] for g in group)
        for name in ("moe_served", "moe_routed"):
            got, want, was = (np.asarray(x[name]) for x in (new, ref, before))
            np.testing.assert_array_equal(got[:-1], want[:-1])
            np.testing.assert_array_equal(got[-1], was[-1])
        grew = np.asarray(new["moe_routed"]) - np.asarray(before["moe_routed"])
        assert grew.tolist() == [top_k * n, 0]


def test_the_rung_is_the_pass_deepest_positions():
    """A cache of 400 rows has two rungs (208, 400): a pass whose deepest
    live position is under 208 runs its attention over 208 rows whatever
    its padding names, one at 208 or over it over all 400."""
    assert rows_ladder(400) == (208, 400)
    arch, params = _model("float32")
    prefill = jax.jit(decode._build_prefill_fn(arch))
    state = dict(_base_state(arch, 400, "float32"),
                 seed=jnp.zeros(SLOTS, np.uint32),
                 tok=jnp.zeros(SLOTS, np.int32))
    # rows past the first rung set to NaN: a pass that stays under the rung
    # must not read them (a softmax weight of 0 times NaN is NaN)
    lat = np.asarray(state["latent"]).copy()
    lat[:, :, 208:, :arch.latent] = np.nan
    state["latent"] = jnp.asarray(lat)
    tokens, slot, pos, n = _pass_args([(1, 190, 18), (0, 0, 5)], 5)
    new = prefill(state, params, tokens, slot, pos, np.int32(n))
    got = np.asarray(new["latent"])
    assert np.isfinite(got[:, 1, 190:208]).all()
    assert np.isfinite(got[:, 0, 0:5]).all()
    # one position deeper and the pass reads the whole depth
    tokens, slot, pos, n = _pass_args([(1, 190, 19)], 5)
    deep = np.asarray(prefill(state, params, tokens, slot, pos,
                              np.int32(n))["latent"])
    assert np.isfinite(deep[0, 1, 190:209]).all()      # layer 0: no reads
    assert not np.isfinite(deep[1:, 1, 190:209, :arch.latent]).any()


def test_the_last_layer_stops_at_its_latent_row():
    """No head, no sampler: the prefill body returns the state alone and
    never touches the head's or the last layer's later matrices."""
    arch, params = _model("float32")
    state = dict(_base_state(arch, MAX_LEN, "float32"),
                 seed=jnp.zeros(SLOTS, np.uint32),
                 tok=jnp.zeros(SLOTS, np.int32))
    fn = decode._build_prefill_fn(arch)
    args = (jnp.zeros(R, np.int32),) * 3 + (jnp.int32(4),)
    jaxpr = jax.make_jaxpr(fn)(state, params, *args)
    used = {str(v) for eqn in jaxpr.eqns for v in eqn.invars}
    flat, _ = jax.tree_util.tree_flatten_with_path((state, params))
    unused = {jax.tree_util.keystr(path) for (path, _), var in
              zip(flat, jaxpr.jaxpr.invars) if str(var) not in used}
    last = "layer%d_" % (arch.num_layers - 1)
    assert {"[1]['lm_head_weight']", "[1]['final_norm_gamma']",
            "[1]['%sattn_q_b_weight']" % last,
            "[1]['%sattn_out_weight']" % last,
            "[1]['%srouter_weight']" % last,
            "[1]['%sexperts_down_weight']" % last} <= unused
    assert "[1]['%sattn_kv_a_weight']" % last not in unused
    out = jax.eval_shape(fn, state, params, *args)
    assert sorted(out) == ["latent", "moe_routed", "moe_served", "seed",
                           "tok"]


def test_which_architectures_pack_and_what_the_constants_are():
    from mxnet_tpu.serving.arch import Architecture
    assert not Architecture.packed_prefill
    assert not decode.OptArch.packed_prefill
    assert serving.DeepseekV3Arch.packed_prefill
    assert not serving.Lfm2Arch.packed_prefill
    assert not serving.MellumArch.packed_prefill
    arch = _model("float32")[0]
    assert arch.build_prefill_pass() is not None
    # a sharded loop keeps one-token feeding: no sharded pass
    assert arch.build_prefill_pass(mesh=object()) is None
    assert (decode.PACKED_ROWS, decode.PASS_PAYS, decode.PREFILL_CHUNK,
            decode.MIN_PREFILL) == (256, 2, 128, 8)


# ---------------------------------------------------------------------------
# the rule, by count: no device
# ---------------------------------------------------------------------------

class _Span(object):
    def __init__(self):
        self.args, self.laps = {}, []

    def lap(self, name, **kw):
        self.laps.append(name)

    def set(self, **kw):
        self.args.update(kw)


def _bare(nslots, chunk, packed=True):
    """A loop that is only what ``_prefill`` reads, its prefill program a
    recorder: ``(loop, the passes dispatched)``."""
    loop = decode.DecodeLoop.__new__(decode.DecodeLoop)
    loop._arch = types.SimpleNamespace(packed_prefill=packed)
    loop._slots = [None] * nslots
    loop._chunk = chunk
    loop._state, loop._params = "state", "params"
    loop._state_lock = threading.Lock()
    loop.health = ServingHealth()
    loop._dev = list
    calls = []

    def program(state, params, tokens, slot, pos, n):
        calls.append((np.array(tokens), np.array(slot), np.array(pos),
                      int(n)))
        return state

    loop._prefill_c = program
    return loop, calls


def _seat(loop, i, prompt_len, rode=0):
    """Seat a request of ``prompt_len`` tokens (i * 1000 + 1 ..) in slot
    ``i``, then let it ride ``rode`` steps."""
    fut = types.SimpleNamespace(
        prompt=[i * 1000 + j for j in range(1, prompt_len + 1)], rid=500 + i)
    seat = 1 + sum(s is not None for s in loop._slots)
    slot = decode._Slot(fut, seat, 0)
    for _ in range(rode):
        slot.pos += 1
        slot.next_token = slot.pending.pop(0)
    loop._slots[i] = slot
    return slot


def _sixty_generate(loop):
    for i in range(60):
        _seat(loop, i, 1)       # its one token is next: it emits


def test_one_join_waits_and_the_second_fires_both_in_one_pass():
    loop, calls = _bare(64, decode.PACKED_ROWS)
    _sixty_generate(loop)
    first = _seat(loop, 60, 64)             # 63 due: under 2 x 60
    sp = _Span()
    assert loop._prefill(sp) == 0
    assert calls == [] and sp.args == {} and sp.laps == []
    assert (first.pos, len(first.pending)) == (0, 63)
    assert loop.health.report()["prefill_passes"] == 0
    # it rides four steps meanwhile, as without a pass; then the second
    loop._slots[60] = None
    first = _seat(loop, 60, 64, rode=4)
    second = _seat(loop, 61, 64)
    assert loop._prefill(sp) == 59 + 63
    (tokens, slot, pos, n), = calls
    assert n == 122 and tokens.shape == (256,)
    # longest seated first, each slot's rows consecutive and ascending
    assert slot[:n].tolist() == [60] * 59 + [61] * 63
    assert pos[:n].tolist() == list(range(4, 63)) + list(range(0, 63))
    assert tokens[:n].tolist() == [60005 + j for j in range(59)] \
        + [61001 + j for j in range(63)]
    assert not tokens[n:].any()
    # every prompt's LAST token is left to the step
    for s in (first, second):
        assert (s.pos, s.next_token, s.pending) == (63, s.fut.prompt[-1], [])
        assert s.prefill == [1, 63 - (s is first) * 4]
    assert sp.args["prefill"] == [[560, 60, 4, 59], [561, 61, 0, 63]]
    assert sp.laps == ["decode_prefill"]
    h = loop.health.report()
    assert (h["prefill_passes"], h["prefill_slots"],
            h["prefill_positions"]) == (1, 2, 122)


@pytest.mark.parametrize("prompt_len, fires", [(8, 0), (9, 8), (40, 39)])
def test_a_request_alone_in_a_wide_loop_fires_at_min_prefill(prompt_len,
                                                             fires):
    """No slot generates beside it: the floor alone decides."""
    loop, calls = _bare(64, decode.PACKED_ROWS)
    _seat(loop, 17, prompt_len)
    assert loop._prefill(_Span()) == fires
    assert [c[3] for c in calls] == [fires] * bool(fires)


def test_a_full_pass_fires_whatever_the_count():
    """32 rows are fewer than 2 x 60, and all the pass can hold: it goes,
    and the slot whose rest does not fit gives what fits."""
    loop, calls = _bare(64, 32)
    _sixty_generate(loop)
    a, b = _seat(loop, 60, 21), _seat(loop, 61, 64)
    sp = _Span()
    assert loop._prefill(sp) == 32
    assert sp.args["prefill"] == [[560, 60, 0, 20], [561, 61, 0, 12]]
    assert (a.pos, a.pending, b.pos, len(b.pending)) == (20, [], 12, 51)
    assert b.next_token == b.fut.prompt[12]
    assert calls[0][3] == 32
    # the rest is due again at once, and full again
    assert loop._prefill(sp) == 32
    assert sp.args["prefill"] == [[561, 61, 12, 32]]


def test_a_pass_under_the_count_waits_even_with_several_slots_due():
    loop, calls = _bare(64, decode.PACKED_ROWS)
    _sixty_generate(loop)
    for i, n in ((60, 40), (61, 40), (62, 41)):     # 39 + 39 + 40 = 118
        _seat(loop, i, n)
    assert loop._prefill(_Span()) == 0 and calls == []
    _seat(loop, 63, 11)                             # + 10: 128 >= 120
    assert loop._prefill(_Span()) == 128
    assert calls[0][3] == 128


def test_a_slot_short_of_min_prefill_is_not_due_and_rides_the_steps():
    loop, calls = _bare(4, decode.PACKED_ROWS)
    _seat(loop, 0, 30, rode=22)         # 7 left before its last
    _seat(loop, 1, 12)
    sp = _Span()
    assert loop._prefill(sp) == 11
    assert sp.args["prefill"] == [[501, 1, 0, 11]]


def test_a_one_slot_pass_keeps_todays_rule():
    """``OptArch``'s: the longest seated due slot gets its pass at once,
    however many slots generate, with scalar ``slot`` and ``pos0``."""
    loop, calls = _bare(64, 128, packed=False)
    _sixty_generate(loop)
    _seat(loop, 60, 20, rode=3)
    _seat(loop, 61, 200)
    sp = _Span()
    assert loop._prefill(sp) == 16
    (tokens, slot, pos0, n), = calls
    assert (slot.shape, pos0.shape) == ((), ())
    assert (int(slot), int(pos0), n) == (60, 3, 16)
    assert tokens[:16].tolist() == [60004 + j for j in range(16)]
    assert sp.args["prefill"] == [[560, 60, 3, 16]]
    assert loop._prefill(sp) == 128     # the next slot's turn, a chunk
    h = loop.health.report()
    assert (h["prefill_passes"], h["prefill_slots"],
            h["prefill_positions"]) == (2, 2, 144)


# ---------------------------------------------------------------------------
# the loop
# ---------------------------------------------------------------------------

def _prompt(n, seed):
    return [int(t) for t in np.random.RandomState(seed).randint(
        1, TINY["vocab_size"], n)]


#: (prompt, new tokens): more requests than slots, prompts under the
#: floor, over it, over a pass (24 rows below) and one that ends on the
#: cache's last row
REQUESTS = [(_prompt(20, 1), 6), (_prompt(5, 2), 8), (_prompt(31, 3), 5),
            (_prompt(9, 4), 7), (_prompt(12, 5), 6), (_prompt(40, 6), 8)]
SAMPLED = dict(temperature=0.9, top_k=12, top_p=0.9, seed=42)


@contextlib.contextmanager
def _loop(dtype="float32", min_prefill=decode.MIN_PREFILL, **kw):
    """The tiny loop with passes of 24 rows, closed on the way out;
    ``min_prefill=NEVER`` feeds it one position a step."""
    arch, params = _model(dtype)
    kw.setdefault("prefix_cache", False)
    kw.setdefault("spec_k", 0)
    if dtype == "bfloat16":
        kw["quantize"] = "bf16"
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(decode, "PACKED_ROWS", 24)
        mp.setattr(decode, "MIN_PREFILL", min_prefill)
        loop = serving.DecodeLoop(params, max_len=MAX_LEN, slots=SLOTS,
                                  arch=arch, **kw)
        try:
            yield loop
        finally:
            loop.close()


def _all_at_once(loop, requests, **kw):
    """Submit ``requests`` with the loop's door shut and open it when all
    are queued: the first ``slots`` of them are seated TOGETHER, whatever
    the threads' timing, and from there the schedule goes by count."""
    door, admit = threading.Event(), loop._admit
    loop._admit = lambda: admit() if door.is_set() else 0
    futs = [loop.generate(p, n, **kw) for p, n in requests]
    door.set()
    return futs


@functools.lru_cache(maxsize=None)
def _served(dtype, min_prefill, sampled=False):
    """``REQUESTS`` to their end under the tracer: ``(tokens, decode_step
    events in order, health, device counters)``."""
    obs_trace.stop()
    obs_trace.clear()
    obs_trace.start()
    try:
        with _loop(dtype, min_prefill) as loop:
            futs = _all_at_once(loop, REQUESTS,
                                **(SAMPLED if sampled else {}))
            outs = [f.result(timeout=120) for f in futs]
            health, counts = loop.health.report(), loop.counter_totals()
    finally:
        obs_trace.stop()
    steps = sorted((e for e in obs_trace.events()
                    if e["ph"] == "X" and e["name"] == "decode_step"),
                   key=lambda e: e["args"]["step"])
    obs_trace.clear()
    return outs, steps, health, counts, [f.rid for f in futs]


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_a_packing_loop_emits_one_token_feedings_tokens(dtype, sampled):
    packed = _served(dtype, decode.MIN_PREFILL, sampled)
    plain = _served(dtype, NEVER, sampled)
    assert packed[0] == plain[0]
    assert [len(o) for o in packed[0]] == [n for _, n in REQUESTS]
    assert len({tuple(o) for o in packed[0]}) == len(REQUESTS)
    assert packed[2]["prefill_passes"] > 0 == plain[2]["prefill_passes"]
    assert packed[2]["decode_steps"] < plain[2]["decode_steps"]


def test_greedy_tokens_are_the_references_full_forward():
    ref = test_lfm2_arch._load("kimi-k2-ep32")
    params = ref.make_params(dict(TINY, dtype="float32"), 7)
    for (prompt, _), out in zip(REQUESTS, _served("float32",
                                                  decode.MIN_PREFILL)[0]):
        seq = jnp.asarray((prompt + out)[:-1], jnp.int32)
        logits = np.asarray(ref.forward(params, seq, TINY, "float32"))
        assert out == logits[len(prompt) - 1:].argmax(-1).tolist()


@pytest.mark.parametrize("dtype", DTYPES)
def test_the_counters_equal_the_spans_sums(dtype):
    outs, steps, h, _, rids = _served(dtype, decode.MIN_PREFILL)
    passes = [st["args"]["prefill"] for st in steps
              if "prefill" in st["args"]]
    assert h["prefill_passes"] == len(passes)
    assert h["prefill_slots"] == sum(len(p) for p in passes)
    assert h["prefill_positions"] == sum(e[3] for p in passes for e in p)
    assert h["prefill_slots"] > h["prefill_passes"]     # some pass packed
    assert all(sum(e[3] for e in p) <= 24 for p in passes)
    n = sum(sum(st["args"]["n"]) for st in steps)
    emit = sum(sum(st["args"]["emit"]) for st in steps)
    prompt = sum(len(p) for p, _ in REQUESTS)
    new = sum(len(o) for o in outs)
    assert (n, emit) == (prompt + new - len(REQUESTS), new)
    assert h["prompt_positions"] == n - emit
    assert h["decode_steps"] == len(steps)
    fed = {rid: [] for rid in rids}
    for st in steps:
        for rid, slot, pos0, k in st["args"].get("prefill", ()):
            fed[rid].append((pos0, k))
            # the span's ``n`` of that request: the pass's rows + the step's
            i = st["args"]["reqs"].index(rid)
            assert st["args"]["n"][i] == k + 1
            assert st["args"]["pos"][i] == pos0
    for rid, (prompt, _) in zip(rids, REQUESTS):
        chunks = fed[rid]
        # chunks lie in order, apart, and stop before the last token
        ends = [p + k for p, k in chunks]
        assert all(e <= p for e, (p, _) in zip(ends, chunks[1:]))
        assert not ends or ends[-1] <= len(prompt) - 1
        if len(prompt) - 1 < decode.MIN_PREFILL:
            assert chunks == []
    # the pass's lap stands first in its step
    for st in steps:
        laps = [lap[0] for lap in st["args"]["laps"]]
        assert (laps[0] == "decode_prefill") == ("prefill" in st["args"])


def test_the_routing_counters_by_either_feeding():
    """Equal in every expert layer the pass runs; the last expert layer
    lacks the pass's positions, ``top_k`` pairs each (its experts do not
    run for them: nothing reads the last layer's output of a prompt
    position)."""
    packed = _served("float32", decode.MIN_PREFILL)
    plain = _served("float32", NEVER)
    top_k = TINY["num_experts_per_tok"]
    a, b = packed[3], plain[3]
    np.testing.assert_array_equal(a["moe_served"][:-1], b["moe_served"][:-1])
    assert a["moe_routed"][:-1].tolist() == b["moe_routed"][:-1].tolist()
    assert int(b["moe_routed"][-1]) - int(a["moe_routed"][-1]) \
        == top_k * packed[2]["prefill_positions"] > 0
    assert (a["moe_served"][-1] <= b["moe_served"][-1]).all()


def test_eos_ends_a_packed_request_where_it_ended_before():
    prompt = _prompt(30, 5)
    with _loop() as loop:
        ref = loop.generate(prompt, 10).result(timeout=120)
    cut = next(j for j in range(1, 10) if ref[j] not in ref[:j])
    outs = []
    for min_prefill in (decode.MIN_PREFILL, NEVER):
        with _loop(min_prefill=min_prefill, eos_id=ref[cut]) as loop:
            outs.append(loop.generate(prompt, 10).result(timeout=120))
            assert bool(loop.health.report()["prefill_passes"]) \
                == (min_prefill != NEVER)
    assert outs[0] == outs[1] == ref[:cut + 1]


def test_a_prefix_hit_is_followed_by_a_pass_and_a_producer_harvested_after_one():
    """The producer's declared prefix is covered by a pass: it is harvested
    right behind it. The consumer's slot starts at the prefix's length and
    its rows in a pass attend the implanted ones."""
    prefix = _prompt(12, 77)
    first, second = prefix + _prompt(6, 78), prefix + _prompt(14, 79)
    with _loop(min_prefill=NEVER) as loop:
        want = [loop.generate(p, 4).result(timeout=120)
                for p in (first, second)]
    obs_trace.stop()
    obs_trace.clear()
    obs_trace.start()
    try:
        with _loop(prefix_cache=True) as loop:
            got = [loop.generate(p, 4, prefix_len=12).result(timeout=120)
                   for p in (first, second)]
            h = loop.health.report()
    finally:
        obs_trace.stop()
    passes = [e["args"]["prefill"] for e in obs_trace.events()
              if e["ph"] == "X" and e["name"] == "decode_step"
              and "prefill" in e["args"]]
    obs_trace.clear()
    assert got == want
    assert (h["prefix_prefills"], h["prefix_hits"]) == (1, 1)
    assert [[e[2:] for e in p] for p in passes] == [[[0, 17]], [[12, 13]]]


def test_a_speculative_loop_keeps_one_token_feeding():
    arch, params = _model("float32")
    prompt = _prompt(30, 6)
    with _loop(min_prefill=NEVER) as loop:
        want = loop.generate(prompt, 6).result(timeout=120)
    with _loop(spec_k=2, draft_params=params,
               draft_arch=serving.DeepseekV3Arch(TINY)) as loop:
        assert loop._prefill_c is None
        assert not any("prefill" in name for name in loop._programs)
        out = loop.generate(prompt, 6).result(timeout=120)
        h = loop.health.report()
    assert out == want
    assert h["prefill_passes"] == h["prefill_slots"] == 0


def test_the_pass_is_the_cache_where_that_is_shallower():
    """``PACKED_ROWS`` is 256; a cache of 48 rows takes passes of 48, and
    the program set stays lint-clean."""
    arch, params = _model("float32")
    loop = serving.DecodeLoop(params, max_len=MAX_LEN, slots=SLOTS, arch=arch,
                              prefix_cache=False, spec_k=0)
    try:
        assert loop._chunk == 48
        assert [n for n in loop._programs if "prefill" in n] \
            == ["%s/prefill[chunk=48,len=48]" % loop.name]
        (_, structs, donate), = [v for k, v in loop._programs.items()
                                 if "/prefill[" in k]
        assert [tuple(s.shape) for s in structs[2:]] \
            == [(48,), (48,), (48,), ()] and donate == (0,)
        assert loop.check(memory=True) == []
        futs = _all_at_once(loop, [(_prompt(n, n), 3) for n in (30, 25, 45)])
        outs = [f.result(timeout=120) for f in futs]
        h = loop.health.report()
    finally:
        loop.close()
    assert [len(o) for o in outs] == [3, 3, 3]
    # seated together with no slot generating: ONE pass takes what fits of
    # the first two (29 + 19 of 24); the third rides that iteration's step
    # and its other 43 go behind it; the second's last 5 ride the steps
    assert (h["prefill_passes"], h["prefill_slots"],
            h["prefill_positions"]) == (2, 3, 29 + 19 + 43)
    assert h["prompt_positions"] == 29 + 24 + 44
