"""The Laguna block through ``DecodeLoop`` (docs/serving.md "Architectures";
PERF.md), at a tiny size on the CPU, against the benchmark's plain
reference (``benchmark/reference/laguna-s-2.1-ep8.py``, loaded by path: it
imports nothing of ``mxnet_tpu``; no cache, no ring, a banded mask). The
tiny configuration keeps the published 48-entry per-layer lists and runs
their first five layers: a full layer that is dense, then three window
layers and a full one that route, so it holds the dense lead and a whole
F S S S period. Window layers have 6 query heads and full layers 4 over the
same 2 K/V heads; the full layers rotate half of each head with YaRN; this
chip holds experts 4..7 of a router of 16, beside a shared expert.

* the loop's greedy tokens, and the token pass's LOGITS at every position
  through the ring and the per-position cache, are the reference's full
  forward's, in float32 and in bfloat16;
* a reused slot gives a fresh slot's logits bit for bit;
* bfloat16 leaves and state stay bfloat16;
* THE SHARES ADD UP: the eight shares' partial results, with the shared
  expert and all that every chip computes alike counted once, give the
  uncut reference layer;
* PLANTED FAULTS fail the same comparison: the gate dropped, a full layer's
  whole head rotated, the shared expert dropped, the routed scaling 1, the
  window lifted;
* the scopes, the counters, the state, and what cannot run says so.
"""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.obs import trace as obs_trace
from mxnet_tpu.serving import arch as arch_mod
from mxnet_tpu.serving import laguna

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ROPE = {"full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
WINDOW = 8
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
TINY = dict(
    model_type="laguna", hidden_size=64, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_hidden_layers=5, vocab_size=97,
    intermediate_size=96, moe_intermediate_size=32,
    shared_expert_intermediate_size=24, num_experts=4,
    num_experts_per_tok=3, norm_topk_prob=True, decoder_sparse_step=1,
    mlp_only_layers=[0], tie_word_embeddings=False, gating="per-head",
    sliding_window=WINDOW, rope_parameters=ROPE, layer_types=PERIOD * 12,
    mlp_layer_types=["dense"] + ["sparse"] * 47,
    gating_types=["per_head"] * 48, moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[4, 6, 6, 6] * 12,
    moe_router_logit_softcapping=0, moe_apply_router_weight_on_input=False,
    max_position_embeddings=1048576, attention_bias=False, rms_norm_eps=1e-6,
    router_width=16, share_index=1, dtype="float32", init_std=0.2,
    embed_std=1.0, head_std=0.3, router_std=0.2, gate_std=0.15)
MAX_LEN, SLOTS, NEW = 48, 3, 24
PROMPTS = [[5, 9, 11, 3, 8], [1, 2, 3], [40, 41, 42, 43, 44, 45, 46, 47, 48],
           [7]]


def _load(name):
    path = os.path.join(ROOT, "benchmark", "reference", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "ref_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("laguna-s-2.1-ep8")


@pytest.fixture(scope="module")
def params(ref):
    return ref.make_params(TINY, 7)


def _loop(params, cfg=TINY, **kw):
    kw.setdefault("prefix_cache", False)
    kw.setdefault("spec_k", 0)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("slots", SLOTS)
    return serving.DecodeLoop(params, arch=serving.LagunaArch(cfg), **kw)


@pytest.fixture(scope="module")
def served(params):
    """The plain loop's streams over PROMPTS (more requests than slots; 25
    to 33 positions each: 3 to 4 windows of 8), traced."""
    loop = _loop(params)
    obs_trace.clear()
    obs_trace.start()
    try:
        futs = [loop.generate(p, NEW) for p in PROMPTS]
        outs = [f.result(timeout=120) for f in futs]
    finally:
        obs_trace.stop()
        evs = [e for e in obs_trace.events() if e.get("ph") == "X"]
        obs_trace.clear()
        health = loop.health.report()
        loop.close()
    return {"outs": outs, "health": health, "evs": evs}


def _state(arch, slots, rows, dtype=np.float32):
    quant = "bf16" if dtype is not np.float32 else "none"
    state = {k: jnp.zeros((a.layers, slots, a.depth(rows), a.width), a.dtype)
             for k, a in arch.slot_state(None, quant).items()}
    state.update({k: jnp.zeros(s, np.int32)
                  for k, s in arch.counters().items()})
    return state


def _walk(step, state, dev, seqs, slots=None):
    """Feed ``seqs`` position by position, sequence j in slot ``slots[j]``
    of ``state``; returns the state and each sequence's logits (T, vocab)."""
    n = state["k_win"].shape[1]
    slots = list(range(len(seqs))) if slots is None else slots
    got = [[] for _ in seqs]
    for t in range(max(len(s) for s in seqs)):
        live, toks, pos = (np.zeros(n, bool), np.zeros(n, np.int32),
                           np.zeros(n, np.int32))
        for j, s in enumerate(seqs):
            live[slots[j]] = t < len(s)
            toks[slots[j]] = s[min(t, len(s) - 1)]
            pos[slots[j]] = min(t, len(s) - 1)
        state, logits = step(state, dev, toks, pos, live)
        for j, s in enumerate(seqs):
            if t < len(s):
                got[j].append(np.asarray(logits)[slots[j]])
    return state, [np.stack(g) for g in got]


def _seq(n, seed):
    return np.random.default_rng(seed).integers(0, 97, n).tolist()


def _pass_logits(cfg, p, seq):
    arch = serving.LagunaArch(cfg)
    _, (got,) = _walk(jax.jit(arch.build_token_pass()), _state(arch, 1, 48),
                      arch.load(p), [seq])
    return got


# ---------------------------------------------------------------------------
# the loop against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_greedy_stream_is_the_references_full_forward(ref, params, served, i):
    prompt, out = PROMPTS[i], served["outs"][i]
    assert len(prompt) + len(out) - 1 >= 3 * WINDOW
    seq = jnp.asarray((prompt + out)[:-1], jnp.int32)
    logits = np.asarray(ref.forward(params, seq, TINY, "float32", block=16))
    assert out == logits[len(prompt) - 1:].argmax(-1).tolist()


@pytest.mark.parametrize("dtype, tol", [
    # float32 on both sides: the step's sums run in another order
    ("float32", 3e-4),
    # bfloat16 operands with float32 accumulation against float32 at
    # "highest": 2^-9 a rounded operand over 5 layers; a position whose
    # router flips a choice on that rounding moves further, so positions
    # are judged by their median and the flips counted
    ("bfloat16", 0.08)])
def test_token_pass_logits_through_the_ring_and_the_cache(ref, dtype, tol):
    """Two slots at DIFFERENT positions in one step (one has wrapped the
    ring while the other has not), against the full forward's logits at
    every position: 33 and 26 positions over a window of 8."""
    cfg = dict(TINY, dtype=dtype)
    p = ref.make_params(cfg, 7)
    arch = serving.LagunaArch(cfg)
    step = jax.jit(arch.build_token_pass())
    seqs = [_seq(33, 1), _seq(26, 2)]
    store = np.float32 if dtype == "float32" else jnp.bfloat16
    state, got = _walk(step, _state(arch, 2, 48, store), arch.load(p), seqs)
    for s, g in zip(seqs, got):
        want = np.asarray(ref.forward(p, jnp.asarray(s, jnp.int32), cfg,
                                      "float32", block=16))
        if dtype == "float32":
            np.testing.assert_allclose(g, want, rtol=tol, atol=tol)
            continue
        err = np.abs(g - want)
        assert np.median(err) < tol and np.mean(err.max(-1) > 0.5) <= 0.4
    assert str(state["k_win"].dtype) == dtype
    assert state["k_win"].shape[2] == WINDOW and state["k"].shape[2] == 48


def test_a_reused_slot_gives_the_logits_of_a_fresh_one(ref, params):
    """The second request of a slot starts at position 0 over a ring its
    longer predecessor wrapped three times: the logits are a fresh slot's
    BIT FOR BIT."""
    arch = serving.LagunaArch(TINY)
    dev = arch.load(params)
    step = jax.jit(arch.build_token_pass())
    first, second = _seq(30, 4), _seq(19, 5)
    dirty, _ = _walk(step, _state(arch, 2, 48), dev, [first], slots=[1])
    assert np.abs(np.asarray(dirty["k_win"])[:, 1]).min(axis=-1).max() > 0
    _, (reused,) = _walk(step, dirty, dev, [second], slots=[1])
    _, (fresh,) = _walk(step, _state(arch, 2, 48), dev, [second], slots=[1])
    np.testing.assert_array_equal(reused, fresh)
    np.testing.assert_allclose(
        fresh, np.asarray(ref.forward(params, jnp.asarray(second, jnp.int32),
                                      TINY)), rtol=3e-4, atol=3e-4)


def test_bf16_leaves_and_state_stay_bf16(ref):
    cfg = dict(TINY, dtype="bfloat16", sliding_window=16)
    p = ref.make_params(cfg, 7)
    loop = _loop(p, cfg, quantize="bf16")
    try:
        assert all(str(v.dtype) == "bfloat16" for v in loop._params.values())
        assert {str(loop._state[k].dtype)
                for k in ("k", "v", "k_win", "v_win")} == {"bfloat16"}
        assert loop._state["k"].shape[2] == 48          # 16-row tiles
        assert loop._state["k_win"].shape[2] == 16
        assert loop.weight_bytes() == 2 * ref.param_count(cfg)
        out = loop.generate(PROMPTS[0], NEW).result(120)
    finally:
        loop.close()
    seq = jnp.asarray((PROMPTS[0] + out)[:-1], jnp.int32)
    logits = np.asarray(ref.forward(p, seq, cfg, "float32"))[4:]
    gap = logits.max(-1) - logits[np.arange(NEW), out]
    # a served token lies within bfloat16 rounding of the reference's best
    assert gap.max() < 0.15 and np.mean(gap * gap) < 1e-3


# ---------------------------------------------------------------------------
# the shares
# ---------------------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer(ref):
    """ONE expert layer (a window layer, 19 positions over a window of 8),
    so a share's partial sum reaches the output without passing another
    router. With ``x_k`` share k's output (2 of the 16 experts held),
    ``x_0`` the output with no expert held (the residual, the gated
    attention and the shared expert: what every chip computes alike) and
    ``x`` the uncut 16-expert layer's: ``sum_k (x_k - x_0) + x_0 == x``."""
    uncut = dict(TINY, num_hidden_layers=1, num_experts=16,
                 layer_types=["sliding_attention"],
                 mlp_layer_types=["sparse"], mlp_only_layers=[],
                 num_attention_heads_per_layer=[6])
    del uncut["router_width"], uncut["share_index"]
    full = ref.make_params(uncut, 11)
    toks = jnp.asarray(_seq(19, 6), jnp.int32)

    def last_layer(cfg, p):
        taps = {}
        ref.forward(p, toks, cfg, "float32", taps=taps)
        return np.asarray(taps["layers"][-1])

    def share(k, held=2):
        p = dict(full)
        for name in ("gate", "up", "down"):
            key = "layer0_experts_%s_weight" % name
            p[key] = full[key][held * k:held * k + held]
        return dict(uncut, num_experts=held, router_width=16,
                    share_index=k), p

    x = last_layer(uncut, full)
    x0 = last_layer(*share(0, held=0))
    parts = [last_layer(*share(k)) for k in range(8)]
    assert max(np.abs(pk - x0).max() for pk in parts) > 1e-2
    # the shared expert is in x0: without it the layer is another one
    no_shared = dict(full, layer0_shared_down_weight=jnp.zeros_like(
        full["layer0_shared_down_weight"]))
    assert np.abs(last_layer(uncut, no_shared) - x).max() > 1e-2
    # float32 sums in another order
    np.testing.assert_allclose(sum(pk - x0 for pk in parts) + x0, x,
                               rtol=2e-5, atol=2e-5)
    # and the program's share of that layer is the reference's
    cfg, p = share(5)
    got = _pass_logits(cfg, p, list(np.asarray(toks)))
    np.testing.assert_allclose(
        got, np.asarray(ref.forward(p, toks, cfg)), rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# planted faults
# ---------------------------------------------------------------------------

FULL_WHOLE_HEAD = dict(ROPE, full_attention=dict(
    ROPE["full_attention"], partial_rotary_factor=1))


@pytest.mark.parametrize("fault, change", [
    ("gate_dropped", {}),
    ("whole_head_rotation", dict(rope_parameters=FULL_WHOLE_HEAD)),
    ("shared_dropped", dict(shared_expert_intermediate_size=0)),
    ("routed_scaling_one", dict(moe_routed_scaling_factor=1.0)),
    ("window_lifted", dict(sliding_window=MAX_LEN))])
def test_a_planted_fault_fails_the_same_comparison(ref, params, fault,
                                                   change):
    """The comparison of the logits test, on a program built with one
    mechanism changed (the parameters as they are: a dropped part's weights
    are present and unread), against the true reference."""
    seq = _seq(33, 1)
    want = np.asarray(ref.forward(params, jnp.asarray(seq, jnp.int32), TINY))
    if fault == "gate_dropped":
        # the config keys accept no ungated Laguna: plant it in the pass
        arch = serving.LagunaArch(TINY)
        arch.gated = False
        _, (got,) = _walk(jax.jit(arch.build_token_pass()),
                          _state(arch, 1, 48), arch.load(params), [seq])
    else:
        got = _pass_logits(dict(TINY, **change), params, seq)
    if fault == "window_lifted":
        early = WINDOW - 1
        np.testing.assert_allclose(got[:early], want[:early], rtol=3e-4,
                                   atol=3e-4)
        got, want = got[WINDOW + 2:], want[WINDOW + 2:]
    assert np.abs(got - want).max() > 1e-2, fault


# ---------------------------------------------------------------------------
# the state protocol, the scopes and the counters
# ---------------------------------------------------------------------------

PUBLISHED = dict(
    TINY, hidden_size=3072, num_attention_heads=48, num_key_value_heads=8,
    head_dim=128, num_hidden_layers=12, vocab_size=12544,
    intermediate_size=12288, moe_intermediate_size=1024,
    shared_expert_intermediate_size=1024, num_experts=32,
    num_experts_per_tok=10, sliding_window=512, router_width=256,
    share_index=0, num_attention_heads_per_layer=[48, 72, 72, 72] * 12)


def test_the_stage_the_shapes_and_the_state_at_published_widths():
    a = serving.LagunaArch(PUBLISHED)
    assert a.layer_types == tuple(PERIOD * 3)
    assert a.heads == (48, 72, 72, 72) * 3
    assert (a.dense_layers, a.expert_layers) == ([0], list(range(1, 12)))
    assert (a.window_layers, a.full_layers) == (
        [1, 2, 3, 5, 6, 7, 9, 10, 11], [0, 4, 8])
    shapes = a.param_shapes()
    assert shapes["layer1_attn_q_weight"] == (72 * 128, 3072)
    assert shapes["layer4_attn_out_weight"] == (3072, 48 * 128)
    assert shapes["layer5_attn_gate_weight"] == (72, 3072)
    assert shapes["layer0_ffn_gate_weight"] == (12288, 3072)
    assert "layer0_router_weight" not in shapes
    assert shapes["layer1_shared_down_weight"] == (3072, 1024)
    assert shapes["layer1_experts_up_weight"] == (32, 1024, 3072)
    assert abs(sum(int(np.prod(s)) for s in shapes.values())
               - 4.325e9) < 1e6
    assert (a.share.scaling, a.share.score, a.share.top_k, a.share.held,
            a.share.first) == (2.5, "softmax", 10, 32, 0)
    st = a.slot_state(None, "bf16")
    assert st["k_win"] == arch_mod.SlotArray(9, 512, 1024, jnp.bfloat16,
                                             ring=True)
    assert st["k"] == arch_mod.SlotArray(3, arch_mod.PER_POSITION, 1024,
                                         jnp.bfloat16)
    # 64 slots, 3584 rows: the full layers' rows 2.82 GB, the ring 1.21 GB
    assert 2 * 3 * 64 * st["k"].depth(3584) * 1024 * 2 == 2818572288
    assert 2 * 9 * 64 * st["k_win"].depth(3584) * 1024 * 2 == 1207959552
    assert a.counters() == {"moe_served": (11, 32), "moe_routed": (11,)}
    # the compiler left to itself, Mellum's bound taken off
    assert a.compiler_options("tpu") == {} == a.compiler_options("cpu")
    # half of a full layer's head turns, YaRN's blend over those 64 lanes
    inv, factor = a.rotary["full_attention"]
    assert inv.shape == (32,) and factor == 1.4852030263919618
    assert a.rotary["sliding_attention"][0].shape == (64,)


def test_the_scopes_of_a_traced_run(served):
    (prog,) = [e for e in served["evs"] if e["name"] == "loop_program"]
    assert set(prog["args"]["state"]) == {"k_win", "v_win", "k", "v"}
    kinds = set(prog["args"]["scopes"].values())
    for scope in ("embed", "layer/attn/window", "layer/attn/full",
                  "cache_write/kv/window", "cache_write/kv/full",
                  "layer/mlp", "layer/moe/router", "layer/moe/experts",
                  "layer/moe/shared", "head", "sample"):
        assert any(k.startswith(scope) for k in kinds), (scope, kinds)


def test_the_loop_counted_what_it_routed(served):
    # 4 expert layers (the first is dense), top-3, every position of every
    # request but the last token's (it is emitted, never fed)
    positions = sum(len(p) + NEW - 1 for p in PROMPTS)
    h = served["health"]
    assert h["moe_pairs_routed"] == 4 * 3 * positions
    assert 0 < h["moe_pairs_here"] < h["moe_pairs_routed"]
    steps = [e["args"] for e in served["evs"] if e["name"] == "decode_step"]
    assert h["ring_wrapped_slot_steps"] == sum(
        p >= WINDOW for a in steps for p in a["pos"]) > 0


def test_mellum_keeps_its_own_program_values():
    """What Mellum's config does not say stays Mellum's: no gate, no dense
    layer, no shared expert, scaling 1, every head rotated whole."""
    from test_mellum_arch import TINY as MELLUM
    a = serving.MellumArch(MELLUM)
    assert (a.gated, a.shared_width, a.dense_layers, a.share.scaling) \
        == (False, 0, [], 1.0)
    assert a.heads == (4,) * 5
    assert a.rotary["full_attention"][0].shape == (8,)
    assert not [k for k in a.param_shapes()
                if "attn_gate" in k or "shared_" in k or "ffn_gate" in k]


# ---------------------------------------------------------------------------
# what cannot run says so
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw, match", [
    (dict(spec_k=2, draft_params={}, draft_arch=None),
     "spec_k=2.*still inside the sliding window"),
    (dict(prefix_cache=True), "prefix cache.*last 8 positions"),
    (dict(contexts=2), "no model mesh"),
    (dict(quantize="int8"), "int8")])
def test_what_a_ring_forbids_is_refused(params, kw, match):
    if "draft_params" in kw:
        kw = dict(kw, draft_params=params,
                  draft_arch=serving.LagunaArch(TINY))
    with pytest.raises(MXNetError, match=match):
        _loop(params, **kw)


@pytest.mark.parametrize("fault, match", [
    ("missing", "layer4_shared_up_weight"), ("gate_shape",
                                             "layer2_attn_gate_weight"),
    ("lacks", "config lacks gating"), ("gating", "gating other than"),
    ("softcap", "soft cap"), ("on_input", "on the input"),
    ("heads", "do not divide"), ("short", "layer_types must name"),
    ("rotary", "no whole pairs")])
def test_bad_parameters_and_configs_are_named(params, fault, match):
    p, cfg = dict(params), dict(TINY)
    if fault == "missing":
        del p["layer4_shared_up_weight"]
    elif fault == "gate_shape":
        p["layer2_attn_gate_weight"] = p["layer2_attn_gate_weight"][:4]
    elif fault == "lacks":
        del cfg["gating"]
    elif fault == "gating":
        cfg["gating"] = "per-channel"
    elif fault == "softcap":
        cfg["moe_router_logit_softcapping"] = 30.0
    elif fault == "on_input":
        cfg["moe_apply_router_weight_on_input"] = True
    elif fault == "heads":
        cfg["num_attention_heads_per_layer"] = [4, 5, 6, 6] * 12
    elif fault == "short":
        cfg["layer_types"] = PERIOD
    else:
        cfg["rope_parameters"] = dict(ROPE, full_attention=dict(
            ROPE["full_attention"], partial_rotary_factor=0.3))
    with pytest.raises(MXNetError, match=match):
        _loop(p, cfg)


def test_the_module_is_exported_and_does_no_work_at_import():
    assert serving.LagunaArch is laguna.LagunaArch
    assert "LagunaArch" in serving.__all__
    assert issubclass(laguna.LagunaArch, serving.MellumArch)
