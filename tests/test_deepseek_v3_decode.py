"""The DeepSeek-V3 / Kimi-K2 block through ``DecodeLoop`` (docs/serving.md
"Architectures"; PERF.md, PR 29), at a tiny size on the CPU, against the
benchmark's plain reference (``benchmark/reference/kimi-k2-ep32.py``, loaded
by path: it imports nothing of ``mxnet_tpu``):

* the loop's greedy tokens, prompt fed and then decoding through the latent
  cache, are the reference's full forward's; the token pass's logits are
  the reference's at every position;
* absorbed MLA against naive MLA; the router; YaRN and the softmax scale;
* THE SHARES ADD UP: all shares of an expert layer, the shared expert
  counted once, give the uncut layer;
* the prefix cache and speculation emit the plain loop's stream on the
  latent state, greedy and sampled;
* the device's routing counters equal a host recount;
* under ``quantize="bf16"`` no float32 copy of a weight is made, on the
  host or in the step program.
"""
import importlib.util
import math
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.obs import trace as obs_trace
from mxnet_tpu.serving import decode
from mxnet_tpu.serving import deepseek_v3 as ds

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

YARN = {"beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
#: 16 experts top-4 with 1 shared, this share the second four; 1 dense + 2
#: expert layers
TINY = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    intermediate_size=96, moe_intermediate_size=32, num_experts_per_tok=4,
    n_shared_experts=1, n_routed_experts=4, router_width=16, share_index=1,
    first_k_dense_replace=1, num_hidden_layers=3, vocab_size=97,
    rms_norm_eps=1e-6, rope_theta=50000, routed_scaling_factor=2.827,
    scoring_func="sigmoid", n_group=1, topk_group=1, norm_topk_prob=True,
    dtype="float32", init_std=0.2, router_std=0.2, router_bias_std=0.2,
    rope_scaling=YARN)
KIMI = dict(TINY, hidden_size=7168, num_attention_heads=64, q_lora_rank=1536,
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, intermediate_size=18432,
            moe_intermediate_size=2048, num_experts_per_tok=8,
            n_routed_experts=12, router_width=384, share_index=0,
            num_hidden_layers=7, vocab_size=20480)
MAX_LEN, SLOTS = 48, 3
PROMPTS = [[5, 9, 11, 3, 8], [1, 2, 3], [40, 41, 42, 43, 44, 45, 46], [7]]


@pytest.fixture(scope="module")
def ref():
    path = os.path.join(ROOT, "benchmark", "reference", "kimi-k2-ep32.py")
    spec = importlib.util.spec_from_file_location("ref_kimi_k2_ep32", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params(ref):
    return ref.make_params(TINY, 7)


def _loop(params, cfg=TINY, **kw):
    kw.setdefault("prefix_cache", False)
    kw.setdefault("spec_k", 0)
    return serving.DecodeLoop(params, max_len=MAX_LEN, slots=SLOTS,
                              arch=serving.DeepseekV3Arch(cfg), **kw)


@pytest.fixture(scope="module")
def served(params):
    """The plain loop's streams over PROMPTS (more requests than slots, so
    slots are joined mid-stream), its health and its counters."""
    loop = _loop(params)
    futs = [loop.generate(p, 12) for p in PROMPTS]
    outs = [f.result(timeout=120) for f in futs]
    health, counts = loop.health.report(), loop.counter_totals()
    loop.close()
    return {"outs": outs, "health": health, "counts": counts}


# ---------------------------------------------------------------------------
# the loop against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_greedy_stream_is_the_references_full_forward(ref, params, served, i):
    prompt, out = PROMPTS[i], served["outs"][i]
    seq = jnp.asarray((prompt + out)[:-1], jnp.int32)
    logits = np.asarray(ref.forward(params, seq, TINY, "float32"))
    assert out == logits[len(prompt) - 1:].argmax(-1).tolist()


def test_token_pass_logits_through_the_latent_cache(ref, params):
    """Position by position through the cache, two slots at different
    positions, against the full forward at every position."""
    arch = serving.DeepseekV3Arch(TINY)
    step = jax.jit(arch.build_token_pass())
    seqs = [PROMPTS[0] + [17, 60, 2, 33], PROMPTS[2] + [4]]
    rows = 16
    state = {"latent": jnp.zeros((arch.num_layers, 2, rows,
                                  arch.latent_width), np.float32)}
    state.update({k: jnp.zeros(s, np.int32)
                  for k, s in arch.counters().items()})
    dev = {k: jnp.asarray(v) for k, v in params.items()}
    want = [np.asarray(ref.forward(params, jnp.asarray(s, jnp.int32), TINY))
            for s in seqs]
    for t in range(max(len(s) for s in seqs)):
        live = np.array([t < len(s) for s in seqs])
        toks = np.array([s[min(t, len(s) - 1)] for s in seqs], np.int32)
        pos = np.array([min(t, len(s) - 1) for s in seqs], np.int32)
        state, logits = step(state, dev, toks, pos, live)
        for j in range(2):
            if live[j]:
                np.testing.assert_allclose(np.asarray(logits)[j], want[j][t],
                                           rtol=2e-4, atol=2e-4)
    # the surplus lanes of the cache stay zero
    assert not np.asarray(state["latent"])[..., arch.latent:].any()


def test_absorbed_mla_is_naive_mla():
    rng = np.random.default_rng(0)
    slots, heads, rows, nope, rope, lora, vdim = 3, 4, 10, 8, 4, 16, 8
    q_nope = rng.standard_normal((slots, heads, nope)).astype(np.float32)
    q_pe = rng.standard_normal((slots, heads, rope)).astype(np.float32)
    lat = rng.standard_normal((slots, rows, lora + rope)).astype(np.float32)
    kv_b = rng.standard_normal((heads, nope + vdim, lora)).astype(np.float32)
    filled = np.array([10, 4, 1])
    tmask = np.arange(rows)[None, :] < filled[:, None]
    padded = np.pad(lat, [(0, 0), (0, 0), (0, 128 - lora - rope)])
    got = np.asarray(ds.mla_absorbed(q_nope, q_pe, jnp.asarray(padded), kv_b,
                                     jnp.asarray(tmask), 0.3, nope))
    # naive: K and V of every row and head, built
    c, k_pe = lat[..., :lora], lat[..., lora:]
    k_nope = np.einsum("stc,hdc->sthd", c, kv_b[:, :nope])
    v = np.einsum("stc,hdc->sthd", c, kv_b[:, nope:])
    s = (np.einsum("shd,sthd->sht", q_nope, k_nope)
         + np.einsum("shd,std->sht", q_pe, k_pe)) * 0.3
    s = np.where(tmask[:, None, :], s, -1e30)
    w = np.exp(s - s.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    want = np.einsum("sht,sthd->shd", w, v).reshape(slots, heads * vdim)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_router_selects_with_the_bias_and_weighs_without_it():
    f = np.eye(6, dtype=np.float32)[:2] * 4.0       # two one-hot rows
    weight = np.zeros((5, 6), np.float32)
    weight[:, 0] = [2.0, 1.0, 0.0, -1.0, -2.0]      # row 0's logits
    weight[:, 1] = [0.0, 0.0, 0.0, 0.0, 0.0]        # row 1: all 0.5
    bias = np.array([-10.0, 0.0, 0.0, 0.0, 10.0], np.float32)
    idx, w = ds.route(f, weight, bias, top_k=2, scaling=2.827)
    idx, w = np.asarray(idx), np.asarray(w)
    sig = 1.0 / (1.0 + np.exp(-np.array([8.0, 4.0, 0.0, -4.0, -8.0])))
    # row 0: the bias throws expert 0 (the best score) out and pulls expert
    # 4 (the worst) in; the weights are the SCORES, over their sum
    assert idx[0].tolist() == [4, 1]
    np.testing.assert_allclose(
        w[0], 2.827 * sig[[4, 1]] / sig[[4, 1]].sum(), rtol=1e-6)
    assert idx[1][0] == 4 and np.allclose(w[1], 2.827 * 0.5)
    # not normalised: the scores themselves, scaled
    _, w2 = ds.route(f, weight, bias, top_k=2, scaling=2.827,
                     normalise=False)
    np.testing.assert_allclose(np.asarray(w2)[0], 2.827 * sig[[4, 1]],
                               rtol=1e-6)
    # the chosen ones' weights sum to the scaling factor, over ALL chosen
    np.testing.assert_allclose(w.sum(-1), 2.827, rtol=1e-6)


def test_program_and_reference_route_alike(ref, params):
    f = np.random.default_rng(3).standard_normal((9, 64)).astype(np.float32)
    wt, b = params["layer1_router_weight"], params["layer1_router_bias"]
    a_idx, a_w = ds.route(f, wt, b, 4, 2.827)
    b_idx, b_w = ref.route(jnp.asarray(f), jnp.asarray(wt), jnp.asarray(b),
                           TINY)
    assert np.array_equal(np.asarray(a_idx), np.asarray(b_idx))
    np.testing.assert_allclose(np.asarray(a_w), np.asarray(b_w), rtol=1e-6)


def _share(full, cfg, k, held=4):
    """Share ``k``'s configuration and parameters, cut from the uncut."""
    cfg = dict(cfg, n_routed_experts=held, router_width=16, share_index=k)
    p = dict(full)
    for name in ("gate", "up", "down"):
        key = "layer1_experts_%s_weight" % name
        p[key] = full[key][held * k:held * k + held]
    return cfg, p


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """One dense and ONE expert layer, so a share's partial sum reaches the
    output without passing another router. With ``x_k`` share k's output,
    ``x_0`` the output with the routed experts weighted 0 (the residual,
    attention and the shared expert: what every chip computes alike) and
    ``x`` the uncut layer's: ``sum_k (x_k - x_0) + x_0 == x``."""
    uncut = dict(TINY, num_hidden_layers=2, n_routed_experts=16)
    del uncut["router_width"], uncut["share_index"]
    full = ref.make_params(uncut, 11)
    toks = jnp.asarray([3, 70, 12, 12, 9, 55, 1], jnp.int32)

    def last_layer(cfg, p):
        taps = {}
        ref.forward(p, toks, cfg, "float32", taps=taps)
        return np.asarray(taps["layers"][-1])

    x = last_layer(uncut, full)
    x0 = last_layer(dict(uncut, routed_scaling_factor=0.0), full)
    parts = [last_layer(*_share(full, uncut, k)) for k in range(4)]
    assert min(np.abs(pk - x0).max() for pk in parts) > 1e-2
    np.testing.assert_allclose(sum(pk - x0 for pk in parts) + x0, x,
                               rtol=2e-5, atol=2e-5)


def test_the_programs_shares_add_up_too(ref):
    """The program's expert layer: route once over all 16, let each of the
    four shares add its own experts' terms, and the shared expert once."""
    uncut = dict(TINY, num_hidden_layers=2, n_routed_experts=16)
    del uncut["router_width"], uncut["share_index"]
    p = ref.make_params(uncut, 12)
    f = np.random.default_rng(5).standard_normal((7, 64)).astype(np.float32)
    gate, up, down = (p["layer1_experts_%s_weight" % n]
                      for n in ("gate", "up", "down"))
    idx, w = ds.route(f, p["layer1_router_weight"], p["layer1_router_bias"],
                      4, 2.827)
    total = ds.swiglu(f, p["layer1_shared_gate_weight"],
                      p["layer1_shared_up_weight"],
                      p["layer1_shared_down_weight"])
    served = 0
    for k in range(4):
        hit, dense = ds.held_weights(idx, w, 4 * k, 4)
        served += int(np.asarray(hit).sum())
        total = total + ds.held_experts(f, dense, gate[4 * k:4 * k + 4],
                                        up[4 * k:4 * k + 4],
                                        down[4 * k:4 * k + 4])
    assert served == 7 * 4          # every (token, choice) pair, once

    def expert(j, x):
        g, u = x @ gate[j].T, x @ up[j].T
        return (g / (1 + np.exp(-g)) * u) @ down[j].T

    idx, w = np.asarray(idx), np.asarray(w)
    want = np.asarray(ds.swiglu(f, p["layer1_shared_gate_weight"],
                                p["layer1_shared_up_weight"],
                                p["layer1_shared_down_weight"])).copy()
    for t in range(7):
        for j, wj in zip(idx[t], w[t]):
            want[t] += wj * expert(int(j), f[t:t + 1])[0]
    np.testing.assert_allclose(np.asarray(total), want, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------

def test_yarn_frequencies_and_softmax_scale_are_the_formulas(ref):
    arch = serving.DeepseekV3Arch(KIMI)
    # 64 rope dims, theta 50000, factor 32 over 4096, beta_fast = beta_slow
    # = 1: the pair that turns once over 4096 positions is
    # 64 ln(4096 / 2 pi) / (2 ln 50000) = 19.16, so pairs 0..19 keep their
    # frequency and pairs 20..31 are divided by 32
    plain = 50000.0 ** (-np.arange(0, 64, 2) / 64.0)
    turn = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(50000))
    assert 19 < turn < 20
    np.testing.assert_allclose(arch.inv_freq[:20], plain[:20], rtol=1e-12)
    np.testing.assert_allclose(arch.inv_freq[20:], plain[20:] / 32,
                               rtol=1e-12)
    np.testing.assert_allclose(arch.inv_freq, ref.yarn_inv_freq(KIMI),
                               rtol=1e-12)
    want = 192 ** -0.5 * (0.1 * math.log(32) + 1) ** 2
    assert abs(want - 0.1309) < 5e-5
    assert abs(arch.softmax_scale - want) < 1e-12
    assert abs(ref.softmax_scale(KIMI) - want) < 1e-12
    assert arch.rope_scale == ref.rope_scale(KIMI) == 1.0   # mscale == all_dim
    # without scaling: plain frequencies and the plain scale
    bare = serving.DeepseekV3Arch(dict(KIMI, rope_scaling=None))
    np.testing.assert_allclose(bare.inv_freq, plain, rtol=1e-12)
    assert abs(bare.softmax_scale - 192 ** -0.5) < 1e-12


def test_rope_rotates_interleaved_pairs():
    x = np.arange(8, dtype=np.float32)[None, :]
    ang = np.array([[0.0, np.pi / 2, np.pi, 0.3]], np.float32)
    got = np.asarray(ds.rope(x, np.cos(ang), np.sin(ang)))[0]
    pairs = x[0].reshape(4, 2)
    want = np.stack([pairs[:, 0] * np.cos(ang[0]) - pairs[:, 1] * np.sin(ang[0]),
                     pairs[:, 1] * np.cos(ang[0]) + pairs[:, 0] * np.sin(ang[0])],
                    -1).reshape(8)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(got[:2], [0.0, 1.0], atol=1e-6)
    np.testing.assert_allclose(got[2:4], [-3.0, 2.0], atol=1e-5)


# ---------------------------------------------------------------------------
# prefix cache, speculation, sampling on the latent state
# ---------------------------------------------------------------------------

SHARED = [21, 22, 23, 24, 25, 26]
SAMPLING = {"greedy": {}, "sampled": {"temperature": 0.8, "top_k": 20,
                                      "top_p": 0.9, "seed": 1234}}


@pytest.fixture(scope="module")
def plain_streams(params):
    loop = _loop(params)
    out = {}
    for name, kw in SAMPLING.items():
        out[name] = [loop.generate(SHARED + tail, 10, **kw).result(120)
                     for tail in ([3], [4, 5], [3])]
    loop.close()
    return out


@pytest.mark.parametrize("sampling", sorted(SAMPLING))
def test_prefix_cache_emits_the_plain_stream(params, plain_streams, sampling):
    loop = _loop(params, prefix_cache=True)
    kw = SAMPLING[sampling]
    got = []
    for tail in ([3], [4, 5], [3]):     # in turn: the first stores
        got.append(loop.generate(SHARED + tail, 10, prefix_len=len(SHARED),
                                 **kw).result(120))
    rep = loop.health.report()
    (entry,) = loop._prefix.values()
    loop.close()
    assert got == plain_streams[sampling]
    assert rep["prefix_prefills"] == 1 and rep["prefix_hits"] == 2
    # the slab is the slot's latent rows and nothing else
    assert sorted(entry["target"]) == ["latent"]
    assert entry["target"]["latent"].shape == (3, MAX_LEN, 128)


@pytest.mark.parametrize("draft", ["itself", "smaller"])
@pytest.mark.parametrize("sampling", sorted(SAMPLING))
def test_speculation_emits_the_plain_stream(ref, params, plain_streams,
                                            sampling, draft):
    if draft == "itself":
        dcfg, dparams = TINY, params
    else:
        dcfg = dict(TINY, num_hidden_layers=2)
        dparams = ref.make_params(dcfg, 8)
    loop = _loop(params, spec_k=2, draft_params=dparams,
                 draft_arch=serving.DeepseekV3Arch(dcfg))
    kw = SAMPLING[sampling]
    got = [loop.generate(SHARED + tail, 10, **kw).result(120)
           for tail in ([3], [4, 5], [3])]
    rep = loop.health.report()
    loop.close()
    assert got == plain_streams[sampling]
    assert rep["spec_rounds"] > 0
    if draft == "itself":
        assert rep["spec_accepted"] == rep["spec_drafted"] > 0


def test_speculation_over_this_architecture_wants_the_drafts_own(params):
    with pytest.raises(MXNetError, match="draft_arch"):
        _loop(params, spec_k=2, draft_params=params)


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

def test_routing_counters_equal_a_host_recount(ref, params, served):
    """Every position the loop processed (a request's prompt and all but
    the last of its tokens), through the reference's router."""
    want = np.zeros((2, 4), np.int64)
    positions = 0
    for prompt, out in zip(PROMPTS, served["outs"]):
        seq = jnp.asarray((prompt + out)[:-1], jnp.int32)
        taps = {}
        ref.forward(params, seq, TINY, "float32", taps=taps)
        positions += len(seq)
        for m, idx in enumerate(taps["chosen"]):
            local = np.asarray(idx) - 4            # share 1 holds 4..7
            for j in range(4):
                want[m, j] += int((local == j).sum())
    counts = served["counts"]
    assert np.array_equal(counts["moe_served"], want)
    assert counts["moe_routed"].tolist() == [4 * positions] * 2
    health = served["health"]
    assert health["moe_pairs_routed"] == 2 * 4 * positions
    assert health["moe_pairs_here"] == int(want.sum())
    assert health["moe_busiest_expert"] == int(want.max())
    assert health["prompt_positions"] + health["tokens_emitted"] == positions


@pytest.fixture(scope="module")
def eos_served(params, served):
    """The same requests with ``eos_id`` a token that ends some of the
    plain streams early: the loop runs one step ahead of its readback, so
    it learns of an ``eos`` one step late (docs/serving.md)."""
    outs = served["outs"]
    for eos in range(TINY["vocab_size"]):
        cut = [o.index(eos) + 1 if eos in o else len(o) for o in outs]
        late = sum(c < len(o) for c, o in zip(cut, outs))
        if 1 <= late < len(outs):
            break
    else:
        raise AssertionError("no token ends some plain stream early")
    loop = _loop(params, eos_id=eos)
    futs = [loop.generate(p, 12) for p in PROMPTS]
    got = [f.result(timeout=120) for f in futs]
    # a trash slot-step is counted when it is read back, after its request
    # has its tokens: wait for the loop to drain the step in flight
    deadline = time.monotonic() + 10.0
    while loop._inflight is not None and time.monotonic() < deadline:
        time.sleep(0.001)
    health, counts = loop.health.report(), loop.counter_totals()
    loop.close()
    return {"eos": eos, "cut": cut, "late": late, "outs": got,
            "health": health, "counts": counts}


@pytest.mark.parametrize("i", range(len(PROMPTS)))
def test_run_ahead_delivers_nothing_after_eos(served, eos_served, i):
    """And the next occupant of a slot whose last slot-step was trash (its
    latent row written one past the ``eos``) decodes the plain stream."""
    assert eos_served["outs"][i] == served["outs"][i][:eos_served["cut"][i]]


def test_a_trash_slot_step_is_counted_as_routed_work(eos_served):
    """``live`` gates the device's routing counters and a slot-step that
    turns out to be trash was dispatched live: the counters hold every
    position processed AND the late slot-steps."""
    h, late = eos_served["health"], eos_served["late"]
    assert h["trash_slot_steps"] == late
    assert h["tokens_emitted"] == sum(len(o) for o in eos_served["outs"])
    positions = sum(len(p) + len(o) - 1
                    for p, o in zip(PROMPTS, eos_served["outs"]))
    assert h["prompt_positions"] + h["tokens_emitted"] == positions
    assert eos_served["counts"]["moe_routed"].tolist() \
        == [4 * (positions + late)] * 2
    assert h["steps_ahead"] > 0 and h["joined"] == h["retired"] == 4


def test_health_counts_increments_and_mirrors_them(params):
    parent = serving.ServingHealth()
    health = serving.ServingHealth(parent=parent)
    loop = _loop(params, health=health)
    loop.generate([1, 2, 3], 4).result(120)
    first = health.report()["moe_pairs_routed"]
    assert first == 2 * 4 * 6                  # 3 prompt + 3 fed tokens
    assert health.report()["moe_pairs_routed"] == first     # no double count
    loop.generate([1, 2, 3], 4).result(120)
    loop.close()                               # the last counts, then let go
    assert health.report()["moe_pairs_routed"] == 2 * first
    assert parent.report()["moe_pairs_routed"] == 2 * first
    assert health._sources == []


def test_counters_are_read_from_other_threads_while_the_loop_steps(params):
    """Readers hold the lock the step donates under: no reader ever meets a
    donated buffer, no count is lost, and the loop lives."""
    import sys
    import threading
    loop = _loop(params)
    stop, seen, errors = threading.Event(), [], []

    def reader():
        try:
            while not stop.is_set():
                seen.append(int(loop.counter_totals()["moe_routed"].sum()))
                loop.health.report()
        except Exception as e:      # a donated buffer would raise here
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=reader) for _ in range(4)]
    try:
        for t in threads:
            t.start()
        futs = [loop.generate([3, 1, 4, 1, 5], 20) for _ in range(6)]
        for f in futs:
            f.result(timeout=120)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == [] and loop.dead is None
    rep = loop.health.report()
    loop.close()
    assert rep["moe_pairs_routed"] == 2 * 4 * 6 * (5 + 19)
    assert seen and max(seen) <= rep["moe_pairs_routed"]
    assert loop.health.report()["moe_pairs_routed"] == rep["moe_pairs_routed"]


def test_a_traced_run_carries_the_scope_table_and_counter_spans(params,
                                                                monkeypatch):
    monkeypatch.setattr(decode, "COUNTER_SPAN_STEPS", 4)
    loop = _loop(params)
    obs_trace.clear()
    obs_trace.start()
    try:
        loop.generate([9, 8, 7], 14).result(120)
    finally:
        obs_trace.stop()
        evs = [e for e in obs_trace.events() if e.get("ph") == "X"]
        obs_trace.clear()
        loop.close()
    (prog,) = [e for e in evs if e["name"] == "loop_program"]
    assert prog["args"]["program"] == "jit_decode_fn"
    kinds = set(prog["args"]["scopes"].values())
    for scope in ("layer/mla", "cache_write", "layer/mlp", "layer/moe/router",
                  "layer/moe/experts", "layer/moe/shared", "sample"):
        assert any(k.startswith(scope) for k in kinds), (scope, kinds)
    snaps = [e["args"] for e in evs if e["name"] == "loop_counters"]
    assert len(snaps) >= 3 and all(s["step"] % 4 == 0 for s in snaps)
    routed = [sum(s["moe_routed"]) for s in snaps]
    assert routed == sorted(routed) and routed[-1] > routed[0]
    # the step's own span keeps its arguments and laps
    steps = [e for e in evs if e["name"] == "decode_step"]
    assert steps and all({"pos", "n", "emit", "laps"} <= set(e["args"])
                         for e in steps)


# ---------------------------------------------------------------------------
# bfloat16: no float32 copy of a weight
# ---------------------------------------------------------------------------

def test_bf16_leaves_stay_bf16_on_the_host_and_in_the_step(ref):
    cfg = dict(TINY, dtype="bfloat16")
    p = ref.make_params(cfg, 7)
    assert all(str(v.dtype) == "bfloat16" for v in p.values())
    assert decode._host_leaf(p["lm_head_weight"], "bf16") \
        is p["lm_head_weight"]
    assert decode._host_leaf(p["lm_head_weight"], "none").dtype == np.float32
    loop = _loop(p, cfg, quantize="bf16")
    try:
        assert all(str(v.dtype) == "bfloat16" for v in loop._params.values())
        assert str(loop._state["latent"].dtype) == "bfloat16"
        assert loop._rows % 16 == 0         # two-byte tiles hold 16 rows
        assert loop.weight_bytes() == 2 * ref.param_count(cfg)
        text = loop._step_c.as_text()
        # experts, dense ffn: no float32 copy (XLA:CPU converts the rows it
        # gathers from the embedding inside the gather's fusion)
        for shape in ("f32[4,32,64]", "f32[4,64,32]", "f32[96,64]"):
            assert shape not in text, shape
        out = loop.generate(PROMPTS[0], 12).result(120)
    finally:
        loop.close()
    seq = jnp.asarray((PROMPTS[0] + out)[:-1], jnp.int32)
    logits = np.asarray(ref.forward(p, seq, cfg, "float32"))[4:]
    gap = logits.max(-1) - logits[np.arange(12), out]
    assert gap.max() < 0.15 and np.mean(gap * gap) < 1e-3


def test_hot_reload_swaps_the_weights_under_the_running_loop(ref, params):
    other = ref.make_params(TINY, 99)
    loop = _loop(params)
    try:
        a = loop.generate(PROMPTS[1], 8).result(120)
        loop.update_params(other)
        b = loop.generate(PROMPTS[1], 8).result(120)
    finally:
        loop.close()
    fresh = _loop(other)
    want = fresh.generate(PROMPTS[1], 8).result(120)
    fresh.close()
    assert b == want and a != b


# ---------------------------------------------------------------------------
# what cannot run yet says so
# ---------------------------------------------------------------------------

def test_a_model_mesh_over_this_architecture_is_refused(params):
    with pytest.raises(MXNetError, match="no model mesh"):
        _loop(params, contexts=2)


def test_int8_is_refused(params):
    with pytest.raises(MXNetError, match="int8"):
        _loop(params, quantize="int8")


@pytest.mark.parametrize("fault, match", [
    ("missing", "layer2_router_bias"), ("shape", "layer1_experts_gate_weight"),
    ("share", "outside the router"), ("router", "sigmoid router"),
    ("layers", "num_layers")])
def test_bad_parameters_and_configs_are_named(params, fault, match):
    p, cfg, kw = dict(params), dict(TINY), {}
    if fault == "missing":
        del p["layer2_router_bias"]
    elif fault == "shape":
        p["layer1_experts_gate_weight"] = p["layer1_experts_gate_weight"][:3]
    elif fault == "share":
        cfg["share_index"] = 4
    elif fault == "router":
        cfg["scoring_func"] = "softmax"
    else:
        kw["num_layers"] = 5
    with pytest.raises(MXNetError, match=match):
        serving.DecodeLoop(p, max_len=MAX_LEN, slots=SLOTS,
                           arch=serving.DeepseekV3Arch(cfg),
                           prefix_cache=False, spec_k=0, **kw)


# ---------------------------------------------------------------------------
# the reference's own accounts
# ---------------------------------------------------------------------------

def test_the_cells_cut_is_what_the_issue_reckoned(ref):
    shapes = ref.param_shapes(KIMI)
    assert shapes == serving.DeepseekV3Arch(KIMI).param_shapes()
    count = ref.param_count(KIMI)
    assert abs(count - 4849.5e6) < 1e6                  # 9.70 GB in bf16
    assert abs(ref.weight_bytes(KIMI) - 9.41e9) < 0.01e9
    flops = ref.flops_per_position(KIMI, 1)
    assert abs(flops - 3.19e9) < 0.02e9
    f, b = ref.step_work(KIMI, [100] * 64)
    assert f == 64 * ref.flops_per_position(KIMI, 100)
    assert b == ref.weight_bytes(KIMI) + 64 * (7 * 101 * 576 * 2 + 7168 * 2)
    mf, mb = ref.moe_layer_work(KIMI, 64)
    assert abs(mb - 6 * 2 * (2.75e6 + 13 * 44.04e6)) < 6 * 2 * 0.02e6 * 14
    assert abs(mf - 64 * 6 * 2 * (2.75e6 + 1.25 * 44.04e6)) < 1e9
    af, ab = ref.mla_layer_work(KIMI, [100] * 64)
    assert abs(ab - 7 * (2 * 101.1e6 + 64 * 101 * 576 * 2)) < 7 * 1e6
    assert af > 7 * 64 * 2 * 101e6


def test_make_params_is_seeded_and_made_on_the_device(ref):
    a, b = ref.make_params(TINY, 5), ref.make_params(TINY, 5)
    c = ref.make_params(TINY, 3000029011)       # a seed past 2**31
    assert all(isinstance(v, jax.Array) for v in a.values())
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["lm_head_weight"], c["lm_head_weight"])
    assert not np.array_equal(a["layer1_shared_gate_weight"],
                              a["layer1_shared_up_weight"])     # same shape
    w = np.asarray(a["lm_head_weight"])
    assert abs(float(np.std(w)) - 0.2) < 0.02
    assert abs(float(np.mean(a["final_norm_gamma"])) - 1.0) < 0.1
    p = ref.make_params(dict(TINY, dtype="bfloat16"), 5)
    assert all(str(v.dtype) == "bfloat16" for v in p.values())


def test_a_bf16_device_leaf_is_served_where_it_is(ref):
    """No trip to the host and back: the loop's parameter IS the array it
    was given, and a hot reload takes device arrays the same way."""
    cfg = dict(TINY, dtype="bfloat16")
    p, q = ref.make_params(cfg, 5), ref.make_params(cfg, 6)
    loop = _loop(p, cfg, quantize="bf16")
    try:
        assert all(loop._params[k] is p[k] for k in p)
        a = loop.generate(PROMPTS[1], 6).result(120)
        loop.update_params(q)
        assert all(str(v.dtype) == "bfloat16" for v in loop._params.values())
        b = loop.generate(PROMPTS[1], 6).result(120)
    finally:
        loop.close()
    fresh = _loop(q, cfg, quantize="bf16")
    want = fresh.generate(PROMPTS[1], 6).result(120)
    fresh.close()
    assert b == want and a != b


# ---------------------------------------------------------------------------
# chip_smoke's latent leg, tiny
# ---------------------------------------------------------------------------

def test_chip_smokes_latent_leg_tiny(capsys):
    import chip_smoke
    meter = chip_smoke.CompileMeter(None)
    cfg = {k: v for k, v in TINY.items() if k in chip_smoke.KIMI_K2_DEPTH2}
    facts = chip_smoke.latent_decode_leg(meter, cfg, max_len=48, slots=3,
                                         requests=5, prompt_range=(6, 14),
                                         max_new=4)
    assert facts["decode_steps"] > 0
    assert facts["step_program"]["cache_bytes"] == 3 * 3 * 48 * 128 * 2
    # some prompts are long enough to be due: they went in by packed passes
    assert 0 < facts["prefill_passes"] <= facts["prefill_slots"]
    assert facts["prefill_positions"] >= 8 * facts["prefill_passes"] \
        or facts["prefill_slots"] > facts["prefill_passes"]
    assert facts["prefill_program"]["rows"] == 48
    assert facts["prefill_program"]["cache_bytes"] == 3 * 3 * 48 * 128 * 2
    assert facts["moe_pairs_routed"] > facts["moe_pairs_here"] >= 0
    assert set(chip_smoke.KIMI_K2_DEPTH2) <= set(KIMI) | {"rope_scaling"}
    assert all(KIMI[k] == v for k, v in chip_smoke.KIMI_K2_DEPTH2.items()
               if k != "num_hidden_layers")
