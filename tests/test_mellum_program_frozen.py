"""The Mellum step program is held to the form it had before the Laguna
block joined it (PERF.md).

``MellumArch``'s token pass also runs the Laguna block (per-layer head
counts, a per-head gate, partial rotary positions, dense layers, a shared
expert, a routed scaling): what Mellum's config does not set must leave
Mellum's program as it was. Below is a FROZEN copy of ``serving/mellum.py``'s
token pass as it stood at commit 2237fe6 (``ring_row`` written out), and of
``blocks.rope_half`` then, which now also rotates the leading lanes of a
head; the other helpers it calls (``rms_norm``, ``linear``,
``gqa_attention``, ``over_filled_rows``, ``routed_share``) are the live
ones, which the Laguna block left as they were. The live program LOWERS TO
THE SAME TEXT as the frozen one, in float32 and bfloat16, and gives the
same logits, state and counters bit for bit over a walk that wraps the
ring."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import serving
from mxnet_tpu.serving.blocks import (gqa_attention, linear, over_filled_rows,
                                      rms_norm, routed_share)

from test_mellum_arch import TINY, _load


# ---- frozen at commit 2237fe6: do not edit --------------------------------
def _rope_half(x, cos, sin):
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _build_token_pass(self):
    f32 = jnp.float32
    heads, groups, hd = (self.num_heads, self.num_key_value_heads,
                         self.head_dim)
    eps, window, scale = self.eps, self.sliding_window, hd ** -0.5
    names = {"sliding_attention": ("k_win", "v_win", "window"),
             "full_attention": ("k", "v", "full")}
    own = {"sliding_attention":
           {i: n for n, i in enumerate(self.window_layers)},
           "full_attention":
           {i: n for n, i in enumerate(self.full_layers)}}

    def token_pass(state, params, tokens, pos, live):
        state = dict(state)
        nslots = tokens.shape[0]
        sidx = jnp.arange(nslots)
        with jax.named_scope("embed"):
            x = params["tok_embed_weight"][tokens].astype(f32)
        kinds = {}
        for kind in own:
            if not own[kind]:
                continue
            depth = state[names[kind][0]].shape[2]
            at = pos % window if kind == "sliding_attention" else pos
            inv_freq, factor = self.rotary[kind]
            with jax.named_scope("embed"):
                angle = pos.astype(f32)[:, None] \
                    * np.asarray(inv_freq, np.float32)[None, :]
                cos = (f32(factor) * jnp.cos(angle))[:, None]
                sin = (f32(factor) * jnp.sin(angle))[:, None]
            kinds[kind] = (jnp.minimum(at, jnp.int32(depth - 1)), cos,
                           sin, over_filled_rows(pos, depth))
        counts = (state["moe_served"], state["moe_routed"])
        nlive = jnp.sum(live.astype(jnp.int32))
        for i, kind in enumerate(self.layer_types):
            def p(name, pre="layer%d_" % i):
                return params[pre + name]
            n = own[kind][i]
            kname, vname, tag = names[kind]
            wrow, cos, sin, over = kinds[kind]
            with jax.named_scope("layer/attn/" + tag):
                a = rms_norm(x, p("op_norm_gamma"), eps)
                q = rms_norm(linear(a, p("attn_q_weight")).reshape(
                    nslots, heads, hd), p("attn_q_norm_gamma"), eps)
                k = rms_norm(linear(a, p("attn_k_weight")).reshape(
                    nslots, groups, hd), p("attn_k_norm_gamma"), eps)
                q, k = _rope_half(q, cos, sin), _rope_half(k, cos, sin)
                v = linear(a, p("attn_v_weight"))
            with jax.named_scope("cache_write/kv/" + tag):
                ck = state[kname].at[n, sidx, wrow].set(
                    k.reshape(nslots, -1).astype(state[kname].dtype))
                cv = state[vname].at[n, sidx, wrow].set(
                    v.astype(state[vname].dtype))
                state[kname], state[vname] = ck, cv
            with jax.named_scope("layer/attn/" + tag):
                o = over((ck, cv), n,
                         lambda mask, kr, vr: gqa_attention(
                             q, kr, vr, mask, scale))
                x = x + linear(o, p("attn_out_weight"))
            _, y, counts = routed_share(x, p, self.share, live, nlive,
                                        counts, i)
            x = x + y
        with jax.named_scope("head"):
            logits = linear(
                rms_norm(x, params["final_norm_gamma"], eps),
                params["tok_embed_weight" if self.tied
                       else "lm_head_weight"])
        state.update(moe_served=counts[0], moe_routed=counts[1])
        return state, logits

    return token_pass


# ---- end of the frozen copy ------------------------------------------------

SLOTS, ROWS = 3, 48


@pytest.fixture(scope="module")
def ref():
    return _load("mellum2-12b-a2.5b-ep4")


def _args(arch, ref, dtype):
    cfg = dict(TINY, dtype=dtype)
    params = arch.load(ref.make_params(cfg, 7))
    quant = "bf16" if dtype == "bfloat16" else "none"
    state = {k: jnp.zeros((a.layers, SLOTS, a.depth(ROWS), a.width), a.dtype)
             for k, a in arch.slot_state(None, quant).items()}
    state.update({k: jnp.zeros(s, np.int32)
                  for k, s in arch.counters().items()})
    return state, params


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_step_lowers_to_the_parents_text(ref, dtype):
    arch = serving.MellumArch(TINY)
    state, params = _args(arch, ref, dtype)
    feed = [jnp.zeros((SLOTS,), np.int32), jnp.zeros((SLOTS,), np.int32),
            jnp.ones((SLOTS,), bool)]
    new = jax.jit(arch.build_token_pass()).lower(state, params, *feed)
    old = jax.jit(_build_token_pass(arch)).lower(state, params, *feed)
    assert new.as_text() == old.as_text()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_step_gives_the_parents_values(ref, dtype):
    """Three slots, one of which runs past the window of 8 twice over
    while another starts late: logits, both arrays and both counters equal
    BIT FOR BIT at every step."""
    arch = serving.MellumArch(TINY)
    state, params = _args(arch, ref, dtype)
    new = jax.jit(arch.build_token_pass())
    old = jax.jit(_build_token_pass(arch))
    rng = np.random.default_rng(2)
    a = b = state
    for t in range(22):
        toks = jnp.asarray(rng.integers(0, 97, SLOTS), jnp.int32)
        pos = jnp.asarray([t, max(0, t - 9), min(t, 5)], jnp.int32)
        live = jnp.asarray([True, t >= 9, t < 6])
        a, la = new(a, params, toks, pos, live)
        b, lb = old(b, params, toks, pos, live)
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
    for k in b:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert int(np.asarray(a["moe_routed"]).sum()) > 0
