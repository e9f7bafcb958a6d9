"""The DeepSeek-V3 block (``modeling_deepseek.py``; ``model_type: kimi_k2``
uses it unchanged) for :class:`~mxnet_tpu.serving.decode.DecodeLoop`:
RMSNorm, rotary positions with YaRN, SwiGLU, latent attention (MLA) in its
ABSORBED decode form over a latent cache, the sigmoid router with its
selection bias, and a routed-expert layer that is told which experts it
holds (docs/serving.md "Architectures").

Parameter names (the repo's): ``tok_embed_weight``, ``final_norm_gamma``,
``lm_head_weight`` and per layer ``layer{i}_`` + ``attn_norm_gamma``,
``attn_q_a_weight`` (q_lora, hidden), ``attn_q_a_norm_gamma``,
``attn_q_b_weight`` (heads * (nope + rope), q_lora), ``attn_kv_a_weight``
(kv_lora + rope, hidden), ``attn_kv_a_norm_gamma``, ``attn_kv_b_weight``
(heads * (nope + v), kv_lora), ``attn_out_weight`` (hidden, heads * v),
``ffn_norm_gamma``; a dense layer ``ffn_{gate,up,down}_weight``; an expert
layer ``router_weight`` (router_width, hidden), ``router_bias``,
``shared_{gate,up,down}_weight`` and the HELD experts stacked:
``experts_{gate,up}_weight`` (held, width, hidden), ``experts_down_weight``
(held, hidden, width). Every matrix is (out, in).

**The latent cache.** A position leaves ``kv_lora_rank + qk_rope_head_dim``
values per layer (Kimi-K2: 576, against 2 * 64 * 128 for K and V): the
normed latent ``c'`` and the rotated shared ``k_pe``, in one array
``latent`` of ``(layers, slots, rows, width)``, the width minor so that a
position's write is one contiguous row (PERF.md, PR 28). ``width`` is the
576 rounded up to whole 128-lane tiles, 640, the surplus lanes zero: at
576 the chip stores the array ROWS minor (1024 rows fill its lanes, 576
does not), and the step program, which writes rows, converts the whole
cache to width-minor on entry and back on exit and one layer's slab
before every scores product (PERF.md, PR 29). No K and no V is
ever built: ``q_nope`` goes through the head's K half of ``W_kvb`` into the
latent space, the scores are one product of ``[q_lat, q_pe]`` with the
rows, and the weighted sum of ``c'`` goes through the V half.

**The share.** ``n_routed_experts`` of the config is how many experts this
chip HOLDS: indices ``share_index * n ..`` of the ``router_width`` experts
the router ranks. The layer routes over all of them, adds only its own
experts' terms for the (token, choice) pairs that chose them, plus the
shared expert, and THAT partial sum goes on (:mod:`.blocks` has the layer,
shared with :mod:`.lfm2`, and says what it leaves out).

**Precision.** The operands of every weight product and of the two cache
products are the STORED dtype (bfloat16 under ``quantize="bf16"``: no
float32 copy of a weight is ever made), accumulation float32; norms,
softmax, the router (its product at ``HIGHEST``) and the residual stream
float32.

**Counters**: ``moe_served`` and ``moe_routed`` of :mod:`.blocks`.
"""
from __future__ import annotations

import math

import numpy as np

from ..base import MXNetError
from .arch import PER_POSITION, Architecture, SlotArray
# shared with serving/lfm2.py (these names stay this module's too)
from .blocks import (ExpertShare, held_experts, held_weights,  # noqa: F401
                     linear, moe_counters, over_filled_rows, record_moe,
                     rms_norm, route, routed_share, swiglu, validate_share,
                     yarn_inv_freq)

_KEYS = ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
         "n_shared_experts", "n_routed_experts", "first_k_dense_replace",
         "num_hidden_layers", "vocab_size")


def yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rope(x, cos, sin):
    """Rotate the interleaved pairs ``(x[2i], x[2i+1])`` of the minor
    dimension by the angles whose cos and sin are given per pair."""
    import jax.numpy as jnp
    shape = x.shape
    x = x.reshape(shape[:-1] + (shape[-1] // 2, 2))
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(shape)


def _pad_lanes(x, width):
    """``x`` with zeros appended to its minor dimension up to ``width``."""
    import jax.numpy as jnp
    pad = width - x.shape[-1]
    return x if not pad else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def mla_absorbed(q_nope, q_pe, rows, kv_b, tmask, scale, nope):
    """Latent attention of one position per slot over its ``rows``
    ``(slots, rows, width)`` (``kv_lora + rope`` values and zero lanes up
    to whole tiles), the absorbed form: ``q_nope``
    (slots, heads, nope) through the K half of ``kv_b`` (heads, nope + v,
    kv_lora), scores of ``[q_lat, q_pe]`` with the rows, float32 softmax
    under ``tmask`` (slots, rows), the mix of the rows' latent part through
    the V half. Returns (slots, heads * v) float32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    op = rows.dtype
    lora = kv_b.shape[-1]
    # heads lead both operands: XLA:CPU has no bfloat16 product for the
    # "shd,hdc" order (the tier-1 tests run this path in bfloat16 too)
    q_lat = jnp.einsum("hsd,hdc->shc", q_nope.astype(op).swapaxes(0, 1),
                       kv_b[:, :nope], preferred_element_type=f32)
    qc = _pad_lanes(jnp.concatenate([q_lat, q_pe], axis=-1),
                    rows.shape[-1]).astype(op)
    s = jnp.einsum("shc,stc->sht", qc, rows,
                   preferred_element_type=f32) * f32(scale)
    s = jnp.where(tmask[:, None, :], s, f32(-1e30))
    w = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("sht,stc->shc", w.astype(op), rows[..., :lora],
                       preferred_element_type=f32)
    o = jnp.einsum("shc,hdc->shd", o_lat.astype(op), kv_b[:, nope:],
                   preferred_element_type=f32)
    return o.reshape(o.shape[0], -1)


class DeepseekV3Arch(Architecture):
    """The DeepSeek-V3 / Kimi-K2 block from its ``config.json`` keys, with
    two of this repo's: ``router_width`` (the experts the router ranks;
    default ``n_routed_experts``: nothing cut) and ``share_index`` (which
    ``n_routed_experts`` of them are held here; default 0)."""

    name = "deepseek_v3"
    wants_live = True
    packed_prefill = True

    def __init__(self, config):
        missing = [k for k in _KEYS + ("rms_norm_eps", "rope_theta",
                                       "routed_scaling_factor")
                   if k not in config]
        if missing:
            raise MXNetError("DeepseekV3Arch: config lacks %s"
                             % ", ".join(missing))
        for k in _KEYS:
            setattr(self, k, int(config[k]))
        if config.get("scoring_func", "sigmoid") != "sigmoid" \
                or int(config.get("n_group", 1)) != 1 \
                or int(config.get("topk_group", 1)) != 1:
            raise MXNetError(
                "DeepseekV3Arch: only the sigmoid router without group "
                "limits (n_group = topk_group = 1) is implemented")
        self.num_layers = self.num_hidden_layers
        self.num_heads = self.num_attention_heads
        self.eps = float(config["rms_norm_eps"])
        self.routed_scaling = float(config["routed_scaling_factor"])
        self.norm_topk_prob = bool(config.get("norm_topk_prob", True))
        self.router_width = int(config.get("router_width",
                                           self.n_routed_experts))
        self.share_index = int(config.get("share_index", 0))
        self.first_expert = self.share_index * self.n_routed_experts
        self.share = ExpertShare(
            self.num_experts_per_tok, self.routed_scaling,
            self.norm_topk_prob, 1e-20, self.first_expert,
            self.n_routed_experts, self.eps)
        if self.first_expert + self.n_routed_experts > self.router_width:
            raise MXNetError(
                "DeepseekV3Arch: share %d of %d held experts lies outside "
                "the router's %d" % (self.share_index, self.n_routed_experts,
                                     self.router_width))
        self.latent = self.kv_lora_rank + self.qk_rope_head_dim
        #: the cache's minor dimension: whole 128-lane tiles
        self.latent_width = -(-self.latent // 128) * 128
        self.moe_layers = [i for i in range(self.num_layers)
                           if i >= self.first_k_dense_replace]
        sc = config.get("rope_scaling") or None
        if sc is not None and sc.get("type", "yarn") != "yarn":
            raise MXNetError("DeepseekV3Arch: rope_scaling type %r is not "
                             "implemented (yarn is)" % sc.get("type"))
        self.inv_freq = yarn_inv_freq(self.qk_rope_head_dim,
                                      float(config["rope_theta"]), sc)
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        self.softmax_scale = qk ** -0.5
        self.rope_scale = 1.0
        if sc is not None:
            f = float(sc["factor"])
            all_dim = float(sc.get("mscale_all_dim", 0))
            if all_dim:
                self.softmax_scale *= yarn_mscale(f, all_dim) ** 2
            self.rope_scale = yarn_mscale(f, float(sc.get("mscale", 1))) \
                / yarn_mscale(f, all_dim)

    # -- what the loop asks ----------------------------------------------------
    def param_shapes(self):
        e, h = self.hidden_size, self.num_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        out = {"tok_embed_weight": (self.vocab_size, e),
               "final_norm_gamma": (e,),
               "lm_head_weight": (self.vocab_size, e)}
        for i in range(self.num_layers):
            pre = "layer%d_" % i
            out.update({
                pre + "attn_norm_gamma": (e,),
                pre + "attn_q_a_weight": (self.q_lora_rank, e),
                pre + "attn_q_a_norm_gamma": (self.q_lora_rank,),
                pre + "attn_q_b_weight": (h * qk, self.q_lora_rank),
                pre + "attn_kv_a_weight": (self.latent, e),
                pre + "attn_kv_a_norm_gamma": (self.kv_lora_rank,),
                pre + "attn_kv_b_weight": (
                    h * (self.qk_nope_head_dim + self.v_head_dim),
                    self.kv_lora_rank),
                pre + "attn_out_weight": (e, h * self.v_head_dim),
                pre + "ffn_norm_gamma": (e,)})
            if i not in self.moe_layers:
                f = self.intermediate_size
                out.update({pre + "ffn_gate_weight": (f, e),
                            pre + "ffn_up_weight": (f, e),
                            pre + "ffn_down_weight": (e, f)})
                continue
            f, n = self.moe_intermediate_size, self.n_routed_experts
            fs = f * self.n_shared_experts
            out.update({pre + "router_weight": (self.router_width, e),
                        pre + "router_bias": (self.router_width,),
                        pre + "shared_gate_weight": (fs, e),
                        pre + "shared_up_weight": (fs, e),
                        pre + "shared_down_weight": (e, fs),
                        pre + "experts_gate_weight": (n, f, e),
                        pre + "experts_up_weight": (n, f, e),
                        pre + "experts_down_weight": (n, e, f)})
        return out

    def validate(self, host_params, max_len, mesh, quant_mode, spec_k=0,
                 prefix_cache=False):
        validate_share(self, "deepseek_v3", host_params, mesh, quant_mode)
        return self.vocab_size

    def slot_state(self, host_params, quant_mode):
        import jax.numpy as jnp
        dtype = jnp.bfloat16 if quant_mode == "bf16" else np.float32
        return {"latent": SlotArray(self.num_layers, PER_POSITION,
                                    self.latent_width, dtype)}

    def counters(self):
        return moe_counters(len(self.moe_layers), self.n_routed_experts)

    def load(self, params):
        return params      # as stored: no float32 copy (int8 was refused)

    def record_counters(self, health, counts, before):
        record_moe(health, counts, before)

    # -- what the token pass and the prefill pass are both made of -------------
    def _layer_parts(self):
        """``(angles, queries, latent_row, feed_forward)``: a layer's
        mathematics over any number of rows, one position each. The token
        pass runs them over a row a slot, the prefill pass over the rows of
        several slots' prompts: ONE text for both, so they cannot drift."""
        import jax
        import jax.numpy as jnp
        f32 = jnp.float32
        nope, lora = self.qk_nope_head_dim, self.kv_lora_rank
        heads, eps = self.num_heads, self.eps
        inv_freq = np.asarray(self.inv_freq, np.float32)
        moe_index = {i: m for m, i in enumerate(self.moe_layers)}

        def angles(pos):
            angle = pos.astype(f32)[:, None] * inv_freq[None, :]
            return (jnp.cos(angle) * f32(self.rope_scale),
                    jnp.sin(angle) * f32(self.rope_scale))

        def queries(a, p, cos, sin):
            q = linear(rms_norm(linear(a, p("attn_q_a_weight")),
                                p("attn_q_a_norm_gamma"), eps),
                       p("attn_q_b_weight"))
            q = q.reshape(a.shape[0], heads, -1)
            return q, rope(q[..., nope:], cos[:, None], sin[:, None])

        def latent_row(a, p, cos, sin, width):
            kva = linear(a, p("attn_kv_a_weight"))
            return _pad_lanes(jnp.concatenate(
                [rms_norm(kva[:, :lora], p("attn_kv_a_norm_gamma"), eps),
                 rope(kva[:, lora:], cos, sin)], axis=-1), width)

        def feed_forward(x, p, i, live, nlive, counts):
            if i not in moe_index:
                with jax.named_scope("layer/mlp"):
                    f = rms_norm(x, p("ffn_norm_gamma"), eps)
                    return x + swiglu(f, p("ffn_gate_weight"),
                                      p("ffn_up_weight"),
                                      p("ffn_down_weight")), counts
            f, y, counts = routed_share(x, p, self.share, live, nlive,
                                        counts, moe_index[i])
            with jax.named_scope("layer/moe/shared"):
                y = y + swiglu(f, p("shared_gate_weight"),
                               p("shared_up_weight"),
                               p("shared_down_weight"))
            return x + y, counts

        return angles, queries, latent_row, feed_forward

    def _attend(self, q, q_pe, p):
        """A layer's absorbed attention as :func:`.blocks.over_filled_rows`
        takes it: ``attend(mask, rows)`` for the queries ``q`` (rows, heads,
        nope + rope; the rope part is read from ``q_pe``, rotated)."""
        nope = self.qk_nope_head_dim
        return lambda mask, rows: mla_absorbed(
            q[..., :nope], q_pe, rows,
            p("attn_kv_b_weight").reshape(self.num_heads, -1,
                                          self.kv_lora_rank),
            mask, self.softmax_scale, nope)

    # -- one position per slot through every layer -----------------------------
    def build_token_pass(self, mesh=None):
        import jax
        import jax.numpy as jnp
        if mesh is not None:
            self.slot_partition()
        f32 = jnp.float32
        eps = self.eps
        angles, queries, latent_row, feed_forward = self._layer_parts()

        def token_pass(state, params, tokens, pos, live):
            lat = state["latent"]
            nslots, rows = tokens.shape[0], lat.shape[2]
            wpos = jnp.minimum(pos, jnp.int32(rows - 1))
            sidx = jnp.arange(nslots)
            with jax.named_scope("embed"):
                x = params["tok_embed_weight"][tokens].astype(f32)
                cos, sin = angles(wpos)
            over = over_filled_rows(pos, rows)
            counts = (state.get("moe_served"), state.get("moe_routed"))
            nlive = jnp.sum(live.astype(jnp.int32))
            # the scope names are what a device trace is searched for: the
            # same in every layer, so they sum by kind
            for i in range(self.num_layers):
                def p(name, pre="layer%d_" % i):
                    return params[pre + name]
                with jax.named_scope("layer/mla"):
                    a = rms_norm(x, p("attn_norm_gamma"), eps)
                    q, q_pe = queries(a, p, cos, sin)
                    row = latent_row(a, p, cos, sin, lat.shape[-1])
                with jax.named_scope("cache_write"):
                    lat = lat.at[i, sidx, wpos].set(row.astype(lat.dtype))
                with jax.named_scope("layer/mla"):
                    o = over((lat,), i, self._attend(q, q_pe, p))
                    x = x + linear(o, p("attn_out_weight"))
                x, counts = feed_forward(x, p, i, live, nlive, counts)
            with jax.named_scope("head"):
                logits = linear(rms_norm(x, params["final_norm_gamma"], eps),
                                params["lm_head_weight"])
            out = {"latent": lat}
            if counts[0] is not None:
                out.update(moe_served=counts[0], moe_routed=counts[1])
            return out, logits

        return token_pass

    # -- the prompts of several slots behind one read of the weights -----------
    def build_prefill_pass(self, mesh=None):
        """The packed pass (:attr:`packed_prefill`): the token pass's own
        mathematics over ``R`` rows, each a position of a slot it NAMES.

        Layer by layer every live row's latent row is scattered to
        ``(layer, slot[r], pos[r])`` of the donated array, in place, the
        padding rows sent out of bounds and DROPPED; then each row's
        absorbed attention runs over ITS slot's rows ``<= pos[r]`` as the
        array now holds them: the rows a prefix hit implanted, the rows
        that rode a step while the pass was held back, and the rows its
        sibling rows wrote in this layer. The rows reach their queries by
        a gather of ``R x rung x width`` a layer, the rung that of
        :func:`.blocks.rows_ladder` above the pass's deepest position. The
        LAST layer stops at its latent row: no head, no sampler, nothing
        to read back. The pass's positions count into ``moe_served`` and
        ``moe_routed`` as they did when they rode a step."""
        import jax
        import jax.numpy as jnp
        if mesh is not None:    # a sharded loop is fed one position a step
            return None
        f32 = jnp.float32
        eps = self.eps
        angles, queries, latent_row, feed_forward = self._layer_parts()

        def prefill_pass(state, params, tokens, slot, pos, n):
            lat = state["latent"]
            rows = lat.shape[2]
            at = jnp.arange(tokens.shape[0], dtype=jnp.int32)
            live = at < n
            # a padding row attends row 0 of slot 0 and writes nowhere
            # (each its own way out of bounds: the indices stay unique)
            slot = jnp.where(live, slot, 0)
            pos = jnp.where(live, pos, 0)
            wpos = jnp.where(live, pos, jnp.int32(rows) + at)
            with jax.named_scope("embed"):
                x = params["tok_embed_weight"][tokens].astype(f32)
                cos, sin = angles(pos)
            over = over_filled_rows(pos, rows, slot)
            counts = (state.get("moe_served"), state.get("moe_routed"))
            for i in range(self.num_layers):
                def p(name, pre="layer%d_" % i):
                    return params[pre + name]
                with jax.named_scope("layer/mla"):
                    a = rms_norm(x, p("attn_norm_gamma"), eps)
                    row = latent_row(a, p, cos, sin, lat.shape[-1])
                with jax.named_scope("cache_write"):
                    lat = lat.at[i, slot, wpos].set(
                        row.astype(lat.dtype), mode="drop",
                        unique_indices=True)
                if i + 1 == self.num_layers:
                    break       # nothing reads the last layer's output
                with jax.named_scope("layer/mla"):
                    q, q_pe = queries(a, p, cos, sin)
                    o = over((lat,), i, self._attend(q, q_pe, p))
                    x = x + linear(o, p("attn_out_weight"))
                x, counts = feed_forward(x, p, i, live, n, counts)
            out = {"latent": lat}
            if counts[0] is not None:
                out.update(moe_served=counts[0], moe_routed=counts[1])
            return out

        return prefill_pass
