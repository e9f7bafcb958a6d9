"""Plain reference for the ``kimi-k2-ep32`` configuration: the DeepSeek-V3
block that ``model_type: kimi_k2`` uses (``modeling_deepseek.py``), as one
full forward pass over a whole sequence in ``jax.numpy``: no cache, no
slots, no per-token steps, no absorbed projections. It imports nothing of
``mxnet_tpu``; the parameter NAMES are the repo's
(``serving/deepseek_v3.py``).

Block: ``h = x + MLA(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; a final
RMSNorm; an untied head with no bias; no learned positions.

MLA, naive: ``q = W_qb RMSNorm(W_qa x)`` per head ``[q_nope, q_pe]``;
``W_kva x -> [c, k_pe]``, ``c' = RMSNorm(c)``; ``W_kvb c'`` per head
``[k_nope, v]``, MATERIALISED for every position; rotary positions
(interleaved pairs, YaRN) on ``q_pe`` and on the one shared ``k_pe``;
scores ``(q_nope . k_nope + q_pe . k_pe) * s`` with ``s = qk_head_dim^-0.5
* mscale(factor, mscale_all_dim)^2``; causal softmax; ``W_o`` over the
heads' ``softmax . v``.

Feed-forward: the first ``first_k_dense_replace`` layers a dense SwiGLU;
the others ``scores = sigmoid(W_g h)`` over ALL ``router_width`` experts in
float32, the top ``num_experts_per_tok`` of ``scores + bias`` chosen,
weights = the chosen scores without the bias, over their sum (+1e-20),
times ``routed_scaling_factor``; plus ``n_shared_experts`` shared SwiGLU.

THE SHARE. ``n_routed_experts`` is how many experts are HELD here:
indices ``share_index * n .. share_index * n + n - 1`` of the
``router_width`` the router ranks. Only their terms are added, for the
(token, choice) pairs that chose them, with the shared expert; that
partial sum goes on to the next layer. ``router_width`` absent means the
layer is uncut.

Departures from the published code: the residual stream is float32 (the
published code carries it in bfloat16); the router's product is never
rounded through ``operand`` (it is float32 in every published precision).

``dtype`` float32 runs under ``jax.default_matmul_precision("highest")``;
bfloat16 (weights, activations and the residual stream) and bfloat16 with
the operands of every weight product rounded through ``operand`` (fp8) are
the controls. A weight is converted to ``dtype`` where it is used, and a
layer's weights wait behind a barrier for the layer's input: no float32
copy of more than one layer's weights (in practice, of more than a matrix
or two) lives at once, beside 9.7 GB of bfloat16 weights on the chip.
"""
import math

import numpy as np

ROPE_PAIR = 2


# -- sizes --------------------------------------------------------------------
def _dims(cfg):
    d = {k: int(cfg[k]) for k in (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
        "n_shared_experts", "n_routed_experts", "first_k_dense_replace",
        "num_hidden_layers", "vocab_size")}
    d["router_width"] = int(cfg.get("router_width", d["n_routed_experts"]))
    d["share_index"] = int(cfg.get("share_index", 0))
    d["latent"] = d["kv_lora_rank"] + d["qk_rope_head_dim"]
    d["qk_head_dim"] = d["qk_nope_head_dim"] + d["qk_rope_head_dim"]
    return d


def is_moe_layer(cfg, i):
    return i >= int(cfg["first_k_dense_replace"])


def param_shapes(cfg):
    d = _dims(cfg)
    e, h = d["hidden_size"], d["num_attention_heads"]
    out = {"tok_embed_weight": (d["vocab_size"], e),
           "final_norm_gamma": (e,),
           "lm_head_weight": (d["vocab_size"], e)}
    for i in range(d["num_hidden_layers"]):
        pre = "layer%d_" % i
        out.update({
            pre + "attn_norm_gamma": (e,),
            pre + "attn_q_a_weight": (d["q_lora_rank"], e),
            pre + "attn_q_a_norm_gamma": (d["q_lora_rank"],),
            pre + "attn_q_b_weight": (h * d["qk_head_dim"], d["q_lora_rank"]),
            pre + "attn_kv_a_weight": (d["latent"], e),
            pre + "attn_kv_a_norm_gamma": (d["kv_lora_rank"],),
            pre + "attn_kv_b_weight": (
                h * (d["qk_nope_head_dim"] + d["v_head_dim"]),
                d["kv_lora_rank"]),
            pre + "attn_out_weight": (e, h * d["v_head_dim"]),
            pre + "ffn_norm_gamma": (e,)})
        if not is_moe_layer(cfg, i):
            f = d["intermediate_size"]
            out.update({pre + "ffn_gate_weight": (f, e),
                        pre + "ffn_up_weight": (f, e),
                        pre + "ffn_down_weight": (e, f)})
            continue
        f, n = d["moe_intermediate_size"], d["n_routed_experts"]
        fs = f * d["n_shared_experts"]
        out.update({pre + "router_weight": (d["router_width"], e),
                    pre + "router_bias": (d["router_width"],),
                    pre + "shared_gate_weight": (fs, e),
                    pre + "shared_up_weight": (fs, e),
                    pre + "shared_down_weight": (e, fs),
                    pre + "experts_gate_weight": (n, f, e),
                    pre + "experts_up_weight": (n, f, e),
                    pre + "experts_down_weight": (n, e, f)})
    return out


def param_count(cfg):
    return int(sum(np.prod(s) for s in param_shapes(cfg).values()))


def _std(cfg, name):
    """The standard deviation of one leaf's family (``assumed.weights``)."""
    if name == "tok_embed_weight":
        return float(cfg.get("embed_std", 1.0))
    if name.endswith("router_bias"):
        return float(cfg.get("router_bias_std", 0.01))
    if name.endswith("router_weight"):
        return float(cfg.get("router_std", 0.006))
    return float(cfg.get("init_std", 0.02))


def make_params(cfg, seed):
    """Seeded weights in the configuration's ``dtype`` (bfloat16: every
    value is bfloat16-representable because it IS a bfloat16), made ON THE
    DEVICE, one fused program a leaf (float32 normals scaled and rounded in
    the registers: no float32 copy of a leaf ever lives, on the host or
    the device), and left there: ``DecodeLoop`` takes a bfloat16 device
    array as it is under ``quantize="bf16"``, and the check reads the same
    buffers. On the host, 4.85e9 normals were half of this cell's set-up
    (20-29 s on 12 threads of the chip's shared cores: chip runs, PR 29).
    N(0, std) by family, gamma 1 + 0.1 N(0, 1)."""
    import functools
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg.get("dtype", "bfloat16"))

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def make(key, shape, scale, shift):
        x = jax.random.normal(key, shape, jnp.float32)
        return (x * jnp.float32(scale) + jnp.float32(shift)).astype(dtype)

    root = jax.random.PRNGKey(int(seed))
    shapes = param_shapes(cfg)
    out = {}
    for i, name in enumerate(sorted(shapes)):
        scale, shift = ((0.1, 1.0) if name.endswith("_gamma")
                        else (_std(cfg, name), 0.0))
        out[name] = make(jax.random.fold_in(root, i), tuple(shapes[name]),
                         scale, shift)
    return out


# -- the work a step requires, from the shapes --------------------------------
def _mla_weights(cfg):
    """Elements of one layer's attention matrices."""
    d = _dims(cfg)
    e, h = d["hidden_size"], d["num_attention_heads"]
    return (e * d["q_lora_rank"] + d["q_lora_rank"] * h * d["qk_head_dim"]
            + e * d["latent"]
            + d["kv_lora_rank"] * h * (d["qk_nope_head_dim"]
                                       + d["v_head_dim"])
            + h * d["v_head_dim"] * e)


def _mla_flops(cfg, context):
    """One position through one MLA layer in the ABSORBED form (the least
    a decode step can do over a latent cache: the naive form would
    up-project every cached row again): every matrix once, and per head
    the scores over ``latent`` and the mix over ``kv_lora_rank`` values of
    each of ``context`` rows."""
    d = _dims(cfg)
    h = d["num_attention_heads"]
    return 2 * _mla_weights(cfg) \
        + 2 * h * context * (d["latent"] + d["kv_lora_rank"])


def _moe_elements(cfg):
    """``(always, each held expert)`` elements of one expert layer's
    matrices: the router and the shared expert; one routed expert."""
    d = _dims(cfg)
    e, f = d["hidden_size"], d["moe_intermediate_size"]
    return (e * d["router_width"] + 3 * e * f * d["n_shared_experts"],
            3 * e * f)


def _moe_flops(cfg):
    """One position through one expert layer on THIS share: the router, the
    shared expert, and the routed experts it can expect here:
    ``num_experts_per_tok * n_routed_experts / router_width`` of them."""
    d = _dims(cfg)
    always, each = _moe_elements(cfg)
    here = d["num_experts_per_tok"] * d["n_routed_experts"] \
        / float(d["router_width"])
    return 2 * (always + here * each)


def _layer_counts(cfg):
    layers = int(cfg["num_hidden_layers"])
    dense = min(layers, int(cfg["first_k_dense_replace"]))
    return layers, dense, layers - dense


def flops_per_position(cfg, context):
    """FLOPs one position requires with ``context`` positions to attend
    (itself included): 2 per multiply-add."""
    d = _dims(cfg)
    layers, dense, moe = _layer_counts(cfg)
    e = d["hidden_size"]
    return int(layers * _mla_flops(cfg, context)
               + dense * 2 * 3 * e * d["intermediate_size"]
               + moe * _moe_flops(cfg) + 2 * e * d["vocab_size"])


def weight_bytes(cfg, itemsize=2):
    """Bytes of every weight a decode step must read once: all leaves but
    the embedding table, of which a step reads one row per position. The
    held experts count whole."""
    shapes = param_shapes(cfg)
    return itemsize * sum(int(np.prod(s)) for k, s in shapes.items()
                          if k != "tok_embed_weight")


def _latent_bytes(cfg, contexts, itemsize):
    """Per position and layer: its ``latent``-wide rows read, one written."""
    d = _dims(cfg)
    return sum((c + 1) * d["latent"] * itemsize for c in contexts)


def step_work(cfg, contexts, itemsize=2):
    """``(flops, bytes)`` one decode step requires for slots whose
    positions attend ``contexts`` rows each: the weights once, and per
    position its latent rows read and one written in every layer, and one
    embedding row."""
    layers = int(cfg["num_hidden_layers"])
    flops = sum(flops_per_position(cfg, c) for c in contexts)
    return flops, weight_bytes(cfg, itemsize) \
        + layers * _latent_bytes(cfg, contexts, itemsize) \
        + int(cfg["hidden_size"]) * itemsize * len(contexts)


def mla_layer_work(cfg, contexts, itemsize=2):
    """``(flops, bytes)`` of the MLA part of every layer in one step: the
    attention matrices and norms once, the positions' latent rows."""
    d = _dims(cfg)
    layers = int(cfg["num_hidden_layers"])
    norms = d["hidden_size"] + d["q_lora_rank"] + d["kv_lora_rank"]
    flops = layers * sum(_mla_flops(cfg, c) for c in contexts)
    return flops, layers * (itemsize * (_mla_weights(cfg) + norms)
                            + _latent_bytes(cfg, contexts, itemsize))


def moe_layer_work(cfg, positions, itemsize=2):
    """``(flops, bytes)`` of every expert layer in one step of
    ``positions`` positions: router, shared expert and norm once, every
    held expert once and whole."""
    d = _dims(cfg)
    _, _, moe = _layer_counts(cfg)
    always, each = _moe_elements(cfg)
    elements = always + each * d["n_routed_experts"] + d["hidden_size"] \
        + d["router_width"]
    return moe * positions * _moe_flops(cfg), moe * itemsize * elements


# -- rotary positions ---------------------------------------------------------
def yarn_inv_freq(cfg):
    """The ``qk_rope_head_dim / 2`` inverse frequencies, float64: plain
    rotary frequencies where ``rope_scaling`` is absent, else YaRN's blend
    of them (``extrapolation``) with the same divided by ``factor``
    (``interpolation``), by a linear ramp between the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""
    dim = int(cfg["qk_rope_head_dim"])
    theta = float(cfg["rope_theta"])
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    sc = cfg.get("rope_scaling")
    if not sc:
        return extra
    inter = extra / float(sc["factor"])
    orig = float(sc["original_max_position_embeddings"])

    def turns_dim(n_rot):
        return dim * math.log(orig / (n_rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(float(sc["beta_fast"]))), 0)
    high = min(math.ceil(turns_dim(float(sc["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(cfg):
    d = _dims(cfg)
    s = d["qk_head_dim"] ** -0.5
    sc = cfg.get("rope_scaling")
    if sc and sc.get("mscale_all_dim", 0):
        s *= yarn_mscale(float(sc["factor"]), float(sc["mscale_all_dim"])) ** 2
    return s


def rope_scale(cfg):
    """What cos and sin are multiplied by: 1 where ``mscale`` equals
    ``mscale_all_dim``."""
    sc = cfg.get("rope_scaling")
    if not sc:
        return 1.0
    f = float(sc["factor"])
    return yarn_mscale(f, float(sc.get("mscale", 1))) \
        / yarn_mscale(f, float(sc.get("mscale_all_dim", 0)))


def _rope(x, cos, sin):
    """Rotate the interleaved pairs ``(x[2i], x[2i+1])`` of the minor
    dimension by the angle whose cos and sin are given per pair."""
    import jax.numpy as jnp
    shape = x.shape
    x = x.reshape(shape[:-1] + (shape[-1] // ROPE_PAIR, ROPE_PAIR))
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(shape)


# -- the forward --------------------------------------------------------------
def _rms(x, gamma, eps):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * gamma


def route(scores_in, weight, bias, cfg):
    """``(indices, weights)`` ``(T, k)`` of the experts each token chooses
    among all ``router_width``, in float32: chosen by ``scores + bias``,
    weighted by the scores alone over their sum, times the scaling."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    logits = jnp.matmul(scores_in.astype(f32), weight.astype(f32).T,
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias.astype(f32),
                           int(cfg["num_experts_per_tok"]))
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + f32(1e-20))
    return idx, w * f32(cfg["routed_scaling_factor"])


def forward(params, tokens, cfg, dtype="float32", operand=None, taps=None):
    """Logits ``(T, vocab)`` of one sequence ``tokens`` (T,), causal.
    ``operand`` rounds the operands of every weight product but the
    router's through a lower precision (the fp8 control); the rest stays
    in ``dtype``. ``taps``, a dict, is given every layer's output
    (``taps["layers"]``) and every expert layer's chosen experts (T, k)
    (``taps["chosen"]``): the tests' hook."""
    import contextlib
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype)
    d = _dims(cfg)
    heads, nope, rope, vdim = (d["num_attention_heads"],
                               d["qk_nope_head_dim"], d["qk_rope_head_dim"],
                               d["v_head_dim"])
    eps = float(cfg["rms_norm_eps"])
    held = d["n_routed_experts"]
    first = d["share_index"] * held
    ctx = (jax.default_matmul_precision("highest")
           if dtype == jnp.float32 else contextlib.nullcontext())
    with ctx:
        def lin(x, w):
            # the weight is converted where it is used: one matrix's
            # converted copy at a time
            w = w.astype(dtype)
            if operand is not None:
                x = x.astype(operand).astype(dtype)
                w = w.astype(operand).astype(dtype)
            return x @ w.T

        def swiglu(x, gate, up, down):
            return lin(jax.nn.silu(lin(x, gate)) * lin(x, up), down)

        t = tokens.shape[0]
        x = params["tok_embed_weight"][tokens].astype(dtype)
        angle = jnp.arange(t, dtype=jnp.float32)[:, None] \
            * jnp.asarray(yarn_inv_freq(cfg), jnp.float32)[None, :]
        rs = jnp.float32(rope_scale(cfg))
        cos = (jnp.cos(angle) * rs).astype(dtype)
        sin = (jnp.sin(angle) * rs).astype(dtype)
        causal = jnp.tril(jnp.ones((t, t), bool))
        scale = jnp.asarray(softmax_scale(cfg), dtype)
        for i in range(d["num_hidden_layers"]):
            pre = "layer%d_" % i
            names = [k for k in params if k.startswith(pre)]
            # this layer's weights wait for the layer's input, so that the
            # layers' conversions cannot all be scheduled first
            x, p = jax.lax.optimization_barrier(
                (x, {k[len(pre):]: params[k] for k in names}))

            def gamma(name):
                return p[name].astype(dtype)

            a = _rms(x, gamma("attn_norm_gamma"), eps)
            q = lin(_rms(lin(a, p["attn_q_a_weight"]),
                         gamma("attn_q_a_norm_gamma"), eps),
                    p["attn_q_b_weight"]).reshape(t, heads, nope + rope)
            q_nope, q_pe = q[..., :nope], q[..., nope:]
            kva = lin(a, p["attn_kv_a_weight"])
            c = _rms(kva[:, :d["kv_lora_rank"]],
                     gamma("attn_kv_a_norm_gamma"), eps)
            k_pe = _rope(kva[:, d["kv_lora_rank"]:], cos, sin)
            q_pe = _rope(q_pe, cos[:, None], sin[:, None])
            kv = lin(c, p["attn_kv_b_weight"]).reshape(t, heads, nope + vdim)
            k_nope, v = kv[..., :nope], kv[..., nope:]
            s = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
                 + jnp.einsum("qhd,kd->hqk", q_pe, k_pe)) * scale
            s = jnp.where(causal[None], s, jnp.asarray(-1e30, dtype))
            w = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dtype)
            o = jnp.einsum("hqk,khd->qhd", w, v).reshape(t, heads * vdim)
            x = x + lin(o, p["attn_out_weight"])
            f = _rms(x, gamma("ffn_norm_gamma"), eps)
            if not is_moe_layer(cfg, i):
                x = x + swiglu(f, p["ffn_gate_weight"], p["ffn_up_weight"],
                               p["ffn_down_weight"])
            else:
                idx, wts = route(f, p["router_weight"], p["router_bias"],
                                 cfg)
                if taps is not None:
                    taps.setdefault("chosen", []).append(idx)
                y = swiglu(f, p["shared_gate_weight"], p["shared_up_weight"],
                           p["shared_down_weight"]).astype(jnp.float32)
                for j in range(held):       # the experts held here, plainly
                    wj = jnp.sum(jnp.where(idx == first + j, wts, 0.0),
                                 axis=-1)
                    y = y + wj[:, None] * swiglu(
                        f, p["experts_gate_weight"][j],
                        p["experts_up_weight"][j],
                        p["experts_down_weight"][j]).astype(jnp.float32)
                x = x + y.astype(dtype)
            if taps is not None:
                taps.setdefault("layers", []).append(x)
        x = _rms(x, params["final_norm_gamma"].astype(dtype), eps)
        logits = lin(x, params["lm_head_weight"])
    return logits.astype(jnp.float32)
