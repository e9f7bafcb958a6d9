"""Plain reference for the ``lfm2-24b-a2b-ep8`` configuration: the LFM2
hybrid block (``model_type: lfm2_moe``; its dense half is
``transformers/models/lfm2/modeling_lfm2.py``), as one full forward pass
over a whole sequence in ``jax.numpy``: no cache, no state, no slots, no
per-token steps. It imports nothing of ``mxnet_tpu``; the parameter NAMES
are the repo's (``serving/lfm2.py``).

Layer: ``h = x + Op(RMSNorm(x; op_norm))``, ``y = h + FFN(RMSNorm(h;
ffn_norm))``; the embedding unscaled; one RMSNorm after the last layer (the
checkpoint's ``embedding_norm``); the head tied to the embedding
(``tie_embedding``, default true); no bias anywhere.

Conv operator (``layer_types[i] == "conv"``): ``[B, C, z] = split3(W_in
a)``, ``u = B * z``, ``c = causal depthwise convolution of u over the
sequence`` with the ``(hidden, conv_L_cache)`` kernel (zeros before position
0, the kernel's last column on the current position), ``Op = W_out (C *
c)``.

Attention (``"full_attention"``): ``q = W_q a`` as heads, ``k = W_k a``,
``v = W_v a`` as K/V heads; RMSNorm over each head's q and k; rotary
positions over the whole head, pairs ``(i, i + head_dim / 2)``
(``rotate_half``); K and V MATERIALISED and repeated over the query heads
of their group; scores times ``head_dim^-0.5``, causal softmax; ``W_o`` over
the heads.

Feed-forward: the first ``num_dense_layers`` layers a dense SwiGLU; the
others ``scores = sigmoid(W_g f)`` over ALL ``router_width`` experts in
float32, the top ``num_experts_per_tok`` of ``scores + expert_bias``
chosen, weights = the chosen scores without the bias, over their sum
(+1e-6, ``norm_topk_prob``), times ``routed_scaling_factor``; no shared
expert.

THE SHARE. ``num_experts`` is how many experts are HELD here: indices
``share_index * n .. share_index * n + n - 1`` of the ``router_width`` the
router ranks. Only their terms are added, for the (token, choice) pairs
that chose them; that partial sum goes on to the next layer.
``router_width`` absent means the layer is uncut.

Departures from the published code: the residual stream and the gating
products are float32 (published: the checkpoint's bfloat16); the router's
product is never rounded through ``operand`` (it is float32 in every
published precision).

``dtype`` float32 runs under ``jax.default_matmul_precision("highest")``;
bfloat16 (weights, activations and the residual stream) and bfloat16 with
the operands of every weight product rounded through ``operand`` (fp8) are
the controls. A weight is converted to ``dtype`` where it is used, and a
layer's weights wait behind a barrier for the layer's input: no float32
copy of more than one layer's weights (in practice, of more than a matrix
or two) lives at once, beside 7.5 GB of bfloat16 weights on the chip.
"""
import numpy as np

ROUTE_EPS = 1e-6


# -- sizes --------------------------------------------------------------------
def _dims(cfg):
    d = {k: int(cfg[k]) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "num_hidden_layers", "vocab_size", "intermediate_size",
        "moe_intermediate_size", "num_experts", "num_experts_per_tok",
        "num_dense_layers", "conv_L_cache")}
    d["router_width"] = int(cfg.get("router_width", d["num_experts"]))
    d["share_index"] = int(cfg.get("share_index", 0))
    d["head_dim"] = int(cfg.get("head_dim")
                        or d["hidden_size"] // d["num_attention_heads"])
    d["kv_width"] = d["num_key_value_heads"] * d["head_dim"]
    return d


def is_moe_layer(cfg, i):
    return i >= int(cfg["num_dense_layers"])


def _tied(cfg):
    return bool(cfg.get("tie_embedding", True))


def param_shapes(cfg):
    d = _dims(cfg)
    e, hd, h = d["hidden_size"], d["head_dim"], d["num_attention_heads"]
    out = {"tok_embed_weight": (d["vocab_size"], e),
           "final_norm_gamma": (e,)}
    if not _tied(cfg):
        out["lm_head_weight"] = (d["vocab_size"], e)
    for i, kind in enumerate(cfg["layer_types"]):
        pre = "layer%d_" % i
        out.update({pre + "op_norm_gamma": (e,),
                    pre + "ffn_norm_gamma": (e,)})
        if kind == "conv":
            out.update({pre + "conv_in_weight": (3 * e, e),
                        pre + "conv_weight": (e, d["conv_L_cache"]),
                        pre + "conv_out_weight": (e, e)})
        else:
            out.update({pre + "attn_q_weight": (h * hd, e),
                        pre + "attn_k_weight": (d["kv_width"], e),
                        pre + "attn_v_weight": (d["kv_width"], e),
                        pre + "attn_out_weight": (e, h * hd),
                        pre + "attn_q_norm_gamma": (hd,),
                        pre + "attn_k_norm_gamma": (hd,)})
        if not is_moe_layer(cfg, i):
            f = d["intermediate_size"]
            out.update({pre + "ffn_gate_weight": (f, e),
                        pre + "ffn_up_weight": (f, e),
                        pre + "ffn_down_weight": (e, f)})
            continue
        f, n = d["moe_intermediate_size"], d["num_experts"]
        out.update({pre + "router_weight": (d["router_width"], e),
                    pre + "experts_gate_weight": (n, f, e),
                    pre + "experts_up_weight": (n, f, e),
                    pre + "experts_down_weight": (n, e, f)})
        if cfg.get("use_expert_bias", True):
            out[pre + "router_bias"] = (d["router_width"],)
    return out


def param_count(cfg):
    return int(sum(np.prod(s) for s in param_shapes(cfg).values()))


def _std(cfg, name):
    """The standard deviation of one leaf's family (``assumed.weights``)."""
    if name == "tok_embed_weight":
        return float(cfg.get("embed_std", 0.04))
    if name.endswith("router_bias"):
        return float(cfg.get("router_bias_std", 0.01))
    if name.endswith("router_weight"):
        return float(cfg.get("router_std", 0.011))
    if name.endswith("_conv_weight"):
        return float(cfg.get("conv_std", 0.5))
    return float(cfg.get("init_std", 0.02))


def make_params(cfg, seed):
    """Seeded weights in the configuration's ``dtype`` (bfloat16: every
    value is bfloat16-representable because it IS a bfloat16), made ON THE
    DEVICE, one fused program a leaf, and left there: ``DecodeLoop`` takes a
    bfloat16 device array as it is under ``quantize="bf16"``, and the check
    reads the same buffers (PERF.md, PR 29: on the host the normals were
    half of a cell's set-up). N(0, std) by family, gamma 1 + 0.1 N(0, 1)."""
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
def _kinds(cfg):
    """``(conv layers, attention layers, dense layers, expert layers)``."""
    kinds = list(cfg["layer_types"])
    dense = min(len(kinds), int(cfg["num_dense_layers"]))
    return (kinds.count("conv"), kinds.count("full_attention"), dense,
            len(kinds) - dense)


def _conv_weights(cfg):
    """Elements of one conv operator's matrices, kernel and norm."""
    d = _dims(cfg)
    e = d["hidden_size"]
    return 4 * e * e + d["conv_L_cache"] * e + e


def _conv_flops(cfg):
    """One position through one conv operator: both projections, the
    kernel's taps and the two gating products."""
    d = _dims(cfg)
    e = d["hidden_size"]
    return 2 * 4 * e * e + 2 * d["conv_L_cache"] * e + 2 * e


def _attn_weights(cfg):
    """Elements of one attention operator's matrices and norms."""
    d = _dims(cfg)
    e, q = d["hidden_size"], d["num_attention_heads"] * d["head_dim"]
    return 2 * e * q + 2 * e * d["kv_width"] + e + 2 * d["head_dim"]


def _attn_flops(cfg, context):
    """One position through one attention operator: every matrix once, and
    per query head the scores and the mix over ``head_dim`` values of each
    of ``context`` rows of ITS K/V head."""
    d = _dims(cfg)
    e, q = d["hidden_size"], d["num_attention_heads"] * d["head_dim"]
    return 2 * (2 * e * q + 2 * e * d["kv_width"]) + 4 * q * context


def _moe_elements(cfg):
    """``(always, each held expert)`` elements of one expert layer: the
    router with its bias and the norm; one routed expert."""
    d = _dims(cfg)
    e = d["hidden_size"]
    return (e * d["router_width"] + d["router_width"] + e,
            3 * e * d["moe_intermediate_size"])


def _moe_flops(cfg):
    """One position through one expert layer on THIS share: the router and
    the routed experts it can expect here: ``num_experts_per_tok *
    num_experts / router_width`` of them."""
    d = _dims(cfg)
    here = d["num_experts_per_tok"] * d["num_experts"] \
        / float(d["router_width"])
    return 2 * (d["hidden_size"] * d["router_width"]
                + here * _moe_elements(cfg)[1])


def flops_per_position(cfg, context):
    """FLOPs one position requires with ``context`` positions to attend
    (itself included): 2 per multiply-add."""
    d = _dims(cfg)
    conv, attn, dense, moe = _kinds(cfg)
    e = d["hidden_size"]
    return int(conv * _conv_flops(cfg) + attn * _attn_flops(cfg, context)
               + dense * 2 * 3 * e * d["intermediate_size"]
               + moe * _moe_flops(cfg) + 2 * e * d["vocab_size"])


def weight_bytes(cfg, itemsize=2):
    """Bytes of every weight a decode step must read once: every leaf, the
    held experts whole. The tied embedding counts once, as the head (the
    rows a step embeds are among them); untied, the table is left out."""
    shapes = param_shapes(cfg)
    return itemsize * sum(int(np.prod(s)) for k, s in shapes.items()
                          if _tied(cfg) or k != "tok_embed_weight")


def _kv_bytes(cfg, contexts, itemsize):
    """Per position and attention layer: its K and V rows read, one of each
    written."""
    return sum((c + 1) * 2 * _dims(cfg)["kv_width"] * itemsize
               for c in contexts)


def _conv_state_bytes(cfg, positions, itemsize):
    """Per position and conv layer: the state's ``conv_L_cache - 1`` rows
    read and one written."""
    d = _dims(cfg)
    return positions * d["conv_L_cache"] * d["hidden_size"] * itemsize


def step_work(cfg, contexts, itemsize=2):
    """``(flops, bytes)`` one decode step requires for slots whose
    positions attend ``contexts`` rows each: the weights once, per position
    its K/V rows in every attention layer and its conv state in every conv
    layer."""
    conv, attn, _, _ = _kinds(cfg)
    flops = sum(flops_per_position(cfg, c) for c in contexts)
    return flops, weight_bytes(cfg, itemsize) \
        + attn * _kv_bytes(cfg, contexts, itemsize) \
        + conv * _conv_state_bytes(cfg, len(contexts), itemsize)


def conv_layer_work(cfg, positions, itemsize=2):
    """``(flops, bytes)`` of the conv operator of every conv layer in one
    step of ``positions`` positions: its matrices, kernel and norm once,
    each position's state (2 rows read, 1 written at kernel 3)."""
    conv = _kinds(cfg)[0]
    return conv * positions * _conv_flops(cfg), \
        conv * (itemsize * _conv_weights(cfg)
                + _conv_state_bytes(cfg, positions, itemsize))


def attn_layer_work(cfg, contexts, itemsize=2):
    """``(flops, bytes)`` of the attention operator of every attention
    layer in one step: its matrices and norms once, the K/V rows each
    position attends and the row it writes."""
    attn = _kinds(cfg)[1]
    return attn * sum(_attn_flops(cfg, c) for c in contexts), \
        attn * (itemsize * _attn_weights(cfg)
                + _kv_bytes(cfg, contexts, itemsize))


def moe_layer_work(cfg, positions, itemsize=2):
    """``(flops, bytes)`` of every expert layer in one step of
    ``positions`` positions: router and norm once, every held expert once
    and whole."""
    moe = _kinds(cfg)[3]
    always, each = _moe_elements(cfg)
    return moe * positions * _moe_flops(cfg), \
        moe * itemsize * (always + each * _dims(cfg)["num_experts"])


# -- the forward --------------------------------------------------------------
def inv_freq(cfg):
    """The ``head_dim / 2`` inverse frequencies of the rotary pairs."""
    dim = _dims(cfg)["head_dim"]
    theta = float(cfg["rope_parameters"]["rope_theta"])
    return 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)


def _rotate_half(x):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rms(x, gamma, eps):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * gamma


def route(scores_in, weight, bias, cfg):
    """``(indices, weights)`` ``(T, k)`` of the experts each token chooses
    among all ``router_width``, in float32: chosen by ``scores + bias``
    (``bias`` None without ``use_expert_bias``), weighted by the scores
    alone over their sum, times the scaling."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    logits = jnp.matmul(scores_in.astype(f32), weight.astype(f32).T,
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    ranked = scores if bias is None else scores + bias.astype(f32)
    _, idx = jax.lax.top_k(ranked, int(cfg["num_experts_per_tok"]))
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + f32(ROUTE_EPS))
    return idx, w * f32(cfg.get("routed_scaling_factor", 1.0))


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
    e, heads, groups, hd = (d["hidden_size"], d["num_attention_heads"],
                            d["num_key_value_heads"], d["head_dim"])
    kernel = d["conv_L_cache"]
    eps = float(cfg["norm_eps"])
    held = d["num_experts"]
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
            * jnp.asarray(inv_freq(cfg), jnp.float32)[None, :]
        angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
        cos, sin = jnp.cos(angle).astype(dtype), jnp.sin(angle).astype(dtype)
        causal = jnp.tril(jnp.ones((t, t), bool))
        scale = jnp.asarray(hd ** -0.5, dtype)
        for i, kind in enumerate(cfg["layer_types"]):
            pre = "layer%d_" % i
            names = [k for k in params if k.startswith(pre)]
            # this layer's weights wait for the layer's input, so that the
            # layers' conversions cannot all be scheduled first
            x, p = jax.lax.optimization_barrier(
                (x, {k[len(pre):]: params[k] for k in names}))

            def gamma(name):
                return p[name].astype(dtype)

            a = _rms(x, gamma("op_norm_gamma"), eps)
            if kind == "conv":
                b, c, z = jnp.split(lin(a, p["conv_in_weight"]), 3, axis=-1)
                u = b * z
                # causal and depthwise: channel ch of position s sees
                # u[s - (kernel - 1) .. s, ch] under its own kernel row
                conv = jax.lax.conv_general_dilated(
                    u.T[None], p["conv_weight"].astype(dtype)[:, None, :],
                    window_strides=(1,), padding=[(kernel - 1, 0)],
                    feature_group_count=e)[0].T
                x = x + lin(c * conv, p["conv_out_weight"])
            else:
                q = _rms(lin(a, p["attn_q_weight"]).reshape(t, heads, hd),
                         gamma("attn_q_norm_gamma"), eps)
                k = _rms(lin(a, p["attn_k_weight"]).reshape(t, groups, hd),
                         gamma("attn_k_norm_gamma"), eps)
                v = lin(a, p["attn_v_weight"]).reshape(t, groups, hd)
                q = q * cos + _rotate_half(q) * sin
                k = k * cos + _rotate_half(k) * sin
                k = jnp.repeat(k, heads // groups, axis=1)
                v = jnp.repeat(v, heads // groups, axis=1)
                s = jnp.einsum("qhd,khd->hqk", q, k) * scale
                s = jnp.where(causal[None], s, jnp.asarray(-1e30, dtype))
                w = jax.nn.softmax(s.astype(jnp.float32),
                                   axis=-1).astype(dtype)
                o = jnp.einsum("hqk,khd->qhd", w, v).reshape(t, heads * hd)
                x = x + lin(o, p["attn_out_weight"])
            f = _rms(x, gamma("ffn_norm_gamma"), eps)
            if not is_moe_layer(cfg, i):
                x = x + swiglu(f, p["ffn_gate_weight"], p["ffn_up_weight"],
                               p["ffn_down_weight"])
            else:
                idx, wts = route(f, p["router_weight"], p.get("router_bias"),
                                 cfg)
                if taps is not None:
                    taps.setdefault("chosen", []).append(idx)
                y = jnp.zeros((t, e), jnp.float32)
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
        logits = lin(x, params["tok_embed_weight" if _tied(cfg)
                            else "lm_head_weight"])
    return logits.astype(jnp.float32)
