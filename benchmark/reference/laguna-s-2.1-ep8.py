"""Plain reference for the ``laguna-s-2.1-ep8`` configuration: the Laguna
block (``model_type: laguna``, poolside's Laguna-S-2.1), as one full
forward pass over a whole sequence in ``jax.numpy``: no cache, no ring, no
slots, no per-token steps. It imports nothing of ``mxnet_tpu``; the
parameter NAMES are the repo's (``serving/mellum.py``, ``serving/laguna.py``).

THE STAGE. Every per-layer list of the config (``layer_types``,
``mlp_layer_types``, ``num_attention_heads_per_layer``) names the model's
48 layers; this chip runs the first ``num_hidden_layers`` of them (the
first stage of a pipeline), and so does this reference.

Layer ``i`` of kind ``t = layer_types[i]`` with ``H_i`` query heads
(``num_attention_heads_per_layer[i]``), ``x`` the residual stream:

* ``a = RMSNorm(x; op_norm)``; ``q = RMSNorm_head(W_q a)`` over ``H_i``
  heads, ``k = RMSNorm_head(W_k a)`` and ``v = W_v a`` over the K/V heads;
* rotary positions by the position's absolute index over the leading ``r_t
  = head_dim * partial_rotary_factor`` lanes of q and k in halves (pairs
  ``(j, j + r_t / 2)``, ``rotate_half``), the rest passed through, with the
  kind's own entry of ``rope_parameters``: ``sliding_attention`` plain
  ``theta^(-2j/r)`` over the whole head, ``full_attention`` YaRN's blend
  over its 64 rotary lanes with cos and sin times ``attention_factor``;
* K and V MATERIALISED and repeated over the query heads of their group;
  scores times ``head_dim^-0.5``; the mask CAUSAL for a full layer and
  BANDED for a window layer (position ``p`` attends ``max(0, p -
  sliding_window + 1) .. p``); float32 softmax, a block of queries at a
  time (``block``: that changes no value);
* the per-head GATE (``gating: per-head``): head ``h``'s output times
  ``sigmoid(w_g[h] . a)``; ``h = x + W_o o``;
* ``f = RMSNorm(h; ffn_norm)``; a dense layer (``mlp_layer_types``
  ``dense``, or named by ``mlp_only_layers``): ``y = h + SwiGLU(f)`` of
  ``intermediate_size``; an expert layer: ``s = softmax(W_r f)`` over ALL
  ``router_width`` experts in float32, the top ``num_experts_per_tok``
  chosen, weights = the chosen probabilities over their plain sum
  (``norm_topk_prob``), and ``y = h + SwiGLU_shared(f) +
  moe_routed_scaling_factor * sum_e w_e SwiGLU_e(f)``;
* after the last layer one RMSNorm and the untied head.

THE SHARE. ``num_experts`` is how many experts are HELD here: indices
``share_index * n .. share_index * n + n - 1`` of the ``router_width`` the
router ranks. Only their terms are added, for the (token, choice) pairs
that chose them, beside the shared expert once; that partial sum goes on
to the next layer. ``router_width`` absent means the layer is uncut.

Assumed (the published config has no key for them; the configuration's
``assumed`` gives the reasons): the q/k norms per head, softmax scoring
without a cap (``moe_router_logit_softcapping: 0``), an ungated shared
expert outside the routed scaling, rotation in halves, a float32 residual
stream. The router's product is never rounded through ``operand`` (it is
float32 in every published precision).

``dtype`` float32 runs under ``jax.default_matmul_precision("highest")``;
bfloat16 (weights, activations and the residual stream) and bfloat16 with
the operands of every weight product rounded through ``operand`` (fp8) are
the controls. ``sliding_window`` past every position (the window lifted)
and ``gating`` false (the gate lifted) are the configuration's two planted
controls (``benchmark/tools/lifted_control.py``). A weight is converted to
``dtype`` where it is used, and a layer's weights wait behind a barrier for
the layer's input: no float32 copy of more than one layer's weights lives
at once, beside 8.65 GB of bfloat16 weights on the chip.
"""
import math

import numpy as np

KINDS = ("sliding_attention", "full_attention")


# -- sizes --------------------------------------------------------------------
def _dims(cfg):
    d = {k: int(cfg[k]) for k in (
        "hidden_size", "num_key_value_heads", "head_dim",
        "num_hidden_layers", "vocab_size", "intermediate_size",
        "moe_intermediate_size", "shared_expert_intermediate_size",
        "num_experts", "num_experts_per_tok", "sliding_window")}
    d["router_width"] = int(cfg.get("router_width", d["num_experts"]))
    d["share_index"] = int(cfg.get("share_index", 0))
    d["kv_width"] = d["num_key_value_heads"] * d["head_dim"]
    return d


def layers(cfg):
    """``[(kind, query heads, dense)]`` of the layers this chip runs: the
    first ``num_hidden_layers`` of the per-layer lists."""
    n = int(cfg["num_hidden_layers"])
    only = set(cfg.get("mlp_only_layers") or ())
    mlp = cfg.get("mlp_layer_types") or ["sparse"] * n
    return [(cfg["layer_types"][i],
             int(cfg["num_attention_heads_per_layer"][i]),
             mlp[i] == "dense" or i in only) for i in range(n)]


def _gated(cfg):
    return cfg.get("gating") in ("per-head", True)


def _tied(cfg):
    return bool(cfg.get("tie_word_embeddings", False))


def _swiglu_shapes(pre, width, e):
    return {pre + "gate_weight": (width, e), pre + "up_weight": (width, e),
            pre + "down_weight": (e, width)}


def param_shapes(cfg):
    d = _dims(cfg)
    e, hd, kv = d["hidden_size"], d["head_dim"], d["kv_width"]
    f, n = d["moe_intermediate_size"], d["num_experts"]
    out = {"tok_embed_weight": (d["vocab_size"], e),
           "final_norm_gamma": (e,)}
    if not _tied(cfg):
        out["lm_head_weight"] = (d["vocab_size"], e)
    for i, (_, heads, dense) in enumerate(layers(cfg)):
        pre = "layer%d_" % i
        out.update({pre + "op_norm_gamma": (e,),
                    pre + "ffn_norm_gamma": (e,),
                    pre + "attn_q_weight": (heads * hd, e),
                    pre + "attn_k_weight": (kv, e),
                    pre + "attn_v_weight": (kv, e),
                    pre + "attn_out_weight": (e, heads * hd),
                    pre + "attn_q_norm_gamma": (hd,),
                    pre + "attn_k_norm_gamma": (hd,)})
        if _gated(cfg):
            out[pre + "attn_gate_weight"] = (heads, e)
        if dense:
            out.update(_swiglu_shapes(pre + "ffn_", d["intermediate_size"],
                                      e))
            continue
        out.update({pre + "router_weight": (d["router_width"], e),
                    pre + "experts_gate_weight": (n, f, e),
                    pre + "experts_up_weight": (n, f, e),
                    pre + "experts_down_weight": (n, e, f)})
        out.update(_swiglu_shapes(pre + "shared_",
                                  d["shared_expert_intermediate_size"], e))
    return out


def param_count(cfg):
    return int(sum(np.prod(s) for s in param_shapes(cfg).values()))


def _std(cfg, name):
    """The standard deviation of one leaf's family (``assumed.weights``)."""
    if name == "tok_embed_weight":
        return float(cfg.get("embed_std", 1.0))
    if name == "lm_head_weight":
        return float(cfg.get("head_std", 0.035))
    if name.endswith("router_weight"):
        return float(cfg.get("router_std", 0.009))
    if name.endswith("attn_gate_weight"):
        return float(cfg.get("gate_std", 0.018))
    return float(cfg.get("init_std", 0.02))


def make_params(cfg, seed):
    """Seeded weights in the configuration's ``dtype`` (bfloat16: every
    value is bfloat16-representable because it IS a bfloat16), made ON THE
    DEVICE, one fused program a leaf, and left there: ``DecodeLoop`` takes a
    bfloat16 device array as it is under ``quantize="bf16"``, and the check
    reads the same buffers. N(0, std) by family, gamma 1 + 0.1 N(0, 1)."""
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
def _attended(cfg, kind, context):
    """Rows a position with ``context`` positions to attend (itself
    included) reads in a layer of ``kind``: all of them in a full layer,
    the last ``sliding_window`` in a window layer."""
    return context if kind == KINDS[1] \
        else min(int(context), _dims(cfg)["sliding_window"])


def _attn_weights(cfg, heads):
    """Elements of one attention operator's matrices, gate and norms."""
    d = _dims(cfg)
    e, q = d["hidden_size"], heads * d["head_dim"]
    return 2 * e * q + 2 * e * d["kv_width"] + e + 2 * d["head_dim"] \
        + heads * e * _gated(cfg)


def _attn_flops(cfg, heads, rows):
    """One position through one attention operator: every matrix and the
    gate's once, and per query head the scores and the mix over
    ``head_dim`` values of each of the ``rows`` it attends of ITS K/V
    head."""
    d = _dims(cfg)
    e, q = d["hidden_size"], heads * d["head_dim"]
    return 2 * (2 * e * q + 2 * e * d["kv_width"]
                + heads * e * _gated(cfg)) + 4 * q * rows


def _moe_elements(cfg):
    """``(always, each held expert)`` elements of one expert layer: the
    router, the norm and the shared expert; one routed expert."""
    d = _dims(cfg)
    e = d["hidden_size"]
    return e * d["router_width"] + e \
        + 3 * e * d["shared_expert_intermediate_size"], \
        3 * e * d["moe_intermediate_size"]


def _moe_flops(cfg):
    """One position through one expert layer on THIS share: the router, the
    shared expert and the routed experts it can expect here:
    ``num_experts_per_tok * num_experts / router_width`` of them."""
    d = _dims(cfg)
    e = d["hidden_size"]
    here = d["num_experts_per_tok"] * d["num_experts"] \
        / float(d["router_width"])
    return 2 * (e * d["router_width"]
                + 3 * e * d["shared_expert_intermediate_size"]
                + here * _moe_elements(cfg)[1])


def _dense_flops(cfg):
    d = _dims(cfg)
    return 2 * 3 * d["hidden_size"] * d["intermediate_size"]


def flops_per_position(cfg, context):
    """FLOPs one position requires with ``context`` positions to attend
    (itself included): 2 per multiply-add."""
    d = _dims(cfg)
    total = 2 * d["hidden_size"] * d["vocab_size"]
    for kind, heads, dense in layers(cfg):
        total += _attn_flops(cfg, heads, _attended(cfg, kind, context))
        total += _dense_flops(cfg) if dense else _moe_flops(cfg)
    return int(total)


def weight_bytes(cfg, itemsize=2):
    """Bytes of every weight a decode step must read once: every leaf, the
    held experts whole. The untied embedding table is left out (a step
    reads one row of it a slot); tied, it counts once, as the head."""
    shapes = param_shapes(cfg)
    return itemsize * sum(int(np.prod(s)) for k, s in shapes.items()
                          if _tied(cfg) or k != "tok_embed_weight")


def _kv_bytes(cfg, kind, contexts, itemsize):
    """Per position and layer of ``kind``: the K and V rows it attends
    read, one of each written. Never a row it does not attend."""
    return sum((_attended(cfg, kind, c) + 1) * 2 * _dims(cfg)["kv_width"]
               * itemsize for c in contexts)


def step_work(cfg, contexts, itemsize=2):
    """``(flops, bytes)`` one decode step requires for slots whose
    positions attend ``contexts`` rows each: the weights once, per position
    its K/V rows in every layer, a window layer's bounded by the window."""
    flops = sum(flops_per_position(cfg, c) for c in contexts)
    return flops, weight_bytes(cfg, itemsize) + sum(
        _kv_bytes(cfg, kind, contexts, itemsize)
        for kind, _, _ in layers(cfg))


def _attn_layer_work(cfg, kind, contexts, itemsize):
    rows = [_attended(cfg, kind, c) for c in contexts]
    flops = nbytes = 0
    for t, heads, _ in layers(cfg):
        if t == kind:
            flops += sum(_attn_flops(cfg, heads, r) for r in rows)
            nbytes += itemsize * _attn_weights(cfg, heads) \
                + _kv_bytes(cfg, kind, contexts, itemsize)
    return flops, nbytes


def window_attn_layer_work(cfg, contexts, itemsize=2):
    """``(flops, bytes)`` of the attention operator of every WINDOW layer in
    one step: its matrices, gate and norms once, the ``min(context,
    sliding_window)`` K/V rows each position attends and the row it
    writes."""
    return _attn_layer_work(cfg, KINDS[0], contexts, itemsize)


def full_attn_layer_work(cfg, contexts, itemsize=2):
    """The same of every FULL layer: each position's ``context`` rows."""
    return _attn_layer_work(cfg, KINDS[1], contexts, itemsize)


def moe_layer_work(cfg, positions, itemsize=2):
    """``(flops, bytes)`` of every expert layer in one step of
    ``positions`` positions: router, norm and shared expert once, every
    held expert once and whole. The dense layer is no expert layer."""
    moe = sum(1 for _, _, dense in layers(cfg) if not dense)
    always, each = _moe_elements(cfg)
    return moe * positions * _moe_flops(cfg), \
        moe * itemsize * (always + each * _dims(cfg)["num_experts"])


# -- the forward --------------------------------------------------------------
def rotary(cfg, kind):
    """``(inverse frequencies float64 (r / 2,), factor)`` of layers of
    ``kind``, over its ``r = head_dim * partial_rotary_factor`` rotary
    lanes: ``rope_type`` ``default`` gives ``theta^(-2j/r)`` and 1;
    ``yarn`` the blend of those (extrapolation) with the same over
    ``factor`` (interpolation), by a linear ramp over the pairs between
    those that turn ``beta_fast`` and ``beta_slow`` times within
    ``original_max_position_embeddings``, and ``attention_factor`` (``0.1
    ln(factor) + 1`` where none is given), which multiplies cos and sin."""
    rope = cfg["rope_parameters"][kind]
    dim = int(round(_dims(cfg)["head_dim"]
                    * float(rope.get("partial_rotary_factor", 1))))
    theta = float(rope["rope_theta"])
    plain = theta ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") == "default":
        return plain, 1.0
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def pair_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(pair_of(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    attention = rope.get("attention_factor")
    if attention is None:
        attention = 0.1 * math.log(factor) + 1.0
    return plain / factor * ramp + plain * (1.0 - ramp), float(attention)


def _rotate_half(x):
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rms(x, gamma, eps):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * gamma


def route(f, weight, cfg):
    """``(indices, weights)`` ``(T, k)`` of the experts each token chooses
    among all ``router_width``, in float32: the top ``k`` of ``softmax(W
    f)``, weighted by those probabilities over their plain sum (the routed
    scaling is applied to the routed sum, not here)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    logits = jnp.matmul(f.astype(f32), weight.astype(f32).T,
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, int(cfg["num_experts_per_tok"]))
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w


def forward(params, tokens, cfg, dtype="float32", operand=None, taps=None,
            block=512):
    """Logits ``(T, vocab)`` of one sequence ``tokens`` (T,), causal, the
    window layers banded. ``operand`` rounds the operands of every weight
    product but the router's through a lower precision (the fp8 control);
    the rest stays in ``dtype``. ``taps``, a dict, is given every layer's
    output (``taps["layers"]``) and every expert layer's chosen experts
    (T, k) (``taps["chosen"]``): the tests' hook. ``block``: queries scored
    at a time."""
    import contextlib
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype)
    d = _dims(cfg)
    e, groups, hd = d["hidden_size"], d["num_key_value_heads"], d["head_dim"]
    eps = float(cfg["rms_norm_eps"])
    window = d["sliding_window"]
    held = d["num_experts"]
    first = d["share_index"] * held
    scaling = float(cfg.get("moe_routed_scaling_factor", 1.0))
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
        turn = {}
        for kind in set(cfg["layer_types"]):
            inv_freq, factor = rotary(cfg, kind)
            angle = jnp.arange(t, dtype=jnp.float32)[:, None] \
                * jnp.asarray(inv_freq, jnp.float32)[None, :]
            angle = jnp.concatenate([angle, angle], axis=-1)[:, None, :]
            turn[kind] = ((jnp.float32(factor) * jnp.cos(angle)).astype(dtype),
                          (jnp.float32(factor) * jnp.sin(angle)).astype(dtype))

        def rotate(x, cos, sin):
            """The leading ``r`` lanes of each head rotated, the rest as
            they are (``r`` the kind's rotary lanes)."""
            r = cos.shape[-1]
            return jnp.concatenate(
                [x[..., :r] * cos + _rotate_half(x[..., :r]) * sin,
                 x[..., r:]], axis=-1)

        # queries in blocks: (blocks, block) positions, padded past T with
        # queries whose rows are dropped
        blocks = -(-t // block)
        qpos = jnp.arange(blocks * block).reshape(blocks, block)
        kpos = jnp.arange(t)
        scale = jnp.asarray(hd ** -0.5, dtype)

        def attend(q, k, v, banded):
            """``softmax(q k^T) v`` per head under the kind's mask, a block
            of queries at a time."""
            heads = q.shape[1]

            def one(args):
                qb, pb = args
                seen = kpos[None, :] <= pb[:, None]
                if banded:
                    seen = seen & (kpos[None, :] > pb[:, None] - window)
                s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
                s = jnp.where(seen[None], s, jnp.asarray(-1e30, dtype))
                w = jax.nn.softmax(s.astype(jnp.float32),
                                   axis=-1).astype(dtype)
                return jnp.einsum("hqk,khd->qhd", w, v)

            pad = blocks * block - t
            qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
            out = jax.lax.map(one, (qp.reshape(blocks, block, heads, hd),
                                    qpos))
            return out.reshape(blocks * block, heads, hd)[:t]

        for i, (kind, heads, dense) in enumerate(layers(cfg)):
            pre = "layer%d_" % i
            names = [k for k in params if k.startswith(pre)]
            # this layer's weights wait for the layer's input, so that the
            # layers' conversions cannot all be scheduled first
            x, p = jax.lax.optimization_barrier(
                (x, {k[len(pre):]: params[k] for k in names}))

            def gamma(name):
                return p[name].astype(dtype)

            cos, sin = turn[kind]
            a = _rms(x, gamma("op_norm_gamma"), eps)
            q = _rms(lin(a, p["attn_q_weight"]).reshape(t, heads, hd),
                     gamma("attn_q_norm_gamma"), eps)
            k = _rms(lin(a, p["attn_k_weight"]).reshape(t, groups, hd),
                     gamma("attn_k_norm_gamma"), eps)
            v = lin(a, p["attn_v_weight"]).reshape(t, groups, hd)
            q, k = rotate(q, cos, sin), rotate(k, cos, sin)
            k = jnp.repeat(k, heads // groups, axis=1)
            v = jnp.repeat(v, heads // groups, axis=1)
            o = attend(q, k, v, kind == KINDS[0])
            if _gated(cfg):
                o = o * jax.nn.sigmoid(
                    lin(a, p["attn_gate_weight"]))[:, :, None]
            x = x + lin(o.reshape(t, heads * hd), p["attn_out_weight"])
            f = _rms(x, gamma("ffn_norm_gamma"), eps)
            if dense:
                x = x + swiglu(f, p["ffn_gate_weight"], p["ffn_up_weight"],
                               p["ffn_down_weight"])
            else:
                idx, wts = route(f, p["router_weight"], cfg)
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
                x = x + swiglu(f, p["shared_gate_weight"],
                               p["shared_up_weight"],
                               p["shared_down_weight"]) \
                    + (jnp.float32(scaling) * y).astype(dtype)
            if taps is not None:
                taps.setdefault("layers", []).append(x)
        x = _rms(x, params["final_norm_gamma"].astype(dtype), eps)
        logits = lin(x, params["tok_embed_weight" if _tied(cfg)
                            else "lm_head_weight"])
    return logits.astype(jnp.float32)
