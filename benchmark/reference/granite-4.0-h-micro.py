"""Plain reference for the ``granite-4.0-h-micro`` configuration: IBM's
Granite 4.0-H block (``model_type: granitemoehybrid`` with no experts;
``transformers/models/granitemoehybrid/modeling_granitemoehybrid.py``), as
one full forward pass over a whole sequence in ``jax.numpy``: no cache, no
state, no slots, no per-token steps. It imports nothing of ``mxnet_tpu``;
the parameter NAMES are the repo's (``serving/granite_hybrid.py``).

Layer: ``h = x + r * Mixer(RMSNorm(x; op_norm))``, ``y = h + r *
SwiGLU(RMSNorm(h; ffn_norm))``, ``r = residual_multiplier``; the embedding
times ``embedding_multiplier``; one RMSNorm after the last layer; the head
tied to the embedding, its logits over ``logits_scaling``.

Mamba-2 mixer (``layer_types[i] == "mamba"``, one group): ``[z, xBC, dt] =
W_in a``; ``xBC = silu(causal depthwise convolution of xBC over the
sequence + bias)`` (zeros before position 0, the kernel's last column on
the current position); ``[x, B, C] = split(xBC)``; ``dt = softplus(dt +
dt_bias)``, ``A = -exp(A_log)``. The SSM in its QUADRATIC ("SSD",
attention-like) form over the whole sequence, per head ``h``::

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{r = s+1..t} dt_r A) dt_s x_s
          + D x_t

with the exponent a masked SEGMENT SUM (each entry summed from its own
terms, as the published code's ``segment_sum``), never a recurrence: it
shares nothing with a step's state. Then ``RMSNorm(y * silu(z); norm)``
over all heads' lanes, ``W_out``.

Attention (``"attention"``): ``q = W_q a`` as heads, ``k``, ``v`` as K/V
heads, MATERIALISED and repeated over the query heads of their group; NO
positional encoding (``position_embedding_type: nope``); scores times
``attention_multiplier``, causal softmax; ``W_o`` over the heads.

Departures from the published code: the residual stream is float32
(published: the checkpoint's bfloat16); no ``time_step_limit`` (the
published code's is ``(0, inf)``); the segment sums and their ``exp`` are
float32 in every precision (below).

``dtype`` float32 runs under ``jax.default_matmul_precision("highest")``;
bfloat16 (weights, activations, the SSM's products and the residual stream)
and bfloat16 with the operands of every weight product rounded through
``operand`` (fp8) are the controls; in both the segment sums are taken in
float32 and their ``exp`` rounded to ``dtype`` (a bfloat16 running sum of
hundreds of decays is not this model at a lower precision but another).
A weight is converted to ``dtype`` where it is used, and a layer's weights
wait behind a barrier for the layer's input: no float32 copy of more than
one layer's weights lives at once, beside 6.4 GB of bfloat16 weights.
"""
import numpy as np


# -- sizes --------------------------------------------------------------------
def _dims(cfg):
    d = {k: int(cfg[k]) for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "num_hidden_layers", "vocab_size", "shared_intermediate_size",
        "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv")}
    d["head_dim"] = d["hidden_size"] // d["num_attention_heads"]
    d["kv_width"] = d["num_key_value_heads"] * d["head_dim"]
    d["inner"] = d["mamba_n_heads"] * d["mamba_d_head"]
    d["conv_width"] = d["inner"] + 2 * d["mamba_d_state"]
    return d


def param_shapes(cfg):
    d = _dims(cfg)
    e, f, heads = d["hidden_size"], d["shared_intermediate_size"], \
        d["mamba_n_heads"]
    out = {"tok_embed_weight": (d["vocab_size"], e),
           "final_norm_gamma": (e,)}
    for i, kind in enumerate(cfg["layer_types"]):
        pre = "layer%d_" % i
        out.update({pre + "op_norm_gamma": (e,),
                    pre + "ffn_norm_gamma": (e,),
                    pre + "ffn_gate_weight": (f, e),
                    pre + "ffn_up_weight": (f, e),
                    pre + "ffn_down_weight": (e, f)})
        if kind == "mamba":
            out.update({
                pre + "ssm_in_weight": (d["inner"] + d["conv_width"] + heads,
                                        e),
                pre + "ssm_conv_weight": (d["conv_width"], d["mamba_d_conv"]),
                pre + "ssm_conv_bias": (d["conv_width"],),
                pre + "ssm_dt_bias": (heads,),
                pre + "ssm_A_log": (heads,),
                pre + "ssm_D": (heads,),
                pre + "ssm_norm_gamma": (d["inner"],),
                pre + "ssm_out_weight": (e, d["inner"])})
        else:
            out.update({pre + "attn_q_weight": (e, e),
                        pre + "attn_k_weight": (d["kv_width"], e),
                        pre + "attn_v_weight": (d["kv_width"], e),
                        pre + "attn_out_weight": (e, e)})
    return out


def param_count(cfg):
    return int(sum(np.prod(s) for s in param_shapes(cfg).values()))


#: how each leaf is drawn (``assumed.weights``): name suffix -> family
_FAMILIES = (("_gamma", "gamma"), ("ssm_A_log", "A_log"), ("ssm_D", "ones"),
             ("ssm_dt_bias", "dt_bias"), ("ssm_conv_weight", "conv"),
             ("ssm_conv_bias", "conv"), ("ssm_in_weight", "ssm_in"),
             ("_out_weight", "out"), ("ffn_down_weight", "out"),
             ("tok_embed_weight", "embed"))


def _family(name):
    for suffix, family in _FAMILIES:
        if name.endswith(suffix):
            return family
    return "matrix"


def make_params(cfg, seed):
    """Seeded weights in the configuration's ``dtype`` (bfloat16: every
    value is bfloat16-representable because it IS a bfloat16), made ON THE
    DEVICE, one fused program a leaf, and left there. Mamba-2's own
    initialisation for its parameters: ``A_log = log(1..heads)``, ``D = 1``,
    ``dt_bias`` the inverse softplus of a log-uniform draw in ``[1e-3,
    1e-1]``, the depthwise kernel and its bias ``nn.Conv1d``'s default
    (uniform within ``1 / sqrt(d_conv)``); norms ``1 + 0.1 N(0, 1)``; the
    embedding ``N(0, embed_std)``, ``W_in`` ``N(0, ssm_in_std)``, the
    three matrices that write the residual stream (``ssm_out``,
    ``attn_out``, ``ffn_down``) ``N(0, out_std)``, every other matrix
    ``N(0, init_std)``."""
    import functools
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg.get("dtype", "bfloat16"))
    f32 = jnp.float32
    bound = 1.0 / float(np.sqrt(int(cfg["mamba_d_conv"])))
    stds = {"embed": float(cfg.get("embed_std", 0.25)),
            "ssm_in": float(cfg.get("ssm_in_std", 0.05)),
            "out": float(cfg.get("out_std", 1.5)),
            "matrix": float(cfg.get("init_std", 0.02))}

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def make(key, shape, family):
        if family == "A_log":
            x = jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32))
        elif family == "ones":
            x = jnp.ones(shape, f32)
        elif family == "dt_bias":
            lo, hi = np.log(1e-3), np.log(1e-1)
            dt = jnp.exp(jax.random.uniform(key, shape, f32, lo, hi))
            x = dt + jnp.log(-jnp.expm1(-dt))        # softplus(x) = dt
        elif family == "conv":
            x = jax.random.uniform(key, shape, f32, -bound, bound)
        elif family == "gamma":
            x = 1.0 + 0.1 * jax.random.normal(key, shape, f32)
        else:
            x = jax.random.normal(key, shape, f32) * f32(stds[family])
        return x.astype(dtype)

    root = jax.random.PRNGKey(int(seed))
    shapes = param_shapes(cfg)
    return {name: make(jax.random.fold_in(root, i), tuple(shapes[name]),
                       _family(name))
            for i, name in enumerate(sorted(shapes))}


# -- the work a step requires, from the shapes --------------------------------
def _kinds(cfg):
    """``(mamba layers, attention layers)``."""
    kinds = list(cfg["layer_types"])
    return kinds.count("mamba"), kinds.count("attention")


def _mamba_weights(cfg):
    """Elements of one mamba mixer: both projections, the kernel and its
    bias, the three per-head vectors, the gated norm and the op norm."""
    d = _dims(cfg)
    e, heads = d["hidden_size"], d["mamba_n_heads"]
    return ((d["inner"] + d["conv_width"] + heads) * e + d["inner"] * e
            + (d["mamba_d_conv"] + 1) * d["conv_width"] + 3 * heads
            + d["inner"] + e)


def _mamba_flops(cfg):
    """One position through one mamba mixer: both projections, the
    kernel's taps, and the recurrence: per state element a decay and an
    outer-product term (3) and the read-out ``S C`` (2)."""
    d = _dims(cfg)
    e = d["hidden_size"]
    return (2 * (d["inner"] + d["conv_width"] + d["mamba_n_heads"]) * e
            + 2 * d["inner"] * e + 2 * d["mamba_d_conv"] * d["conv_width"]
            + 5 * d["inner"] * d["mamba_d_state"])


def _state_bytes(cfg, positions, itemsize):
    """Per position and mamba layer: its float32 state read and written
    whole, and its conv state's ``mamba_d_conv - 1`` rows read and one
    written."""
    d = _dims(cfg)
    return positions * (2 * d["inner"] * d["mamba_d_state"] * 4
                        + d["mamba_d_conv"] * d["conv_width"] * itemsize)


def _attn_weights(cfg):
    d = _dims(cfg)
    e = d["hidden_size"]
    return 2 * e * e + 2 * e * d["kv_width"] + e


def _attn_flops(cfg, context):
    """One position through one attention operator: every matrix once, and
    per query head the scores and the mix over ``head_dim`` values of each
    of ``context`` rows of ITS K/V head."""
    d = _dims(cfg)
    e = d["hidden_size"]
    return 2 * (2 * e * e + 2 * e * d["kv_width"]) + 4 * e * context


def _kv_bytes(cfg, contexts, itemsize):
    """Per attention layer: each position's K and V rows read, one of each
    written."""
    return sum((c + 1) * 2 * _dims(cfg)["kv_width"] * itemsize
               for c in contexts)


def flops_per_position(cfg, context):
    """FLOPs one position requires with ``context`` positions to attend
    (itself included): 2 per multiply-add."""
    d = _dims(cfg)
    mamba, attn = _kinds(cfg)
    e = d["hidden_size"]
    return int(mamba * _mamba_flops(cfg) + attn * _attn_flops(cfg, context)
               + (mamba + attn) * 2 * 3 * e * d["shared_intermediate_size"]
               + 2 * e * d["vocab_size"])


def weight_bytes(cfg, itemsize=2):
    """Bytes of every weight a decode step must read once: every leaf, the
    tied embedding once, as the head (the rows a step embeds are among
    them)."""
    return itemsize * param_count(cfg)


def step_work(cfg, contexts, itemsize=2):
    """``(flops, bytes)`` one decode step requires for slots whose
    positions attend ``contexts`` rows each: the weights once, per position
    its K/V rows in every attention layer and, in every mamba layer, its
    float32 state read and written (4 bytes an element) and its conv
    state."""
    mamba, attn = _kinds(cfg)
    flops = sum(flops_per_position(cfg, c) for c in contexts)
    return flops, weight_bytes(cfg, itemsize) \
        + attn * _kv_bytes(cfg, contexts, itemsize) \
        + mamba * _state_bytes(cfg, len(contexts), itemsize)


def ssm_layer_work(cfg, positions, itemsize=2):
    """``(flops, bytes)`` of the mixer of every mamba layer in one step of
    ``positions`` positions: its projections, kernel and norms once, and
    each position's state (2 MiB read and written at the published widths)
    and conv rows."""
    mamba = _kinds(cfg)[0]
    return mamba * positions * _mamba_flops(cfg), \
        mamba * (itemsize * _mamba_weights(cfg)
                 + _state_bytes(cfg, positions, itemsize))


# -- the forward --------------------------------------------------------------
def _rms(x, gamma, eps):
    import jax
    import jax.numpy as jnp
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * gamma


def segment_sum(a):
    """``out[..., t, s] = sum_{r = s+1..t} a[..., r]`` for ``s <= t`` and
    ``-inf`` above the diagonal, each entry summed from its own terms. The
    running sum is a scan of additions, the same elementwise float32 adds
    on every backend: ``cumsum`` lowers to a scan on the CPU but to a
    two-level ``reduce-window`` on the TPU, so a reference built on it
    would not do the same arithmetic on the two."""
    import jax
    import jax.numpy as jnp
    t = a.shape[-1]
    x = jnp.broadcast_to(a[..., :, None], a.shape + (t,))
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x, 0.0)
    out = jax.lax.associative_scan(jnp.add, x, axis=x.ndim - 2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), out, -jnp.inf)


def ssd(x, dt, a, b, c, dtype):
    """The SSM's quadratic form over a whole sequence: ``x`` (T, heads, P),
    ``dt`` (T, heads), ``a`` = A (heads,), ``b`` and ``c`` (T, N). Returns
    ``sum_{s <= t} (C_t . B_s) exp(segment_sum(dt A))[t, s] dt_s x_s``
    (T, heads, P), the ``D`` term left to the caller."""
    import jax.numpy as jnp
    f32 = jnp.float32
    decay = jnp.exp(segment_sum((dt.astype(f32) * a.astype(f32)).T))
    scores = (c @ b.T)[None] * decay.astype(dtype) * dt.T[:, None, :]
    return jnp.einsum("hts,shp->thp", scores, x)


def forward(params, tokens, cfg, dtype="float32", operand=None, taps=None):
    """Logits ``(T, vocab)`` of one sequence ``tokens`` (T,), causal.
    ``operand`` rounds the operands of every weight product through a
    lower precision (the fp8 control); the rest stays in ``dtype``.
    ``taps``, a dict, is given every layer's output (``taps["layers"]``):
    the tests' hook."""
    import contextlib
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype)
    d = _dims(cfg)
    e, heads, groups, hd = (d["hidden_size"], d["num_attention_heads"],
                            d["num_key_value_heads"], d["head_dim"])
    mh, mp, n, kernel = (d["mamba_n_heads"], d["mamba_d_head"],
                         d["mamba_d_state"], d["mamba_d_conv"])
    inner, width = d["inner"], d["conv_width"]
    eps = float(cfg["rms_norm_eps"])
    res = jnp.asarray(float(cfg["residual_multiplier"]), dtype)
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

        t = tokens.shape[0]
        x = params["tok_embed_weight"][tokens].astype(dtype) \
            * jnp.asarray(float(cfg["embedding_multiplier"]), dtype)
        causal = jnp.tril(jnp.ones((t, t), bool))
        scale = jnp.asarray(float(cfg["attention_multiplier"]), dtype)
        for i, kind in enumerate(cfg["layer_types"]):
            pre = "layer%d_" % i
            names = [k for k in params if k.startswith(pre)]
            # this layer's weights wait for the layer's input, so that the
            # layers' conversions cannot all be scheduled first
            x, p = jax.lax.optimization_barrier(
                (x, {k[len(pre):]: params[k] for k in names}))

            def vec(name):
                return p[name].astype(dtype)

            a = _rms(x, vec("op_norm_gamma"), eps)
            if kind == "mamba":
                z, xbc, dt = jnp.split(lin(a, p["ssm_in_weight"]),
                                       [inner, inner + width], axis=-1)
                # causal and depthwise: channel ch of position s sees
                # xBC[s - (kernel - 1) .. s, ch] under its own kernel row
                conv = jax.lax.conv_general_dilated(
                    xbc.T[None], vec("ssm_conv_weight")[:, None, :],
                    window_strides=(1,), padding=[(kernel - 1, 0)],
                    feature_group_count=width)[0].T
                xbc = jax.nn.silu(conv + vec("ssm_conv_bias"))
                xs, b, c = jnp.split(xbc, [inner, inner + n], axis=-1)
                dt = jax.nn.softplus(dt + vec("ssm_dt_bias"))
                xs = xs.reshape(t, mh, mp)
                y = ssd(xs, dt, -jnp.exp(p["ssm_A_log"].astype(jnp.float32)),
                        b, c, dtype) + xs * vec("ssm_D")[None, :, None]
                g = y.reshape(t, inner) * jax.nn.silu(z)
                x = x + res * lin(_rms(g, vec("ssm_norm_gamma"), eps),
                                  p["ssm_out_weight"])
            else:
                q = lin(a, p["attn_q_weight"]).reshape(t, heads, hd)
                k = lin(a, p["attn_k_weight"]).reshape(t, groups, hd)
                v = lin(a, p["attn_v_weight"]).reshape(t, groups, hd)
                k = jnp.repeat(k, heads // groups, axis=1)
                v = jnp.repeat(v, heads // groups, axis=1)
                s = jnp.einsum("qhd,khd->hqk", q, k) * scale
                s = jnp.where(causal[None], s, jnp.asarray(-1e30, dtype))
                w = jax.nn.softmax(s.astype(jnp.float32),
                                   axis=-1).astype(dtype)
                o = jnp.einsum("hqk,khd->qhd", w, v).reshape(t, heads * hd)
                x = x + res * lin(o, p["attn_out_weight"])
            f = _rms(x, vec("ffn_norm_gamma"), eps)
            x = x + res * lin(jax.nn.silu(lin(f, p["ffn_gate_weight"]))
                              * lin(f, p["ffn_up_weight"]),
                              p["ffn_down_weight"])
            if taps is not None:
                taps.setdefault("layers", []).append(x)
        x = _rms(x, params["final_norm_gamma"].astype(dtype), eps)
        logits = lin(x, params["tok_embed_weight"]) \
            / jnp.asarray(float(cfg["logits_scaling"]), dtype)
    return logits.astype(jnp.float32)
