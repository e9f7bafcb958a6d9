"""Plain reference for the ``opt-1.3b`` configuration: a decoder-only
pre-LN transformer with learned positions, ReLU feed-forward and biased
projections (OPT, Zhang et al. 2022), as one full forward pass over a whole
sequence in ``jax.numpy``: no cache, no slots, no per-token steps. It
imports nothing of ``mxnet_tpu``; the parameter NAMES are the repo's
(``models/transformer.py``): ``tok_embed_weight``, ``pos_embed_weight``,
``layer{i}_{ln1,ln2}_{gamma,beta}``, ``layer{i}_attn_{qkv,out}_{weight,
bias}``, ``layer{i}_ffn_{fc1,fc2}_{weight,bias}``, ``final_ln_*``,
``lm_head_*``. ``attn_qkv_weight`` stacks q, k, v row-wise, each head-major.

Departures from the published model, the repo's own: the head is not tied
to the embedding and has a bias; positions carry no offset of 2.

``dtype`` float32 runs under ``jax.default_matmul_precision("highest")``;
bfloat16 (weights, activations and the residual stream) is the control.
"""
import numpy as np

LN_EPS = 1e-5


def param_shapes(cfg):
    e, f, v = (int(cfg["hidden_size"]), int(cfg["ffn_dim"]),
               int(cfg["vocab_size"]))
    out = {"tok_embed_weight": (v, e),
           "pos_embed_weight": (int(cfg["max_position_embeddings"]), e),
           "final_ln_gamma": (e,), "final_ln_beta": (e,),
           "lm_head_weight": (v, e), "lm_head_bias": (v,)}
    for i in range(int(cfg["num_hidden_layers"])):
        pre = "layer%d_" % i
        out.update({
            pre + "ln1_gamma": (e,), pre + "ln1_beta": (e,),
            pre + "attn_qkv_weight": (3 * e, e), pre + "attn_qkv_bias": (3 * e,),
            pre + "attn_out_weight": (e, e), pre + "attn_out_bias": (e,),
            pre + "ln2_gamma": (e,), pre + "ln2_beta": (e,),
            pre + "ffn_fc1_weight": (f, e), pre + "ffn_fc1_bias": (f,),
            pre + "ffn_fc2_weight": (e, f), pre + "ffn_fc2_bias": (e,)})
    return out


def param_count(cfg):
    return int(sum(np.prod(s) for s in param_shapes(cfg).values()))


def make_params(cfg, seed, threads=8):
    """Seeded float32 weights as host arrays (the serving loop takes host
    arrays and places them itself): one vectorised generator call per leaf,
    leaves spread over a few threads (numpy's generators release the GIL).
    N(0, init_std) matrices as OPT initialises them, gamma near one."""
    from concurrent.futures import ThreadPoolExecutor
    shapes = param_shapes(cfg)
    names = sorted(shapes)
    std = float(cfg.get("init_std", 0.02))

    def make(i):
        name = names[i]
        rng = np.random.Generator(np.random.Philox(key=[int(seed), i]))
        x = rng.standard_normal(shapes[name], dtype=np.float32)
        if name.endswith("_gamma"):
            x *= np.float32(0.1)
            x += np.float32(1.0)
        else:
            x *= np.float32(std)
        return x

    with ThreadPoolExecutor(threads) as pool:
        leaves = list(pool.map(make, range(len(names))))
    return dict(zip(names, leaves))


def flops_per_position(cfg, context):
    """FLOPs one position requires with ``context`` positions to attend
    (itself included): 2 per multiply-add over the projections, the
    feed-forward, the head, and the attention scores and mix."""
    e, f, v, layers = (int(cfg["hidden_size"]), int(cfg["ffn_dim"]),
                       int(cfg["vocab_size"]), int(cfg["num_hidden_layers"]))
    per_layer = 2 * (3 * e * e + e * e + 2 * e * f) + 2 * 2 * e * context
    return layers * per_layer + 2 * e * v


def weight_bytes(cfg, itemsize=4):
    """Bytes of every weight a decode step must read once: all leaves but
    the two embedding tables, of which a step reads one row per position."""
    shapes = param_shapes(cfg)
    n = sum(int(np.prod(s)) for k, s in shapes.items()
            if k not in ("tok_embed_weight", "pos_embed_weight"))
    return n * itemsize


def step_work(cfg, contexts, itemsize=4):
    """``(flops, bytes)`` one decode step requires for slots whose
    positions attend ``contexts`` rows each: the weights once, and per
    position its K and V rows read, one K and one V row written, and two
    embedding rows."""
    e, layers = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    flops = sum(flops_per_position(cfg, c) for c in contexts)
    cache = sum(2 * layers * (c + 1) * e * itemsize for c in contexts)
    return flops, weight_bytes(cfg, itemsize) + cache \
        + 2 * e * itemsize * len(contexts)


def _ln(x, gamma, beta):
    import jax
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + jnp.asarray(LN_EPS, x.dtype)) \
        * gamma + beta


def forward(params, tokens, cfg, dtype="float32", operand=None):
    """Logits ``(T, vocab)`` of one sequence ``tokens`` (T,), causal.
    ``operand`` rounds the operands of every weight product through a lower
    precision (the fp8 control); the rest stays in ``dtype``."""
    import contextlib
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype)
    heads = int(cfg["num_attention_heads"])
    ctx = (jax.default_matmul_precision("highest")
           if dtype == jnp.float32 else contextlib.nullcontext())
    with ctx:
        p = {k: v.astype(dtype) for k, v in params.items()}

        def lin(x, name):
            w = p[name + "_weight"]
            if operand is not None:
                x = x.astype(operand).astype(dtype)
                w = w.astype(operand).astype(dtype)
            return x @ w.T + p[name + "_bias"]

        t = tokens.shape[0]
        x = p["tok_embed_weight"][tokens] + p["pos_embed_weight"][:t]
        e = x.shape[1]
        d = e // heads
        causal = jnp.tril(jnp.ones((t, t), bool))
        for i in range(int(cfg["num_hidden_layers"])):
            pre = "layer%d_" % i
            a = _ln(x, p[pre + "ln1_gamma"], p[pre + "ln1_beta"])
            qkv = lin(a, pre + "attn_qkv")
            q, k, v = (qkv[:, j * e:(j + 1) * e].reshape(t, heads, d)
                       for j in range(3))
            s = jnp.einsum("qhd,khd->hqk", q, k) \
                * jnp.asarray(1.0 / np.sqrt(d), dtype)
            s = jnp.where(causal[None], s, jnp.asarray(-1e30, dtype))
            w = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("hqk,khd->qhd", w, v).reshape(t, e)
            x = x + lin(o, pre + "attn_out")
            h = _ln(x, p[pre + "ln2_gamma"], p[pre + "ln2_beta"])
            h = jnp.maximum(lin(h, pre + "ffn_fc1"), 0)
            x = x + lin(h, pre + "ffn_fc2")
        x = _ln(x, p["final_ln_gamma"], p["final_ln_beta"])
        logits = lin(x, "lm_head")
    return logits.astype(jnp.float32)
