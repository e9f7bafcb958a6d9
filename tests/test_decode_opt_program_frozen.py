"""The frozen PR 28 token pass is the REFERENCE the live OPT programs are
held to (PERF.md, PRs 29 and 35). Below is a FROZEN copy of
``serving/decode.py``'s token pass, decode body and verify body as they
stood at PR 28, which reads and masks ALL the rows of the cache; the
programs built from the loop's own builders give the same tokens, the same
rows written and logits within float32 rounding, input for input, for
float32, bfloat16 and int8 parameter trees, for the single-token body and
the speculative window.

RE-ANCHORED at PR 35: until then the live programs had to LOWER TO THE SAME
TEXT as the frozen ones (the architecture protocol of PR 29 was to move no
OPT cell). From PR 35 the live pass attends a prefix of the cache's rows
picked from ``pos`` inside a ``lax.switch`` (``serving/blocks.py``,
``over_filled_rows``), so its text differs by design; what must not move
is what it COMPUTES. The rows a branch leaves out are rows the frozen pass
masks to a softmax weight of exactly 0, so the two differ by the order of a
reduction's partial sums and nothing else: the cases below run both on a
cache full of noise at every edge of the ladder (the deepest position one
under a rung, on it, and on the last row), with a slot at position 0 beside
the deepest one.

RE-FROZEN at PR 33, ``decode_fn`` ONLY: the single-token body takes a
slot's input token from the device where the host marks it (a negative id
selects ``state["tok"]``, the token the body sampled for that slot the step
before, which it now also leaves in the state), so the loop can dispatch a
step before it has read the last one back. Nothing else of the body moved;
``_build_token_pass`` and ``verify_fn`` below are PR 28's, untouched (the
verify body's state carries no ``tok``).

The frozen bodies call the LIVE ``sample_rows``: a change that stays
inside ``sample_rows`` (PR 30's ``lax.cond`` around the sampler) reaches
both sides alike and this file passes unedited; ``sample_rows`` itself is
held against its own frozen body in ``tests/test_sampling_branch.py``."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chip_smoke
from mxnet_tpu.serving import blocks, decode
from mxnet_tpu.serving.quantize import dequant_tree, quantize_tree
from mxnet_tpu.serving.sampling import position_uniforms, sample_rows

LAYERS, HEADS, VOCAB, EMBED, SLOTS, ROWS = 2, 2, 48, 128, 3, 384
#: the positions of the three slots: the deepest one under the first rung's
#: edge, on it, on the last row, and a step whose slots are all shallow
EDGES = [(0, 191, 40), (192, 0, 7), (3, 100, 383), (5, 3, 0)]


# ---- frozen at PR 28 (commit 09dcbac): do not edit ------------------------
def _ln(x, gamma, beta):
    import jax
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + jnp.float32(1e-5)) * gamma + beta


def _build_token_pass(num_layers, num_heads, mesh=None):
    """ONE position per slot through every layer, reading and writing the
    (layers, slots, rows, heads * head_dim) KV cache. Matches
    models/transformer.py op-for-op (pre-LN blocks, qkv packing, 1/sqrt(d)
    scaling) so greedy decode agrees with the full forward.

    This is the shared per-position pass: the single-token decode body
    runs it once, the speculative verify body unrolls it over the window —
    a position computes the IDENTICAL op sequence through either, which is
    what makes speculative output token-identical to target-only decode.

    THE HEADS ARE FOLDED INTO THE MINOR DIMENSION because the chip tiles
    an array's two minor dimensions into (8 sublanes, 128 lanes): ``heads
    * head_dim`` (the model's width, a multiple of 128) fills the lanes
    and ``rows`` the sublanes, so the donated buffer holds no padding and
    the step program computes in the layout the runtime stores. With
    ``head_dim`` = 64 alone in the minor dimension the compiler re-laid
    both caches out on the way into and out of every step: four
    cache-sized copies, 56% of the step (PERF.md, PR 28). A position's
    write is one contiguous row per slot. The minor dimension is never
    reshaped into (heads, head_dim), which would bring the padding back:
    the per-head contractions are a float32 multiply over all lanes and a
    sum of each head's lanes (``heads_sum``), and the mix spreads a head's
    weight over its lanes (``heads_spread``) before a float32 multiply and
    a sum over rows. Both go through a 0/1 matrix at ``HIGHEST``
    precision, where a product with 1 is exact: the same float32 products
    and sums as ever, with no bfloat16 rounding anywhere.

    The write/embed position is clamped to the last cache row. Rows past
    ``max_len`` are TRASH rows: a speculative window's positions past
    ``max_len`` land there and no valid query ever attends them (the
    causal mask covers rows ``<= pos`` and live positions are
    ``< max_len``); for live positions the clamp is an index identity.

    With a model ``mesh`` the residual stream is pinned REPLICATED at
    every block boundary while the KV cache and the attention math stay
    sharded over heads: the lanes split into one GROUP of whole heads per
    shard and ``heads_sum``/``heads_spread`` work group by group, so
    per-head contractions never cross shards and the sharded loop emits
    the same tokens as the single-chip one (docs/serving.md
    "Model-parallel replicas")."""
    import jax.numpy as jnp
    import jax

    if mesh is not None:
        _repl = jax.sharding.NamedSharding(mesh,
                                           jax.sharding.PartitionSpec())

        def edge(x):
            return jax.lax.with_sharding_constraint(x, _repl)
    else:
        def edge(x):
            return x

    groups = 1 if mesh is None else int(mesh.devices.size)
    highest = jax.lax.Precision.HIGHEST

    def token_pass(ck, cv, params, tokens, pos):
        nslots = tokens.shape[0]
        rows = ck.shape[2]
        wpos = jnp.minimum(pos, jnp.int32(rows - 1))
        with jax.named_scope("embed"):
            x = edge(params["tok_embed_weight"][tokens]
                     + params["pos_embed_weight"][wpos])
        embed = x.shape[1]
        d = embed // num_heads
        scale = jnp.float32(1.0 / float(np.sqrt(d)))
        sidx = jnp.arange(nslots)
        tmask = (jnp.arange(rows)[None, :] <= pos[:, None])[:, :, None]
        neg = jnp.float32(-1e30)
        # lane e of a group belongs to the group's head e // d
        lanes, gheads = embed // groups, num_heads // groups
        seg = (jnp.arange(lanes)[:, None] // d
               == jnp.arange(gheads)[None, :]).astype(jnp.float32)

        def heads_sum(p):       # (slots, rows, embed) -> (slots, rows, heads)
            p = p.reshape(nslots, rows, groups, lanes)
            return jnp.einsum("stge,eh->stgh", p, seg, precision=highest
                              ).reshape(nslots, rows, num_heads)

        def heads_spread(w):    # (slots, rows, heads) -> (slots, rows, embed)
            w = w.reshape(nslots, rows, groups, gheads)
            return jnp.einsum("stgh,eh->stge", w, seg, precision=highest
                              ).reshape(nslots, rows, embed)

        # the scope names are what an operator searches a device trace
        # for: the same in every layer, so they sum by kind
        for i in range(num_layers):
            pre = "layer%d" % i
            with jax.named_scope("layer/attn"):
                a = _ln(x, params[pre + "_ln1_gamma"],
                        params[pre + "_ln1_beta"])
                qkv = a @ params[pre + "_attn_qkv_weight"].T \
                    + params[pre + "_attn_qkv_bias"]
                qkv = qkv.reshape(nslots, 3, embed)
                q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]   # (slots, H * D)
            with jax.named_scope("cache_write"):
                ck = ck.at[i, sidx, wpos].set(k)
                cv = cv.at[i, sidx, wpos].set(v)
            with jax.named_scope("layer/attn"):
                s = heads_sum(q[:, None, :] * ck[i]) * scale
                s = jnp.where(tmask, s, neg)
                w = jax.nn.softmax(s, axis=1)
                o = jnp.sum(heads_spread(w) * cv[i], axis=1)
                o = o @ params[pre + "_attn_out_weight"].T \
                    + params[pre + "_attn_out_bias"]
                x = edge(x + o)
            with jax.named_scope("layer/mlp"):
                f = _ln(x, params[pre + "_ln2_gamma"],
                        params[pre + "_ln2_beta"])
                f = jnp.maximum(
                    f @ params[pre + "_ffn_fc1_weight"].T
                    + params[pre + "_ffn_fc1_bias"], jnp.float32(0.0))
                f = f @ params[pre + "_ffn_fc2_weight"].T \
                    + params[pre + "_ffn_fc2_bias"]
                x = edge(x + f)
        with jax.named_scope("head"):
            x = _ln(x, params["final_ln_gamma"], params["final_ln_beta"])
            logits = x @ params["lm_head_weight"].T + params["lm_head_bias"]
        return ck, cv, logits

    return token_pass


# (re-frozen at PR 33 with the token select: the two lines marked so)
def _build_decode_fn(num_layers, num_heads, mesh=None):
    """The single-token decode body: one position per slot, sampled
    in-graph. Returns ``(state, next_tokens)`` — the host reads back one
    (slots,) int32 vector, never the logits."""
    token_pass = _build_token_pass(num_layers, num_heads, mesh=mesh)

    def decode_fn(state, params, tokens, pos, temp, top_k, top_p,
                  fresh_seed, reseed):
        import jax
        import jax.numpy as jnp
        seeds = jnp.where(reseed, fresh_seed, state["seed"])
        tokens = jnp.where(tokens < 0, state["tok"], tokens)      # PR 33
        p = dequant_tree(params)
        ck, cv, logits = token_pass(state["k"], state["v"], p, tokens, pos)
        with jax.named_scope("sample"):
            u = position_uniforms(seeds, pos)
            nxt = sample_rows(logits, u, temp, top_k, top_p)
        return {"k": ck, "v": cv, "seed": seeds, "tok": nxt}, nxt  # PR 33

    return decode_fn


def _build_verify_fn(num_layers, num_heads, window, mesh=None):
    """The speculative verify body: ``window`` positions per slot through
    the SAME per-position pass as the single-token body, unrolled (the
    cache threads through, so position j attends the rows j' < j wrote),
    each position sampled with its own (seed, position) uniform. One
    dispatch scores and samples the whole window."""
    token_pass = _build_token_pass(num_layers, num_heads, mesh=mesh)

    def verify_fn(state, params, tokens_w, pos0, temp, top_k, top_p,
                  fresh_seed, reseed):
        import jax
        import jax.numpy as jnp
        seeds = jnp.where(reseed, fresh_seed, state["seed"])
        p = dequant_tree(params)
        ck, cv = state["k"], state["v"]
        outs = []
        for j in range(window):
            pos_j = pos0 + jnp.int32(j)
            ck, cv, logits = token_pass(ck, cv, p, tokens_w[:, j], pos_j)
            with jax.named_scope("sample"):
                u = position_uniforms(seeds, pos_j)
                outs.append(sample_rows(logits, u, temp, top_k, top_p))
        return ({"k": ck, "v": cv, "seed": seeds},
                jnp.stack(outs, axis=1))

    return verify_fn


# ---- end of the frozen copy ------------------------------------------------


def _inputs(mode, fed_back=False):
    """Concrete parameters, a cache FULL OF NOISE (what a retired request
    leaves behind: a row the mask must hide changes the result if it does
    not) and the seven per-slot arrays less ``tokens`` and ``pos``."""
    assert blocks.rows_ladder(ROWS) == (192, ROWS)
    params = jax.tree_util.tree_map(jnp.asarray, quantize_tree(
        chip_smoke.lm_params(VOCAB, EMBED, HEADS, LAYERS, ROWS, seed=1),
        mode))
    rs = np.random.RandomState(3)
    noise = lambda: jnp.asarray(
        rs.randn(LAYERS, SLOTS, ROWS, EMBED).astype(np.float32))
    state = {"k": noise(), "v": noise(),
             "seed": jnp.zeros((SLOTS,), np.uint32)}
    if fed_back:        # the decode body's state; the verify body's has none
        state["tok"] = jnp.asarray([11, 12, 13], np.int32)
    n = (SLOTS,)
    samp = [jnp.zeros(n, np.float32), jnp.zeros(n, np.int32),
            jnp.ones(n, np.float32), jnp.zeros(n, np.uint32),
            jnp.zeros(n, np.bool_)]
    return state, params, samp


def _same(new, old):
    """Tokens and seeds equal; the rows written and the logits within
    float32 rounding of values of their size (the residual stream of the
    second layer already carries the first layer's reordered sums)."""
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype
        if a.dtype.kind in "iub":
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(
                a, b, rtol=0, atol=32 * np.finfo(np.float32).eps
                * float(np.abs(b).max()))


@pytest.mark.parametrize("mode", ["none", "bf16", "int8"])
def test_the_step_program_is_the_parents(mode):
    state, params, samp = _inputs(mode, fed_back=True)
    old = jax.jit(_build_decode_fn(LAYERS, HEADS))
    new = jax.jit(decode._build_decode_fn(decode.OptArch(LAYERS, HEADS)))
    old_pass = jax.jit(_build_token_pass(LAYERS, HEADS))
    new_pass = jax.jit(decode._build_token_pass(LAYERS, HEADS))
    for pos in EDGES:
        pos = jnp.asarray(pos, np.int32)
        tokens = jnp.asarray([3, decode.FED_BACK, 7], np.int32)
        _same(new(state, params, tokens, pos, *samp),
              old(state, params, tokens, pos, *samp))
        p = dequant_tree(params)
        _same(new_pass(state["k"], state["v"], p, state["tok"], pos),
              old_pass(state["k"], state["v"], p, state["tok"], pos))


@pytest.mark.parametrize("window", [2, 3])
def test_the_verify_program_is_the_parents(window):
    """The window crosses a rung's edge between two of its positions, and
    runs past the last row (the trash row's position) in the deepest slot."""
    state, params, samp = _inputs("none")
    old = jax.jit(_build_verify_fn(LAYERS, HEADS, window))
    new = jax.jit(decode._build_verify_fn(decode.OptArch(LAYERS, HEADS),
                                          window))
    tokens = jnp.asarray(np.random.RandomState(5).randint(
        0, VOCAB, (SLOTS, window)), np.int32)
    for pos0 in [(0, 192 - window, 40), (191, 0, 7), (3, 100, ROWS - 2)]:
        pos0 = jnp.asarray(pos0, np.int32)
        _same(new(state, params, tokens, pos0, *samp),
              old(state, params, tokens, pos0, *samp))


def test_the_loop_builds_its_step_from_those_builders():
    """The loop's own state and feed are the frozen program's arguments:
    K, V, seeds and the fed-back token, seven per-slot arrays, no eighth
    (``tests/test_decode_prefill.py`` holds the prefill program to the
    frozen pass)."""
    params = chip_smoke.lm_params(VOCAB, EMBED, HEADS, LAYERS, ROWS, seed=1)
    loop = decode.DecodeLoop(params, LAYERS, HEADS, ROWS, slots=SLOTS,
                             prefix_cache=False, spec_k=0)
    try:
        assert sorted(loop._state) == ["k", "seed", "tok", "v"]
        assert isinstance(loop._arch, decode.OptArch)
        assert not loop._arch.wants_live and not loop._arch.counters()
        # beside the step, from PR 37, the prefill program: the same state
        # and parameters, a chunk of tokens and three scalars
        assert sorted(n.split("/")[1].split("[")[0]
                      for n in loop._programs) == ["prefill", "step"]
        for name, (_, structs, donate) in loop._programs.items():
            assert donate == (0,)
            assert len(structs) == (6 if "/prefill[" in name else 9)
        assert loop.counter_totals() == {}
    finally:
        loop.close()
