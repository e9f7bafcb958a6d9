"""What more than one :class:`~mxnet_tpu.serving.arch.Architecture`'s token
pass is made of (docs/serving.md "Architectures"): RMSNorm, the product
with a stored weight, SwiGLU, rotary positions in halves and YaRN's
frequency blend, grouped-query attention over rows as stored, and the
routed-expert layer that is told which experts it holds: the router
(sigmoid scores with a selection bias, or a softmax over all its experts),
the held experts' dense product, and the device counters of both.
:mod:`.deepseek_v3`, :mod:`.lfm2` and :mod:`.mellum` import them from
here, so a change to any of them reaches every model that runs it.

**The share.** The layer routes every row over ALL ``router_width``
experts, adds only the terms of the ``held`` experts from ``first`` on for
the (row, choice) pairs that chose them, and THAT partial sum goes on. On
one chip the layer runs without its exchange; nothing stands in for the
absent chips. The held experts are computed densely (every row through
every held expert, the unchosen weighted 0): at decode batch sizes an
expert's product is bound by reading its weights, which a step does once
either way.

**Precision.** The operands of a weight product are the STORED dtype
(bfloat16 under ``quantize="bf16"``: no float32 copy of a weight is ever
made), accumulation float32; norms, the router (its product at
``HIGHEST``) and the gating products float32.

**The rows a step attends.** A cache addressed by position is allocated
``max_len`` rows deep and mostly filled far less. Every architecture's
attention runs under :func:`over_filled_rows`: its unchanged score, mask,
softmax and mix over a PREFIX of the layer's rows, the smallest rung of
:func:`rows_ladder` that holds every slot's position, chosen inside the
step program from the ``pos`` it is fed (:func:`filled_rung`). Rows past
the prefix are rows whose mask is false for every slot, so their softmax
weight is exactly 0 and leaving them out changes no value, only which
rows leave the device's memory. A RING of ``W`` rows (a sliding window's K
and V, position ``p`` in row ``p % W``) runs under the same function at
depth ``W``: until a slot has wrapped it the rows above its ``pos`` are
not its own and the prefix mask hides them, and from ``pos = W - 1`` on
the mask is all true and the rung the whole ring. An architecture with
both calls it once a depth.

**Counters**, on the device in the donated state: ``moe_served`` (expert
layer, held expert): (token, choice) pairs served here; ``moe_routed``
(expert layer): pairs routed in all. Only live slots count.
"""
from __future__ import annotations

import collections
import math

import numpy as np

from ..base import MXNetError


def rms_norm(x, gamma, eps):
    """``x * rsqrt(mean(x^2) + eps) * gamma`` in float32."""
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.float32(eps)) \
        * gamma.astype(jnp.float32)


def linear(x, w):
    """``x @ w.T`` with the operands in the WEIGHT's stored dtype and
    float32 accumulation."""
    import jax.numpy as jnp
    return jnp.einsum("se,fe->sf", x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def swiglu(x, gate, up, down):
    import jax
    return linear(jax.nn.silu(linear(x, gate)) * linear(x, up), down)


def yarn_inv_freq(dim, theta, scaling=None):
    """The ``dim / 2`` inverse frequencies of the rotary pairs (float64):
    plain ``theta^(-2i/dim)`` without ``scaling``; with it (``type: yarn``)
    the blend of those with the same over ``factor``, by a linear ramp
    between the pairs that turn ``beta_fast`` and ``beta_slow`` times over
    ``original_max_position_embeddings``."""
    extra = 1.0 / float(theta) ** (np.arange(0, dim, 2, dtype=np.float64)
                                   / dim)
    if not scaling:
        return extra
    orig = float(scaling["original_max_position_embeddings"])

    def pair_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(float(theta)))

    low = max(math.floor(pair_of(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(pair_of(float(scaling["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return extra / float(scaling["factor"]) * ramp + extra * (1.0 - ramp)


def rope_half(x, cos, sin):
    """Rotate the pairs ``(x[i], x[i + r/2])`` of the leading ``r`` lanes
    of the minor dimension by the angles whose cos and sin are given per
    pair (``rotate_half``), ``r`` twice their number: the whole minor
    dimension, or its leading part under a ``partial_rotary_factor``, the
    lanes past ``r`` passed through as they are."""
    import jax.numpy as jnp
    half = cos.shape[-1]
    a, b = x[..., :half], x[..., half:2 * half]
    turned = [a * cos - b * sin, b * cos + a * sin]
    if 2 * half < x.shape[-1]:
        turned.append(x[..., 2 * half:])
    return jnp.concatenate(turned, axis=-1)


def gqa_attention(q, krows, vrows, tmask, scale):
    """Grouped-query attention of one position per slot: ``q`` (slots,
    heads, head_dim) float32 over ``krows``/``vrows`` (slots, rows, kv_heads
    * head_dim) as stored, float32 softmax under ``tmask`` (slots, rows);
    query head ``h`` attends K/V head ``h // (heads / kv_heads)``. Returns
    (slots, heads * head_dim) float32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    op = krows.dtype
    nslots, heads, d = q.shape
    groups = krows.shape[-1] // d
    # own[h, g]: query head h reads the lanes of K/V head g (a constant
    # of the shapes: built once, when the pass is traced)
    own = (np.arange(heads)[:, None] // (heads // groups)
           == np.arange(groups)[None, :]).astype(np.float32)[None, :, :, None]
    qb = (q[:, :, None, :] * own).reshape(nslots, heads, groups * d)
    s = jnp.einsum("shc,stc->sht", qb.astype(op), krows,
                   preferred_element_type=f32) * f32(scale)
    s = jnp.where(tmask[:, None, :], s, f32(-1e30))
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("sht,stc->shc", w.astype(op), vrows,
                   preferred_element_type=f32)
    o = jnp.sum(o.reshape(nslots, heads, groups, d) * own, axis=2)
    return o.reshape(nslots, heads * d)


#: how a router turns its logits into the scores it ranks and weights by
SCORES = ("sigmoid", "softmax")


def route(f, weight, bias, top_k, scaling, normalise=True, eps=1e-20,
          score="sigmoid"):
    """``(indices, weights)`` ``(rows, top_k)`` of the experts each row
    chooses among ALL the router's experts, in float32. ``score``
    ``"sigmoid"``: chosen by ``sigmoid(W f) + bias``, weighted by the
    sigmoid alone; ``"softmax"``: chosen and weighted by ``softmax(W f)``
    over all the router's experts, no bias term (``bias`` is not read).
    Either way the weights are taken over the chosen ones' sum (+ ``eps``:
    the model's own, DeepSeek-V3's where none is given) where the router
    normalises, times ``scaling``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    logits = jnp.einsum("se,xe->sx", f.astype(f32), weight.astype(f32),
                        precision=jax.lax.Precision.HIGHEST)
    if score == "softmax":
        # ranked by what it weights by: the sort hands the weights over
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + bias.astype(f32), top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
    if normalise:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + f32(eps))
    return idx, w * f32(scaling)


def held_weights(idx, w, first, held):
    """``(hit, dense)``: ``hit`` (rows, top_k, held) marks the (row,
    choice) pairs that chose held expert ``first + j``; ``dense`` (rows,
    held) is each row's weight for each held expert, 0 where unchosen."""
    import jax.numpy as jnp
    hit = (idx - first)[:, :, None] == jnp.arange(held)[None, None, :]
    return hit, jnp.sum(jnp.where(hit, w[:, :, None], jnp.float32(0.0)),
                        axis=1)


def held_experts(f, dense, gate, up, down):
    """The held experts' part of the layer's output: every row through
    every held expert (stacked ``(held, ...)`` weights, each read once),
    summed with ``dense`` (rows, held) in float32."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    fb = f.astype(gate.dtype)
    g = jnp.einsum("se,xfe->xsf", fb, gate, preferred_element_type=f32)
    u = jnp.einsum("se,xfe->xsf", fb, up, preferred_element_type=f32)
    act = (jax.nn.silu(g) * u).astype(down.dtype)
    y = jnp.einsum("xsf,xef->xse", act, down, preferred_element_type=f32)
    return jnp.sum(y * dense.T[:, :, None], axis=0)


#: the shallowest prefix of a cache's rows worth a branch of its own: a
#: rung costs a process a third of a second of set-up (its branch of every
#: attention layer is traced, lowered and read from the compile cache),
#: and under 192 rows the attention is a tenth of a step (PERF.md, PR 35)
MIN_PREFIX_ROWS = 192
#: a prefix ends on a whole tile of rows (16 of two bytes, two of 8 of four)
PREFIX_TILE = 16


def rows_ladder(rows):
    """The prefixes of a cache ``rows`` deep that a step's attention may
    cover, ascending, the last one all of it: ``rows`` halved (to whole
    tiles) while a half holds :data:`MIN_PREFIX_ROWS` (768: 192, 384, 768;
    1024: 256, 512, 1024; a cache under 384 rows has one rung and no
    branch). A constant of the allocated depth: nothing to tune."""
    ladder, part = {int(rows)}, -(-int(rows) // 2)
    while part >= MIN_PREFIX_ROWS:
        ladder.add(-(-part // PREFIX_TILE) * PREFIX_TILE)
        part = -(-part // 2)
    return tuple(sorted(ladder))


def filled_rung(top, ladder, xp):
    """The index of the smallest rung of ``ladder`` above the position
    ``top``, the last where none is (a speculative window's positions past
    ``max_len``). The ONE rule of host and device: ``xp`` is ``jax.numpy``
    in the step program, where ``top`` is the deepest ``pos`` it is fed
    (an empty slot is fed 0), and ``numpy`` for what the spans and
    counters say (:func:`rows_covered`)."""
    return xp.sum(top >= xp.asarray(ladder[:-1], "int32"), dtype="int32")


def rows_covered(ladder, top):
    """The rung that a step whose deepest position is ``top`` attends, on
    the host."""
    import numpy as np
    return ladder[int(filled_rung(top, ladder, np))]


def over_filled_rows(pos, rows, slot=None):
    """For one token pass over caches ``rows`` deep: ``over(caches, layer,
    attend)``, which gives ``attend(mask, *[c[layer, :, :R] for c in
    caches])`` with ``R`` the smallest rung of :func:`rows_ladder` above
    every slot's ``pos`` and ``mask`` (slots, R) true on the rows a slot
    attends (``<= pos``). The rung is picked once a pass; a layer's call
    is one ``lax.switch`` with a branch a rung, each over a STATIC slice,
    which fuses into the read of the product that takes it, so that only
    ``R`` rows leave memory. The ``caches`` (each ``(layers, slots, rows,
    width)``) go into the branches whole, as operands that are only read:
    no copy of a layer's rows is made to hand it over. ``attend`` is traced
    inside the call, so it may close over the layer's own values.

    With ``slot`` (a packed prefill pass: row ``r`` is position ``pos[r]``
    of slot ``slot[r]``, several rows a slot) each row is handed ITS slot's
    rows, ``c[layer, slot, :R]``: a gather of ``len(slot) x R`` rows a
    layer, never a copy the size of a cache."""
    import jax
    import jax.numpy as jnp
    ladder = rows_ladder(rows)
    rung = filled_rung(jnp.max(pos), ladder, jnp)

    def prefix(c, layer, depth):
        rows = c[layer, :, :depth]
        return rows if slot is None else rows[slot]

    def over(caches, layer, attend):
        def branch(depth):
            def run(pos, *cs):
                mask = jnp.arange(depth)[None, :] <= pos[:, None]
                return attend(mask, *[prefix(c, layer, depth) for c in cs])
            return run

        return jax.lax.switch(rung, [branch(r) for r in ladder], pos,
                              *caches)

    return over


class ExpertShare(collections.namedtuple(
        "ExpertShare", "top_k scaling normalise eps first held norm_eps "
        "score", defaults=("sigmoid",))):
    """What an expert layer is told: the router's ``top_k``, ``scaling``,
    whether it normalises and with which ``eps``; the ``held`` experts from
    index ``first`` on; the ``norm_eps`` of the RMSNorm before it; the
    ``score`` it ranks by (one of :data:`SCORES`)."""

    __slots__ = ()


def routed_share(x, p, share, live, nlive, counts, m):
    """Expert layer ``m`` (of the expert layers) on this share: ``(f, y,
    counts)`` with ``f`` the normed input, ``y`` the held experts' terms
    (rows, hidden) float32 and ``counts`` = ``(moe_served, moe_routed)``
    brought up to date for the ``live`` rows. ``p(name)`` gives the layer's
    ``ffn_norm_gamma``, ``router_weight``, ``router_bias`` (a sigmoid
    router's only) and ``experts_{gate,up,down}_weight``. The scopes are what a device trace
    is searched for: the same in every layer and model."""
    import jax
    import jax.numpy as jnp
    served, routed = counts
    with jax.named_scope("layer/moe/router"):
        f = rms_norm(x, p("ffn_norm_gamma"), share.norm_eps)
        bias = None if share.score == "softmax" else p("router_bias")
        idx, w = route(f, p("router_weight"), bias, share.top_k,
                       share.scaling, share.normalise, share.eps,
                       share.score)
        hit, dense = held_weights(idx, w, share.first, share.held)
        here = jnp.sum(hit & live[:, None, None], axis=(0, 1),
                       dtype=jnp.int32)
        served = served.at[m].add(here)
        routed = routed.at[m].add(nlive * jnp.int32(share.top_k))
    with jax.named_scope("layer/moe/experts"):
        y = held_experts(f, dense, p("experts_gate_weight"),
                         p("experts_up_weight"), p("experts_down_weight"))
    return f, y, (served, routed)


def feed_forward(x, p, share, live, nlive, counts, m, shared=False):
    """A layer's feed-forward and the residual over it: ``(x + ..., counts)``.
    Where ``m`` is None a dense SwiGLU (``ffn_{gate,up,down}_weight``) over
    ``RMSNorm(x; ffn_norm)`` under ``layer/mlp``; else expert layer ``m``'s
    share (:func:`routed_share`) and, with ``shared``, the shared expert
    (``shared_{gate,up,down}_weight``, ungated, unscaled) over the same
    normed input under ``layer/moe/shared``: every chip computes it alike,
    so it is in every share's partial sum once."""
    import jax
    if m is None:
        with jax.named_scope("layer/mlp"):
            f = rms_norm(x, p("ffn_norm_gamma"), share.norm_eps)
            return x + swiglu(f, p("ffn_gate_weight"), p("ffn_up_weight"),
                              p("ffn_down_weight")), counts
    f, y, counts = routed_share(x, p, share, live, nlive, counts, m)
    if shared:
        with jax.named_scope("layer/moe/shared"):
            y = y + swiglu(f, p("shared_gate_weight"), p("shared_up_weight"),
                           p("shared_down_weight"))
    return x + y, counts


def validate_share(arch, module, host_params, mesh, quant_mode):
    """What every architecture that holds a share of an expert layer
    refuses and checks in its ``validate``: a model mesh, int8, and each
    parameter of ``arch.param_shapes()`` present under the naming of
    ``serving/<module>.py`` with the config's shape."""
    if mesh is not None:
        raise MXNetError(
            "DecodeLoop: no model mesh over the %s architecture yet — "
            "its expert layer has no 'expert' mesh axis and no "
            "exchange (ROADMAP); serve it on one chip" % arch.name)
    if quant_mode == "int8":
        raise MXNetError(
            "DecodeLoop: quantize='int8' is not implemented for the %s "
            "architecture (none and bf16 are)" % arch.name)
    for name, shape in arch.param_shapes().items():
        if name not in host_params:
            raise MXNetError(
                "DecodeLoop: params missing %r — expected the "
                "serving/%s.py parameter naming" % (name, module))
        got = tuple(np.shape(host_params[name]))
        if got != tuple(shape):
            raise MXNetError(
                "DecodeLoop: %r has shape %s, the %s config gives %s"
                % (name, got, arch.name, tuple(shape)))


def moe_counters(expert_layers, held):
    """The ``counters()`` of an architecture with ``expert_layers`` expert
    layers of ``held`` experts each (none: no counters)."""
    if not expert_layers:
        return {}
    return {"moe_served": (expert_layers, held),
            "moe_routed": (expert_layers,)}


def record_moe(health, counts, before):
    """The ``record_counters`` of such an architecture: what the two
    counters grew by since ``before`` into ``health.record_moe``."""
    def total(name):
        return int(np.sum(counts[name], dtype=np.int64)) \
            - int(np.sum(before.get(name, 0), dtype=np.int64))
    health.record_moe(total("moe_routed"), total("moe_served"),
                      int(np.max(counts["moe_served"])))
