"""Continuous-batching decode stack for the transformer LM
(docs/serving.md "Decode loop" + the four production legs: "Sampling",
"Quantized weights", "Prefix cache", "Speculative decoding").

Autoregressive serving is a different animal from batch inference: each
sequence wants ONE token per model pass, sequences finish at different
times, and throughput comes from keeping every batch slot busy. This loop
is the standard continuous-batching shape (the Gemma-on-TPU serving
comparison, arXiv:2605.25645; Orca-style slot scheduling) on the donated
dispatch substrate PR 1/PR 4 built for training:

* the KV cache — plus each slot's RNG seed — is DEVICE STATE, donated
  across steps: the decode body is one AOT-compiled program ``(state,
  params, tokens, pos, temp, top_k, top_p, fresh_seed, reseed) ->
  (state, next_tokens)`` whose buffers are reused in place, exactly like
  the train step's donated parameter state;
* sequences occupy SLOTS: a new request joins any free slot mid-stream
  (its prompt is teacher-forced into the slot's rows, overwriting whatever
  the retired occupant left in the cache — positions past ``pos`` are
  masked, so stale rows are unreachable);
* a prompt goes in by CHUNKS where the architecture supplies a prefill
  pass (docs/serving.md "The prefill pass"): a second AOT program
  ``(state, params, tokens, slot, pos, n) -> state`` writes prompt
  positions as ONE batched forward, at most one pass a step, dispatched
  ahead of the step with nothing to read back; only the prompt's last
  token goes through the decode body. :class:`OptArch`'s pass takes up to
  :data:`PREFILL_CHUNK` positions of ONE slot; a PACKED pass
  (:class:`~mxnet_tpu.serving.deepseek_v3.DeepseekV3Arch`) takes up to
  :data:`PACKED_ROWS` rows that each name their slot and position, filled
  from every slot that is due and held back until it carries enough to
  pay for its read of the weights (:data:`PASS_PAYS`). Elsewhere (the
  other architectures, a speculative loop, a model mesh) the prompt rides
  the decode body one token per step;
* the host only supplies prompt tokens and reads back the SAMPLED token
  ids (one (slots,) int32 readback per step — smaller than the logits
  readback it replaced);
* the loop runs ONE STEP AHEAD of that readback: a generating slot's next
  input token stays on the device (``state["tok"]``, selected where the
  host sends :data:`FED_BACK`), and everything else the next dispatch
  needs is a count the host has (``pos``, the prompt left to feed,
  ``max_new_tokens``, ``max_len``), so step n+1 is fed and dispatched
  while the device computes step n, and step n's tokens are read and
  handed to their requests after that (docs/serving.md "What a step is").
  Only ``eos_id`` needs a token's value: it is learned one step late, and
  the slot-step dispatched meanwhile is dropped (``trash_slot_steps``).

The four legs, each behind a knob (docs/serving.md has the full table):

**Sampling** (per request: ``temperature``/``top_k``/``top_p``/``seed``)
happens IN-GRAPH via :mod:`.sampling`: the uniform for a slot's sample at
cache position ``p`` is a pure function of ``(seed, p)``, so a sequence's
token stream is deterministic under a fixed seed no matter which
co-riders join or retire around it, and ``temperature=0`` is bitwise the
greedy argmax path the loop always had.

**Quantized weights** (``quantize=``/``MXTPU_SERVE_QUANT``: ``none`` |
``bf16`` | ``int8``): per-channel scales computed at load by
:mod:`.quantize`, dequant inside the body, so memcheck's resident
accounting sees the int8/bf16 weight bytes (the HBM win
:meth:`DecodeLoop.weight_bytes` reports); a sharded loop holds 1/N of
the QUANTIZED bytes per chip.

**Prefix cache** (``prefix_cache=``/``MXTPU_SERVE_PREFIX_CACHE``, on by
default; capacity ``MXTPU_SERVE_PREFIX_MAX``): ``generate(...,
prefix_len=L)`` names the shared system prompt ``prompt[:L]``. The first
sequence to decode it has its KV slab extracted and cached ON DEVICE;
later joins implant the slab into their slot and skip re-teacher-forcing
the common prefix entirely. Sampling determinism is unaffected — the RNG
depends only on (seed, absolute position).

**Speculative decoding** (``spec_k=``/``MXTPU_SERVE_SPEC_K`` +
``draft_params=``): a small draft LM co-resident beside the target
(memcheck's resident-set lint audits the pair at load). Each round the
draft proposes K tokens through K+1 cheap single-token passes, then ONE
batched target pass scores all K+1 positions and samples every position
with the same (seed, position) uniforms the single-token body would have
used. Because the sample at a position is a deterministic function of
(prefix, uniform) — not of the draft — acceptance is exact prefix
matching and the emitted stream is token-identical to target-only
decoding; a draft that equals the target gets 100% acceptance
(docs/serving.md "Speculative decoding" has the acceptance math). The
verify body UNROLLS the window through the same per-position pass as the
single-token body, so each position computes the identical op sequence.

Fault sites (docs/robustness.md): ``serve.decode_die`` fires at the top
of every loop iteration; ``serve.sample`` at the top of every
sampled-decode dispatch; ``serve.spec_verify`` before each speculative
verify dispatch. Any raising kind kills the loop thread, which SHEDS
every in-flight and queued sequence with :class:`ServingClosedError` —
callers get a clear error, never a hang.
"""
from __future__ import annotations

import collections
import logging
import queue
import threading
import time

import numpy as np

from ..base import MXNetError, env_int, env_str
from ..obs import trace as _obs
from .arch import PER_POSITION, Architecture, SlotArray
from .batcher import REQUEST_IDS, ServingClosedError, Settleable
from .blocks import over_filled_rows, rows_covered, rows_ladder
from .health import ServingHealth, SERVING_HEALTH
from .quantize import (is_quantized_leaf, quantize_array, quantize_tree,
                       resolve_mode, tree_bytes)
from .sampling import position_uniforms, sample_rows, validate_sampling


def _ln(x, gamma, beta):
    import jax
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + jnp.float32(1e-5)) * gamma + beta


def _qkv(x, params, pre):
    """Layer ``pre``'s pre-LN and packed q, k, v projection of the rows
    ``x``: ``(rows, 3 * embed)``, q first."""
    a = _ln(x, params[pre + "_ln1_gamma"], params[pre + "_ln1_beta"])
    return a @ params[pre + "_attn_qkv_weight"].T \
        + params[pre + "_attn_qkv_bias"]


def _attn_out(o, params, pre):
    return o @ params[pre + "_attn_out_weight"].T \
        + params[pre + "_attn_out_bias"]


def _mlp(x, params, pre):
    """Layer ``pre``'s pre-LN ReLU feed-forward of the rows ``x`` (the
    caller adds it to the residual stream)."""
    import jax.numpy as jnp
    f = _ln(x, params[pre + "_ln2_gamma"], params[pre + "_ln2_beta"])
    f = jnp.maximum(f @ params[pre + "_ffn_fc1_weight"].T
                    + params[pre + "_ffn_fc1_bias"], jnp.float32(0.0))
    return f @ params[pre + "_ffn_fc2_weight"].T \
        + params[pre + "_ffn_fc2_bias"]


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

    THE ATTENTION COVERS A PREFIX OF THE ROWS: the smallest rung of
    ``blocks.rows_ladder(rows)`` that holds every slot's ``pos``, picked
    once a pass from the ``pos`` it is fed and run as one branch of a
    ``lax.switch`` a layer, each over a static slice of the donated array
    (:func:`.blocks.over_filled_rows`). The rows a branch leaves out are
    rows the mask gives a softmax weight of exactly 0, so the logits are
    those of the whole cache but for the order of a sum; the write stays
    on the whole array, in place. What a step reads of the cache follows
    the deepest position in it, not ``max_len`` (PERF.md, PR 35).

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
        neg = jnp.float32(-1e30)
        # lane e of a group belongs to the group's head e // d
        lanes, gheads = embed // groups, num_heads // groups
        seg = (jnp.arange(lanes)[:, None] // d
               == jnp.arange(gheads)[None, :]).astype(jnp.float32)

        def heads_sum(p):       # (slots, rows, embed) -> (slots, rows, heads)
            p = p.reshape(nslots, -1, groups, lanes)
            return jnp.einsum("stge,eh->stgh", p, seg, precision=highest
                              ).reshape(nslots, -1, num_heads)

        def heads_spread(w):    # (slots, rows, heads) -> (slots, rows, embed)
            w = w.reshape(nslots, -1, groups, gheads)
            return jnp.einsum("stgh,eh->stge", w, seg, precision=highest
                              ).reshape(nslots, -1, embed)

        # the rows a step attends: the prefix that holds every slot's
        # position, picked from ``pos`` once a pass (serving/blocks.py)
        over = over_filled_rows(pos, rows)

        # the scope names are what an operator searches a device trace
        # for: the same in every layer, so they sum by kind
        for i in range(num_layers):
            pre = "layer%d" % i
            with jax.named_scope("layer/attn"):
                qkv = _qkv(x, params, pre).reshape(nslots, 3, embed)
                q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]   # (slots, H * D)
            with jax.named_scope("cache_write"):
                ck = ck.at[i, sidx, wpos].set(k)
                cv = cv.at[i, sidx, wpos].set(v)
            with jax.named_scope("layer/attn"):
                def attend(mask, krows, vrows):
                    s = heads_sum(q[:, None, :] * krows) * scale
                    s = jnp.where(mask[:, :, None], s, neg)
                    w = jax.nn.softmax(s, axis=1)
                    return jnp.sum(heads_spread(w) * vrows, axis=1)

                o = over((ck, cv), i, attend)
                x = edge(x + _attn_out(o, params, pre))
            with jax.named_scope("layer/mlp"):
                x = edge(x + _mlp(x, params, pre))
        with jax.named_scope("head"):
            x = _ln(x, params["final_ln_gamma"], params["final_ln_beta"])
            logits = x @ params["lm_head_weight"].T + params["lm_head_bias"]
        return ck, cv, logits

    return token_pass


def _build_prefill_pass(num_layers, num_heads):
    """Up to ``C`` prompt positions of ONE slot through the layers as one
    batched forward: the token pass's mathematics over a chunk's rows, so
    that a layer's weights are read once a chunk and not once a position.

    Layer by layer the chunk's K and V rows go into the slot's rows ``pos0
    .. pos0 + n - 1`` of the donated arrays, in place, and its queries then
    attend that slot's rows as the arrays now hold them: query ``c`` the
    rows ``<= pos0 + c``, which are the rows a prefix-cache hit implanted
    below ``pos0`` and the chunk's own up to it. The LAST layer stops at
    its K and V: there is no head, no sampler and nothing to read back,
    the prompt's last token goes through the ordinary step.

    THE SAME PRECISION as the token pass: the weight products are ``a @
    W.T`` at the default precision, and the per-head score and mix
    products run at ``HIGHEST``, float32 products as the token pass's
    multiply-and-sum makes them, so a chunk rounds nothing to bfloat16
    that a step does not. For them ONE slot's rows are laid out per head
    (``rows x width`` float32, a few MB a layer); the donated arrays keep
    their layout and no copy the size of a cache is made.

    A PADDED CHUNK MOVES NOTHING. Rows ``n .. C - 1`` of ``tokens`` are
    padding, and a ``dynamic_update_slice`` of all ``C`` rows would clamp
    its start where ``pos0 + C`` passes the arrays' depth and land on live
    rows. The write is a scatter of rows instead, padding rows sent out of
    bounds and DROPPED: rows ``pos0 .. pos0 + n - 1`` of the one slot
    change and no other row of any slot (on the chip the scatter costs
    0.7 ms of a 9.1 ms pass over a masked window update, which reads the
    rows it keeps BEFORE it writes and so makes XLA:CPU copy both caches;
    PERF.md, PR 37)."""
    import jax
    import jax.numpy as jnp

    highest = jax.lax.Precision.HIGHEST

    def prefill_pass(ck, cv, params, tokens, slot, pos0, n):
        chunk = tokens.shape[0]
        rows = ck.shape[2]
        cpos = pos0 + jnp.arange(chunk, dtype=jnp.int32)   # positions
        with jax.named_scope("embed"):
            x = params["tok_embed_weight"][tokens] \
                + params["pos_embed_weight"][
                    jnp.minimum(cpos, jnp.int32(rows - 1))]
        embed = x.shape[1]
        d = embed // num_heads
        scale = jnp.float32(1.0 / float(np.sqrt(d)))
        neg = jnp.float32(-1e30)
        # the rows the chunk writes: its padding goes out of bounds (each
        # row its own way out: the indices stay sorted and unique)
        at = jnp.arange(chunk, dtype=jnp.int32)
        wrows = jnp.where(at < n, cpos, jnp.int32(rows) + at)
        mask = (jnp.arange(rows, dtype=jnp.int32)[None, :]
                <= cpos[:, None])[None]          # (1, C, rows)

        def write(cache, i, new):
            return cache.at[i, slot, wrows].set(
                new, mode="drop", indices_are_sorted=True,
                unique_indices=True)

        def per_head(cache, i):     # the slot's rows: (heads, rows, d)
            mine = jax.lax.dynamic_slice(cache, (i, slot, 0, 0),
                                         (1, 1, rows, embed))
            return mine.reshape(rows, num_heads, d).transpose(1, 0, 2)

        for i in range(num_layers):
            pre = "layer%d" % i
            with jax.named_scope("layer/attn"):
                qkv = _qkv(x, params, pre).reshape(chunk, 3, embed)
                q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]   # (C, H * D)
            with jax.named_scope("cache_write"):
                ck = write(ck, i, k)
                cv = write(cv, i, v)
            if i + 1 == num_layers:
                break       # nothing reads the last layer's output
            with jax.named_scope("layer/attn"):
                s = jnp.einsum("chd,htd->hct",
                               q.reshape(chunk, num_heads, d),
                               per_head(ck, i), precision=highest) * scale
                w = jax.nn.softmax(jnp.where(mask, s, neg), axis=-1)
                o = jnp.einsum("hct,htd->chd", w, per_head(cv, i),
                               precision=highest).reshape(chunk, embed)
                x = x + _attn_out(o, params, pre)
            with jax.named_scope("layer/mlp"):
                x = x + _mlp(x, params, pre)
        return ck, cv

    return prefill_pass


class OptArch(Architecture):
    """The default architecture: the pre-LN, ReLU, learned-position block
    of ``models/transformer.py`` (OPT) through :func:`_build_token_pass`,
    its slot state a K and a V array of ``heads * head_dim`` float32 a
    row. ``who`` names the parameter set in its errors (the draft model of
    a speculative loop is validated as ``draft_params``)."""

    name = "opt"

    def __init__(self, num_layers, num_heads, who="params"):
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.who = who

    def validate(self, host_params, max_len, mesh, quant_mode, spec_k=0,
                 prefix_cache=False):
        draft = self.who != "params"
        for need in ("tok_embed_weight", "pos_embed_weight",
                     "final_ln_gamma", "lm_head_weight") \
                + (() if draft else ("lm_head_bias",)):
            if need not in host_params:
                raise MXNetError(
                    "DecodeLoop: %s missing %r%s" % (
                        self.who, need, "" if draft else
                        " — expected the models/transformer.py parameter "
                        "naming"))
        vocab, embed = host_params["tok_embed_weight"].shape
        heads = "draft_num_heads" if draft else "num_heads"
        if embed % self.num_heads:
            raise MXNetError("DecodeLoop: %sembed %d %% %s %d != 0"
                             % ("draft " if draft else "", embed, heads,
                                self.num_heads))
        if mesh is not None and self.num_heads % int(mesh.devices.size):
            raise MXNetError(
                "DecodeLoop: %s %d %% %d model shards != 0 — the KV cache "
                "shards over heads" % (heads, self.num_heads,
                                       int(mesh.devices.size)))
        # jit-mode gather CLAMPS out-of-range indices: a position past the
        # embedding table would silently reuse its last row (wrong tokens,
        # zero errors) — fail loudly at construction instead
        pos_rows = int(host_params["pos_embed_weight"].shape[0])
        if max_len > pos_rows:
            raise MXNetError(
                "DecodeLoop: max_len %d exceeds the %spositional embedding "
                "table (%d rows) — positions past it would be silently "
                "clamped" % (max_len, "DRAFT " if draft else "", pos_rows))
        return int(vocab)

    def slot_state(self, host_params, quant_mode):
        rows = SlotArray(self.num_layers, PER_POSITION,
                         int(host_params["tok_embed_weight"].shape[1]),
                         np.float32)
        return {"k": rows, "v": rows}

    def slot_partition(self):
        from ..parallel.mesh import AXIS_MODEL
        return (None, None, None, AXIS_MODEL)

    def build_token_pass(self, mesh=None):
        inner = _build_token_pass(self.num_layers, self.num_heads, mesh=mesh)

        def token_pass(state, params, tokens, pos):
            ck, cv, logits = inner(state["k"], state["v"], params, tokens,
                                   pos)
            return {"k": ck, "v": cv}, logits

        return token_pass

    def build_prefill_pass(self, mesh=None):
        if mesh is not None:    # a sharded loop is fed one position a step
            return None
        inner = _build_prefill_pass(self.num_layers, self.num_heads)

        def prefill_pass(state, params, tokens, slot, pos0, n):
            ck, cv = inner(state["k"], state["v"], params, tokens, slot,
                           pos0, n)
            return {"k": ck, "v": cv}

        return prefill_pass


#: the members of the donated state that are the loop's own, beside the
#: architecture's: each slot's seed, and the token the decode body last
#: sampled for it (only in a state that body steps)
LOOP_STATE = ("seed", "tok")
#: what the host puts in ``tokens`` for a slot whose input token is the one
#: the device sampled for it the step before
FED_BACK = -1


def _model_state(state):
    """The donated state less the loop's own members: what the
    architecture's token pass reads and writes."""
    return {k: v for k, v in state.items() if k not in LOOP_STATE}


def _build_decode_fn(arch, mesh=None):
    """The single-token decode body: one position per slot, sampled
    in-graph. Returns ``(state, next_tokens)`` — the host reads back one
    (slots,) int32 vector, never the logits. ``live`` is the eighth
    per-slot array of an architecture that asks for it.

    The sampled tokens also STAY ON THE DEVICE, as ``state["tok"]``: a
    slot whose ``tokens`` entry is negative (:data:`FED_BACK`) takes the
    token this body sampled for it the step before, so the loop can
    dispatch a generating slot's next step before it has read the last
    one back. A host token (a prompt position, a newly seated slot, every
    speculative pass) is taken as it is."""
    token_pass = arch.build_token_pass(mesh=mesh)

    def decode_fn(state, params, tokens, pos, temp, top_k, top_p,
                  fresh_seed, reseed, *live):
        import jax
        import jax.numpy as jnp
        seeds = jnp.where(reseed, fresh_seed, state["seed"])
        tokens = jnp.where(tokens < 0, state["tok"], tokens)
        p = arch.load(params)
        new, logits = token_pass(_model_state(state), p, tokens, pos, *live)
        with jax.named_scope("sample"):
            u = position_uniforms(seeds, pos)
            nxt = sample_rows(logits, u, temp, top_k, top_p)
        return dict(new, seed=seeds, tok=nxt), nxt

    return decode_fn


def _build_prefill_fn(arch, mesh=None):
    """The prefill body of an architecture that supplies a prefill pass
    (``None`` for one that does not): a chunk of one slot's prompt into
    the donated state, and nothing else. Returns the state alone: the
    loop has nothing to read back and dispatches it between two steps
    without waiting for either."""
    prefill_pass = arch.build_prefill_pass(mesh=mesh)
    if prefill_pass is None:
        return None

    def prefill_fn(state, params, tokens, slot, pos0, n):
        return dict(state, **prefill_pass(_model_state(state),
                                          arch.load(params), tokens, slot,
                                          pos0, n))

    return prefill_fn


def _build_verify_fn(arch, window, mesh=None):
    """The speculative verify body: ``window`` positions per slot through
    the SAME per-position pass as the single-token body, unrolled (the
    slots' state threads through, so position j attends the rows j' < j
    wrote), each position sampled with its own (seed, position) uniform.
    One dispatch scores and samples the whole window."""
    token_pass = arch.build_token_pass(mesh=mesh)

    def verify_fn(state, params, tokens_w, pos0, temp, top_k, top_p,
                  fresh_seed, reseed, *live):
        import jax
        import jax.numpy as jnp
        seeds = jnp.where(reseed, fresh_seed, state["seed"])
        p = arch.load(params)
        model = _model_state(state)
        outs = []
        for j in range(window):
            pos_j = pos0 + jnp.int32(j)
            model, logits = token_pass(model, p, tokens_w[:, j], pos_j,
                                       *live)
            with jax.named_scope("sample"):
                u = position_uniforms(seeds, pos_j)
                outs.append(sample_rows(logits, u, temp, top_k, top_p))
        return dict(model, seed=seeds), jnp.stack(outs, axis=1)

    return verify_fn


def _build_extract_fn(names, slab_sharding=None):
    """Prefix harvest: copy one slot's full slab, every slot-state array
    of ``names`` less its slot axis (each with its own layers, depth and
    width), out of the state (non-donating — the state keeps serving).
    Garbage rows past the prefix length ride along; every consumer
    rewrites them before any query can attend them, which holds for
    arrays addressed by position only (an architecture with any other
    state refuses the prefix cache in ``validate``)."""
    def extract_fn(state, slot):
        slab = {k: state[k][:, slot] for k in names}
        if slab_sharding is not None:
            import jax
            slab = {k: jax.lax.with_sharding_constraint(v, slab_sharding)
                    for k, v in slab.items()}
        return slab

    return extract_fn


def _build_implant_fn():
    """Prefix reuse: write a cached slab into one slot (the state is
    donated — in-place on device); seeds and counters pass through."""
    def implant_fn(state, slot, slab):
        return dict(state, **{k: state[k].at[:, slot].set(v)
                              for k, v in slab.items()})

    return implant_fn


def _build_snapshot_fn():
    """Copies of an architecture's device counters, NOT donating: a traced
    run enqueues it behind a step and reads the copies a step later, when
    the arrays it copied are long donated away (``jnp.copy``: an output
    that IS its input would be handed back as the same buffer)."""
    def snapshot_fn(counters):
        import jax.numpy as jnp
        return {k: jnp.copy(v) for k, v in counters.items()}

    return snapshot_fn


class GenerateFuture(Settleable):
    """Handle for one in-flight sequence; :meth:`result` blocks. Rides
    the batcher's :class:`~mxnet_tpu.serving.batcher.Settleable` protocol
    (first settle wins, ``on_done`` fires exactly once after the event),
    so open-loop clients can drive ``generate`` exactly like ``infer``."""

    __slots__ = ("prompt", "max_new", "_loop", "rid", "temperature",
                 "top_k", "top_p", "seed", "prefix_len", "token_times",
                 "t_submit", "outcome")

    def __init__(self, loop, prompt, max_new, temperature=0.0, top_k=0,
                 top_p=1.0, seed=None, prefix_len=0, on_done=None):
        super().__init__(on_done=on_done)
        self.prompt = list(prompt)
        self.max_new = int(max_new)
        self._loop = loop
        #: serving correlation id (docs/observability.md): shares the
        #: batcher's process-wide sequence so fleet + decode spans never
        #: collide on an id
        self.rid = next(REQUEST_IDS)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        #: RNG stream id: an unseeded request draws a per-request stream
        #: from its rid (deterministic within a process, distinct across
        #: requests); pass ``seed=`` for replayable sampling
        self.seed = int(self.rid if seed is None else seed) & 0x7FFFFFFF
        self.prefix_len = int(prefix_len)
        #: ``time.perf_counter()`` of the step that emitted each token, in
        #: order (one clock read a step; the tokens of one speculative
        #: round share theirs). ``token_times[0]`` less :attr:`t_submit` is
        #: the time to first token, the differences are the token gaps;
        #: the loop thread appends, so read it once done. The loop's
        #: ``decode_request`` record carries the same as ``token_us``
        self.token_times = []
        #: ``time.perf_counter()`` of the submission
        self.t_submit = time.perf_counter()
        #: how the LOOP ended the request, once it has: ``done``, ``eos``,
        #: ``shed`` (the loop died) or ``failed`` (closed unserved): the
        #: ``outcome`` of its one ``decode_request`` record
        self.outcome = None

    @property
    def tokens(self):
        return self.value

    def result(self, timeout=None):
        deadline = None if timeout is None else time.monotonic() + timeout
        while not self.event.wait(0.05):
            # a future enqueued in the generate()/close() race window is on
            # a queue nothing will ever drain — fail it here rather than
            # spin forever (dead covers crashes; _closed/liveness cover a
            # clean close that raced our enqueue)
            stopped = (self._loop.dead is not None or self._loop._closed
                       or not self._loop._thread.is_alive())
            if stopped and not self.done():
                self.fail(ServingClosedError(
                    "decode loop died with the sequence in flight: %s"
                    % (self._loop.dead,)
                    if self._loop.dead is not None else
                    "decode loop closed with the sequence unserved"))
                break
            if deadline is not None and time.monotonic() > deadline:
                raise MXNetError("generate: timed out after %.1fs"
                                 % timeout)
        if self.error is not None:
            raise self.error
        return self.value


class _Slot(object):
    """One seated request, as far as the loop has DISPATCHED it: ``pos``,
    ``pending`` and ``sent`` move when a step is dispatched (they are
    counts the host knows), ``emitted`` when that step's tokens are read
    back, one step later."""

    __slots__ = ("fut", "seat", "pending", "pos", "next_token", "emitted",
                 "sent", "reseed", "producing", "t_seat", "steps", "prefill",
                 "prefix_hit")

    def __init__(self, fut, seat=0, step=0):
        self.fut = fut
        self.seat = seat                  # the loop's n-th seating
        # for the request's one record (``DecodeLoop._request_done``)
        self.t_seat = time.perf_counter()
        #: ids of the ``decode_step`` spans that first list the request,
        #: that commit its prompt's last position (0: worked out from
        #: ``sent`` when it leaves) and that emit its last token (where it
        #: leaves its slot, or the step whose ``eos`` is read a step late)
        self.steps = [step, 0, 0]
        self.prefill = [0, 0]             # prefill passes, their positions
        self.prefix_hit = 0               # rows a prefix hit implanted
        self.pending = list(fut.prompt)   # prompt tokens still to feed
        self.pos = 0                      # next cache write position
        #: the next input token where the host has it (a prompt's), else
        #: :data:`FED_BACK`: the one the device sampled the step before
        self.next_token = self.pending.pop(0)
        self.emitted = []
        self.sent = 0                     # tokens its dispatched steps emit:
        #                                   ahead of emitted by the step in
        #                                   flight
        self.reseed = True                # seed lands in-state next step
        self.producing = None             # (key, L): harvest prefix at L


#: ONE-SLOT PASS (:class:`OptArch`'s): the positions of one slot's prompt
#: a prefill pass takes (its chunk; the cache's depth where that is less).
#: A pass reads the weights once
#: whatever it holds (OPT-1.3B's float32 tree: 6.9 ms at the memory's
#: rate) and its scores, heads x chunk x rows float32, several times a
#: layer: over 768 rows it takes 7.9 / 9.1 / 12.8 ms at 64 / 128 / 256
#: positions against a step's 8.2 (chip runs, PERF.md PR 37). 128 is
#: within 1% of the best rate where prompts are short (16-48) and takes
#: the chat mix's median prompt (96) in one pass, its longest (256) in
#: two. A constant until a cell pays for a rule
PREFILL_CHUNK = 128
#: BOTH PASSES: the fewest prompt positions of a slot worth a prefill pass
#: (a slot with fewer left before its prompt's last is not due). The pass
#: holds every seated slot up for about one step's time (9.1 ms against
#: 8.2) and saves its own slot a step a position: a saturated loop breaks
#: even at as many positions as it has slots (8 in the OPT cells), a
#: request alone at two. A shorter rest of a prompt rides the steps. It is
#: the ONE-SLOT pass's whole rule (a due slot gets its pass at once), and
#: the floor of the packed pass's (:data:`PASS_PAYS`)
MIN_PREFILL = 8
#: PACKED PASS (an architecture whose ``packed_prefill`` is true): the rows
#: of one pass, each a prompt position of a slot it names (the cache's
#: depth where that is less). The pass reads the weights once whatever it
#: holds, so its rows ride free up to the chip's ridge: 197 TFLOP/s over
#: 819 GB/s = 240 FLOP a byte = 240 rows of bfloat16 weights (2 FLOP a
#: weight a row, 2 bytes a weight); past it the products, not the read,
#: set the pass's time, and every padding row costs what a live one does.
#: 256 is the power of two at the ridge. Kimi-K2's pass over rows under
#: 256 deep takes 12.6 / 16.1 / 23.8 ms at 128 / 256 / 384 rows against a
#: step's 14.5 (chip runs, PERF.md PR 39: at 256 its dense held-expert
#: products run at 81% of the bf16 peak and are half the pass); two joins
#: of the wide cells (about 122 positions) fit 128 and a third rides 256
#: free of a second read, and end to end 256 read 1.7% over 128
PACKED_ROWS = 256
#: PACKED PASS: a pass is dispatched when it would carry this many times
#: the GENERATING slots' number of positions (never fewer than
#: :data:`MIN_PREFILL`), or is full. A pass holds every generating slot up
#: for its own time, ``g x pass / step`` tokens not emitted, and frees one
#: slot-step a position it carries, a token emitted later: it breaks even
#: at ``g x pass / step`` positions, about ``g`` (a pass takes a step's
#: time or a little more). At break-even nothing is gained, and waiting
#: costs nothing: the due slots ride the steps one token at a time
#: meanwhile, exactly as without a pass. So the loop holds the pass back
#: until it carries TWICE that: half its positions are then profit, and at
#: 64 slots it waits for the second join (one join brings 31-127
#: positions, 2 x 60 are wanted). A request alone has no generating slot
#: beside it and fires at :data:`MIN_PREFILL`
PASS_PAYS = 2
#: steps between two readings of an architecture's device counters in a
#: TRACED run (each reading is a ``loop_counters`` span; never once a step)
COUNTER_SPAN_STEPS = 32


def _host_leaf(v, quant_mode):
    """One parameter leaf as a host array: float32, except that under
    ``quantize="bf16"`` a leaf that already IS bfloat16 stays as it is and
    WHERE it is, host or device (a float32 copy of a large model is twice
    its size, and a device array would come to the host only to go back)."""
    if not isinstance(v, np.ndarray):    # an NDArray keeps its array there
        v = getattr(v, "data", v)
    if quant_mode == "bf16" and str(getattr(v, "dtype", "")) == "bfloat16":
        return v
    return np.asarray(v, np.float32)


class DecodeLoop(object):
    """Slot-scheduled continuous decoding over a language model's
    parameter set. The model is an :class:`~mxnet_tpu.serving.arch.
    Architecture` (``arch=``); the default is :class:`OptArch` over
    ``num_layers``/``num_heads`` (``models/transformer.py`` naming:
    ``tok_embed_weight``, ``layer{i}_...``, ``final_ln_*``,
    ``lm_head_*``). With ``arch=`` the two may be ``None``.

    ``generate(prompt, max_new_tokens, temperature=..., top_k=...,
    top_p=..., seed=..., prefix_len=...)`` returns a
    :class:`GenerateFuture`; sequences join a free slot as soon as one
    retires — the decode body never stops for a new arrival.

    Decode knobs resolve arg > ``MXTPU_SERVE_*`` env > tuning DB >
    default (docs/autotune.md): ``spec_k`` (0 = off; needs
    ``draft_params``, and ``draft_arch`` where the draft is no
    :class:`OptArch`), ``prefix_cache`` (default on), ``quantize``
    (default ``"none"``).
    """

    def __init__(self, params, num_layers=None, num_heads=None, max_len=None,
                 slots=4, eos_id=None, health=None, name=None, contexts=None,
                 quantize=None, prefix_cache=None, spec_k=None,
                 draft_params=None, draft_num_layers=None,
                 draft_num_heads=None, arch=None, draft_arch=None):
        import jax
        import jax.numpy as jnp
        from .. import tracecheck as _tc
        from .engine import _model_mesh
        if max_len is None:
            raise MXNetError("DecodeLoop: max_len is required")
        if arch is None:
            if num_layers is None or num_heads is None:
                raise MXNetError("DecodeLoop: num_layers and num_heads are "
                                 "required without arch=")
            arch = OptArch(num_layers, num_heads)
        for given, own, what in ((num_layers, arch.num_layers, "num_layers"),
                                 (num_heads, arch.num_heads, "num_heads")):
            if given is not None and int(given) != own:
                raise MXNetError("DecodeLoop: %s=%d disagrees with arch=%s "
                                 "(%d)" % (what, given, arch.name, own))
        self._arch = arch
        self.num_layers = int(arch.num_layers)
        self.num_heads = int(arch.num_heads)
        self.max_len = int(max_len)
        self.slots = int(slots)
        self.eos_id = eos_id
        self.health = health or ServingHealth(parent=SERVING_HEALTH)
        #: model-axis mesh when the loop spans more than one chip: the KV
        #: cache (the dominant buffer) shards over HEADS (its minor
        #: dimension, a group of whole heads per chip), params shard per
        #: the placement rule, the residual stream stays replicated at
        #: block edges (docs/serving.md "Model-parallel replicas")
        self._mesh = _model_mesh(contexts, who="DecodeLoop")

        self.quant_mode = resolve_mode(
            quantize if quantize is not None
            else env_str("MXTPU_SERVE_QUANT", "none"))
        host_params = {k: _host_leaf(v, self.quant_mode)
                       for k, v in params.items()}
        self._resolve_knobs(host_params, prefix_cache, spec_k, draft_params)
        self.vocab_size = int(arch.validate(
            host_params, self.max_len, self._mesh, self.quant_mode,
            spec_k=self.spec_k, prefix_cache=self.prefix_enabled))
        self.prefix_max = env_int("MXTPU_SERVE_PREFIX_MAX", 8)

        self._params = {
            k: self._place_leaf(v)
            for k, v in quantize_tree(host_params, self.quant_mode).items()}

        # --- draft model (speculative decoding only) ------------------
        self._draft_params = self._draft_arch = None
        self.draft_num_layers = self.draft_num_heads = 0
        if self.spec_k:
            dhost = {k: _host_leaf(v, self.quant_mode)
                     for k, v in draft_params.items()}
            if draft_arch is None:
                if not isinstance(arch, OptArch):
                    raise MXNetError(
                        "DecodeLoop: spec_k over arch=%s needs draft_arch= "
                        "(the draft's own architecture)" % arch.name)
                if draft_num_layers is None:
                    ids = [int(k[5:k.index("_", 5)]) for k in dhost
                           if k.startswith("layer")]
                    draft_num_layers = max(ids) + 1 if ids else 0
                if int(draft_num_layers) <= 0:
                    raise MXNetError(
                        "DecodeLoop: draft_params has no layer{i}_* entries")
                draft_arch = OptArch(draft_num_layers,
                                     draft_num_heads or self.num_heads,
                                     who="draft_params")
            self._draft_arch = draft_arch
            self.draft_num_layers = int(draft_arch.num_layers)
            self.draft_num_heads = int(draft_arch.num_heads)
            dvocab = int(draft_arch.validate(
                dhost, self.max_len, self._mesh, self.quant_mode,
                spec_k=self.spec_k, prefix_cache=self.prefix_enabled))
            if dvocab != self.vocab_size:
                raise MXNetError(
                    "DecodeLoop: draft vocab %d != target vocab %d — "
                    "draft proposals must be target token ids"
                    % (dvocab, self.vocab_size))
            self._draft_params = {
                k: self._place_leaf(v)
                for k, v in quantize_tree(dhost, self.quant_mode).items()}

        # --- device state: the slots' state + per-slot seeds ----------
        # speculative windows run past a retiring sequence's last row;
        # one extra TRASH row absorbs those writes (see _build_token_pass)
        self._state = self._init_state(arch, host_params)
        self._draft_state = None
        if self.spec_k:
            self._draft_state = self._init_state(draft_arch, dhost)
        #: held by whoever dispatches on (and so donates) the state, and by
        #: whoever reads an array of it from another thread
        self._state_lock = threading.Lock()
        self._counter_lock = threading.Lock()
        self._counter_names = tuple(arch.counters())
        self._counter_base = {}    # the counters' totals at the last report
        self._span_sent = False    # this trace has the program's scopes
        if self._counter_names:
            self.health.add_source(self._report_counters)

        # --- AOT-compile + register every program ---------------------
        self.name = _tc.unique_name(name or "serving-decode")
        self._jfns = []
        self._programs = {}

        def compile_one(tag, fn, structs, donate, options=None):
            jfn = jax.jit(fn, donate_argnums=donate)
            compiled = jfn.lower(*structs).compile(
                compiler_options=options or None)
            pname = "%s/%s" % (self.name, tag)
            _tc.register_program(pname, jfn, structs,
                                 donate_argnums=donate)
            self._jfns.append(jfn)   # registry holds only a weakref
            self._programs[pname] = (compiled, structs, donate)
            return compiled

        #: the prefill program, where the architecture supplies a prefill
        #: pass and the loop feeds prompts through it (not a speculative
        #: loop, whose window takes ``spec_k + 1`` prompt positions a round
        #: already; not over a model mesh); else every prompt position
        #: rides a step
        self._prefill_c = None
        self._chunk = 0
        samp = self._sampling_structs(jax)
        live_s = (self._vec_struct(jax, (self.slots,), np.bool_),)
        state_s = self._tree_structs(jax, self._state)
        params_s = self._tree_structs(jax, self._params)
        if self.spec_k:
            window = self.spec_k + 1
            dstate_s = self._tree_structs(jax, self._draft_state)
            dparams_s = self._tree_structs(jax, self._draft_params)
            tokw_s = self._vec_struct(jax, (self.slots, window), np.int32)
            self._verify_c = compile_one(
                "verify[slots=%d,win=%d]" % (self.slots, window),
                _build_verify_fn(arch, window, mesh=self._mesh),
                (state_s, params_s, tokw_s) + samp[1:]
                + live_s * arch.wants_live, (0,))
            self._jfn = self._jfns[-1]   # the main decode body
            self._draft_c = compile_one(
                "draft[slots=%d,len=%d]" % (self.slots, self.max_len),
                _build_decode_fn(draft_arch, mesh=self._mesh),
                (dstate_s, dparams_s) + samp
                + live_s * draft_arch.wants_live, (0,))
        else:
            self._step_c = compile_one(
                "step[slots=%d,len=%d]" % (self.slots, self.max_len),
                _build_decode_fn(arch, mesh=self._mesh),
                (state_s, params_s) + samp + live_s * arch.wants_live,
                (0,), arch.compiler_options(jax.default_backend()))
            self._jfn = self._jfns[-1]   # the main decode body
            prefill = _build_prefill_fn(arch, mesh=self._mesh)
            if prefill is not None:
                self._chunk = min(PACKED_ROWS if arch.packed_prefill
                                  else PREFILL_CHUNK, self._rows)
                scalar_s = self._vec_struct(jax, (), np.int32)
                rows_s = self._vec_struct(jax, (self._chunk,), np.int32)
                # a packed pass's rows each name their slot and position
                where_s = rows_s if arch.packed_prefill else scalar_s
                self._prefill_c = compile_one(
                    "prefill[chunk=%d,len=%d]" % (self._chunk, self.max_len),
                    prefill, (state_s, params_s, rows_s, where_s, where_s,
                              scalar_s), (0,))
        #: a traced run's copy of the device counters (see
        #: :meth:`_snapshot_counters`), built with the others: a compile
        #: inside a measured window is failed work
        self._counter_snap = None      # (step, the copies) not yet read
        if self._counter_names:
            self._snapshot_c = compile_one(
                "counters[%s]" % ",".join(self._counter_names),
                _build_snapshot_fn(),
                ({k: state_s[k] for k in self._counter_names},), ())
        if self.prefix_enabled:
            slot_s = self._vec_struct(jax, (), np.int32)
            self._prefix_programs(compile_one, jax, "target", arch,
                                  state_s, slot_s)
            if self.spec_k:
                self._prefix_programs(compile_one, jax, "draft", draft_arch,
                                      dstate_s, slot_s)

        # MXTPU_MEMCHECK / MXTPU_COMMSCHECK: audit the whole decode
        # program set at LOAD time — memory_report() covers every program
        # above, so the resident-set lint prices the draft+target pair
        # (and the KV caches, the dominant buffers) before any traffic
        from .engine import _audit_load_memory, _audit_load_comms
        _audit_load_memory(self, "DecodeLoop")
        _audit_load_comms(self, "DecodeLoop")

        #: device-resident prefix registry: key (the prefix token tuple)
        #: -> {"len", "target": {k,v}, "draft": {k,v}|None}, LRU-bounded
        self._prefix = collections.OrderedDict()
        self._join_q = queue.Queue()
        self._slots = [None] * self.slots
        self._closed = False
        self.dead = None
        self._steps = 0   # decode-step ordinal for the host trace
        self._seated = 0  # requests seated so far: a slot's ``seat``
        #: the step dispatched and not read back yet: ``[its tokens on the
        #: device, [(slot index, _Slot, emits, last)]]``, or None
        self._inflight = None
        self._cpu_ns = None   # loop thread's CPU clock at the last traced
        #                       step's end (None: the next has no cpu_us)
        self._wake = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="mxtpu-serve-decode",
                                        daemon=True)
        self._thread.start()

    # ------------------------------------------------------------------
    def _resolve_knobs(self, host_params, prefix_cache, spec_k,
                       draft_params):
        """arg > MXTPU_SERVE_* env > tuning DB > default (``quantize`` is
        resolved before the parameters are copied). A DB-resolved
        ``spec_k`` without a draft model falls back with a warning (a
        stale DB row must not break a deploy); an arg/env one raises."""
        db = {}
        if spec_k is None and not env_str("MXTPU_SERVE_SPEC_K") \
                or prefix_cache is None \
                and not env_str("MXTPU_SERVE_PREFIX_CACHE"):
            try:
                from .. import autotune as _at
                if _at.enabled():
                    db = _at.resolve_decode_knobs(host_params) or {}
            except Exception as e:
                logging.warning("DecodeLoop: tuning-DB resolution failed "
                                "(%r) — using defaults", e)

        src = "default"
        if spec_k is not None:
            self.spec_k, src = int(spec_k), "arg"
        elif env_str("MXTPU_SERVE_SPEC_K"):
            self.spec_k, src = env_int("MXTPU_SERVE_SPEC_K", 0), "env"
        elif "spec_k" in db:
            self.spec_k, src = int(db["spec_k"]), "db"
        else:
            self.spec_k = 0
        if self.spec_k < 0:
            raise MXNetError("DecodeLoop: spec_k must be >= 0, got %d"
                             % self.spec_k)
        if self.spec_k and draft_params is None:
            if src == "db":
                logging.warning(
                    "DecodeLoop: tuning DB resolved spec_k=%d but no "
                    "draft_params were given — speculative decoding "
                    "disabled", self.spec_k)
                self.spec_k = 0
            else:
                raise MXNetError(
                    "DecodeLoop: spec_k=%d (%s) needs draft_params — "
                    "speculative decoding drafts through a small "
                    "co-resident model" % (self.spec_k, src))

        if prefix_cache is not None:
            self.prefix_enabled = bool(prefix_cache)
        elif env_str("MXTPU_SERVE_PREFIX_CACHE"):
            self.prefix_enabled = env_str("MXTPU_SERVE_PREFIX_CACHE") \
                .lower() not in ("0", "false", "off", "no")
        elif "prefix_cache" in db:
            self.prefix_enabled = bool(int(db["prefix_cache"]))
        else:
            self.prefix_enabled = True

    def _place_leaf(self, leaf):
        """Place one stored parameter leaf (array or int8 ``{"q","s"}``
        pair). Sharded loops shard the int8 payload by the placement rule
        and pin the per-channel scale along the SAME axis-0 split, so
        each chip holds 1/N of the quantized bytes."""
        import jax
        import jax.numpy as jnp
        if self._mesh is None:
            if is_quantized_leaf(leaf):
                return {"q": jnp.asarray(leaf["q"]),
                        "s": jnp.asarray(leaf["s"])}
            return jnp.asarray(leaf)
        from ..parallel import placement as _pl
        from ..parallel.mesh import AXIS_MODEL

        def put(arr, spec):
            return jax.device_put(arr, jax.sharding.NamedSharding(
                self._mesh, spec or jax.sharding.PartitionSpec()))

        if is_quantized_leaf(leaf):
            spec = _pl.auto_spec(AXIS_MODEL, tuple(leaf["q"].shape),
                                 self._mesh, prefer_first=True)
            s_spec = None
            if spec is not None and len(spec) and spec[0]:
                s_spec = jax.sharding.PartitionSpec(spec[0])
            return {"q": put(leaf["q"], spec), "s": put(leaf["s"], s_spec)}
        spec = _pl.auto_spec(AXIS_MODEL, tuple(leaf.shape), self._mesh,
                             prefer_first=True)
        return put(leaf, spec)

    def _init_state(self, arch, host_params):
        """The donated device state of one model: the arrays the
        architecture's ``slot_state`` names, each ``(layers, slots, depth,
        width)`` with its OWN layers, depth and width (for :class:`OptArch`
        a K and a V cache of ``heads * head_dim`` float32 over every layer,
        a row a position; a recurrent state is a fixed few rows over the
        layers that keep one), its counters, the slots' seeds and, where
        the decode body steps it, the token that body last sampled a slot.
        The ``width`` is the minor dimension (128 lanes at a time) and the
        depth the second minor, for a per-position array a multiple of
        the sublanes a tile of its dtype holds (:meth:`SlotArray.depth`):
        the chip's tiles hold no padding in the rows, and the step program
        computes in this layout as stored (see :func:`_build_token_pass`). A model mesh
        shards a slot-state array as the architecture says (OptArch: the
        minor dimension, a group of whole heads per chip)."""
        import jax
        import jax.numpy as jnp
        spec = arch.slot_state(host_params, self.quant_mode)
        positions = self.max_len + (1 if self.spec_k else 0)
        state = {k: jnp.zeros((a.layers, self.slots, a.depth(positions),
                               a.width), a.dtype) for k, a in spec.items()}
        if arch is self._arch:
            #: the depth of the arrays that keep a row a position, and the
            #: prefixes of it a step's attention may cover (the token pass
            #: derives the same ladder from the same depth)
            self._rows = max([a.depth(positions) for a in spec.values()
                              if a.rows is PER_POSITION] or [0])
            self._ladder = rows_ladder(self._rows)
            #: the same of a RING (rows addressed by ``pos % depth``), 0
            #: without one: its ladder is climbed until the ring is full
            self._ring_rows = max([a.depth(positions)
                                   for a in spec.values() if a.ring] or [0])
            self._ring_ladder = rows_ladder(self._ring_rows)
            self._state_arrays = {
                k: [int(v.shape[0]), int(v.shape[2]), int(v.shape[3]),
                    str(v.dtype), int(v.nbytes)]
                for k, v in state.items()}
        state.update({k: jnp.zeros(shape, np.int32)
                      for k, shape in arch.counters().items()})
        state["seed"] = jnp.zeros((self.slots,), np.uint32)
        if arch is not self._arch or not self.spec_k:
            # stepped by the decode body (the verify body hands no token on)
            state["tok"] = jnp.zeros((self.slots,), np.int32)
        if self._mesh is not None:
            P = jax.sharding.PartitionSpec
            slot_sh = jax.sharding.NamedSharding(
                self._mesh, P(*arch.slot_partition()))
            repl = jax.sharding.NamedSharding(self._mesh, P())
            state = {k: jax.device_put(v, slot_sh if k in spec else repl)
                     for k, v in state.items()}
        return state

    def _prefix_programs(self, compile_one, jax, which, arch, state_s,
                         slot_s):
        names = sorted(set(state_s) - set(arch.counters()) - set(LOOP_STATE))
        sh = None
        if self._mesh is not None:
            part = arch.slot_partition()
            sh = jax.sharding.NamedSharding(
                self._mesh,
                jax.sharding.PartitionSpec(*(part[:1] + part[2:])))
        slab_s = {}
        for k in names:
            shape = tuple(state_s[k].shape)
            slab_s[k] = jax.ShapeDtypeStruct(
                (shape[0],) + shape[2:], state_s[k].dtype,
                **({} if sh is None else {"sharding": sh}))
        get_c = compile_one("prefix_get[%s]" % which,
                            _build_extract_fn(names, sh),
                            (state_s, slot_s), ())
        put_c = compile_one("prefix_put[%s]" % which, _build_implant_fn(),
                            (state_s, slot_s, slab_s), (0,))
        if which == "target":
            self._extract_c, self._implant_c = get_c, put_c
        else:
            self._extract_draft_c, self._implant_draft_c = get_c, put_c

    # ------------------------------------------------------------------
    def _sds(self, jax, x):
        sh = getattr(x, "sharding", None)
        if (self._mesh is not None
                and isinstance(sh, jax.sharding.NamedSharding)):
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype,
                                        sharding=sh)
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)

    def _tree_structs(self, jax, tree):
        out = {}
        for k, v in tree.items():
            if is_quantized_leaf(v):
                out[k] = {"q": self._sds(jax, v["q"]),
                          "s": self._sds(jax, v["s"])}
            else:
                out[k] = self._sds(jax, v)
        return out

    def _vec_struct(self, jax, shape, dtype):
        if self._mesh is not None:
            repl = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec())
            return jax.ShapeDtypeStruct(shape, dtype, sharding=repl)
        return jax.ShapeDtypeStruct(shape, dtype)

    def _sampling_structs(self, jax):
        """(tokens, pos, temp, top_k, top_p, fresh_seed, reseed)."""
        n = (self.slots,)
        return (self._vec_struct(jax, n, np.int32),
                self._vec_struct(jax, n, np.int32),
                self._vec_struct(jax, n, np.float32),
                self._vec_struct(jax, n, np.int32),
                self._vec_struct(jax, n, np.float32),
                self._vec_struct(jax, n, np.uint32),
                self._vec_struct(jax, n, np.bool_))

    def _dev(self, arrs):
        if self._mesh is None:
            import jax.numpy as jnp
            return [jnp.asarray(a) for a in arrs]
        import jax
        repl = jax.sharding.NamedSharding(self._mesh,
                                          jax.sharding.PartitionSpec())
        return [jax.device_put(a, repl) for a in arrs]

    def _dev_scalar(self, i):
        return self._dev([np.int32(i)])[0]

    # ------------------------------------------------------------------
    @property
    def devices(self):
        """The devices holding this loop's parameters, by id."""
        from .engine import _leaf_devices
        return _leaf_devices(self._params)

    def weight_bytes(self):
        """Resident HBM bytes of the (possibly quantized) parameter
        set(s) — target plus draft; GLOBAL across shards (a fully
        sharded loop holds 1/N of this per chip). The memcheck HBM win
        the int8 leg is gated on (docs/serving.md "Quantized
        weights")."""
        total = tree_bytes(self._params)
        if self._draft_params is not None:
            total += tree_bytes(self._draft_params)
        return total

    # ------------------------------------------------------------------
    def update_params(self, params):
        """Hot-reload the TARGET parameter set under the RUNNING loop
        with zero recompiles (train-to-serve handoff, docs/serving.md
        "Hot reload"): the decode body takes params per call and only the
        state is donated, so swapping the dict re-binds the next step's
        arguments without touching the compiled executable. Under a
        quantized loop the incoming f32 checkpoint is re-quantized
        host-side first. (The draft model is fixed at construction —
        rebuild the loop to swap drafts.)

        Every resident parameter must arrive with its exact shape; new
        arrays land with the resident arrays' shardings (the AOT
        executable binds placements). The swap is one atomic dict rebind —
        the decode thread picks the new set up at its next step, and each
        step reads the dict exactly once, so in-flight sequences continue
        on a CONSISTENT parameter set (their KV cache keeps prefix
        entries from the old weights — the standard continuous-batching
        reload semantics; retire slots first for a clean cut)."""
        import jax
        missing = sorted(set(self._params) - set(params))
        if missing:
            raise MXNetError(
                "update_params: checkpoint is missing %s — a partial swap "
                "would decode a chimera; pass the full "
                "models/transformer.py parameter set"
                % ", ".join(missing[:8]))
        new = {}
        for n, resident in self._params.items():
            arr = _host_leaf(params[n], self.quant_mode)
            rq = resident["q"] if is_quantized_leaf(resident) else resident
            if tuple(arr.shape) != tuple(rq.shape):
                raise MXNetError(
                    "update_params: %r shape %s does not match the "
                    "compiled decode body's %s — rebuild the loop for a "
                    "different architecture"
                    % (n, tuple(arr.shape), tuple(rq.shape)))
            stored = quantize_array(arr, self.quant_mode)
            if is_quantized_leaf(resident):
                new[n] = {
                    "q": jax.device_put(stored["q"],
                                        resident["q"].sharding),
                    "s": jax.device_put(stored["s"],
                                        resident["s"].sharding)}
            else:
                sh = getattr(resident, "sharding", None)
                if not isinstance(stored, jax.Array):   # else: as it is
                    stored = np.asarray(stored, rq.dtype)
                new[n] = jax.device_put(stored, sh) \
                    if sh is not None else jax.numpy.asarray(stored)
        # land transfers BEFORE the rebind so the decode thread never
        # blocks on (or races) an in-flight H2D mid-step
        for v in new.values():
            if is_quantized_leaf(v):
                v["q"].block_until_ready()
                v["s"].block_until_ready()
            else:
                v.block_until_ready()
        self._params = new
        from ..obs import REGISTRY
        REGISTRY.counter(
            "serving.param_reloads",
            "parameter hot-reloads into live serving engines").inc()
        _obs.instant("decode_param_reload", params=len(new))
        logging.info("%s: hot-reloaded %d parameters (zero recompiles, "
                     "quantize=%s)", self.name, len(new), self.quant_mode)

    # ------------------------------------------------------------------
    def generate(self, prompt, max_new_tokens, temperature=0.0, top_k=0,
                 top_p=1.0, seed=None, prefix_len=0):
        """Queue one sequence; returns a :class:`GenerateFuture` whose
        ``result()`` is the list of generated token ids.

        ``temperature=0`` (the default) is bitwise greedy decoding;
        ``temperature>0`` samples through the in-graph
        top-k/top-p/inverse-CDF path, deterministically per ``seed``.
        ``prefix_len=L`` declares ``prompt[:L]`` a shared prefix for the
        KV prefix cache (first use prefills and stores it; later joins
        implant the cached slab and skip those L steps)."""
        if self.dead is not None or self._closed:
            raise ServingClosedError(
                "decode loop is not running (%s)"
                % (self.dead or "closed"))
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise MXNetError("generate: empty prompt")
        bad = [t for t in prompt if t < 0 or t >= self.vocab_size]
        if bad:
            # same clamp hazard as positions: an out-of-vocab id would
            # silently embed as the last vocab row
            raise MXNetError(
                "generate: prompt token id(s) %s outside the vocabulary "
                "[0, %d)" % (bad[:5], self.vocab_size))
        if len(prompt) + int(max_new_tokens) > self.max_len:
            raise MXNetError(
                "generate: prompt (%d) + max_new_tokens (%d) exceeds the "
                "cache length %d" % (len(prompt), max_new_tokens,
                                     self.max_len))
        temperature, top_k, top_p = validate_sampling(
            temperature, top_k, top_p)
        prefix_len = int(prefix_len)
        if prefix_len < 0 or prefix_len >= len(prompt):
            raise MXNetError(
                "generate: prefix_len %d must be in [0, len(prompt)=%d) — "
                "at least one prompt token must follow the shared prefix"
                % (prefix_len, len(prompt)))
        fut = GenerateFuture(self, prompt, max_new_tokens,
                             temperature=temperature, top_k=top_k,
                             top_p=top_p, seed=seed, prefix_len=prefix_len)
        self._join_q.put(fut)
        self._wake.set()
        _obs.instant("decode_submit", req=fut.rid, prompt_len=len(prompt),
                     max_new=int(max_new_tokens))
        self.health.record_request()
        return fut

    def close(self):
        self._closed = True
        self._wake.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
        self._shed(ServingClosedError("decode loop closed"))
        if self._counter_names:     # the last counts, then let go of us
            self._report_counters()
            self.health.remove_source(self._report_counters)

    # ------------------------------------------------------------------
    def _shed(self, exc):
        shed = 0
        outcome = "failed" if self.dead is None else "shed"
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._request_done(slot.fut, outcome, i, slot)
                slot.fut.fail(exc)
                self._slots[i] = None
                shed += 1
        # and the requests whose last step is in flight: they left their
        # slots when it was dispatched, and their tokens will not be read
        rec, self._inflight = self._inflight, None
        for i, slot, _, last in rec[1] if rec is not None else ():
            if last and not slot.fut.done():
                self._request_done(slot.fut, outcome, i, slot)
                shed += slot.fut.fail(exc)
        while True:
            try:
                fut = self._join_q.get_nowait()
                self._request_done(fut, outcome)
                fut.fail(exc)
                shed += 1
            except queue.Empty:
                break
        if shed:
            self.health.record_shed(shed, exc)

    def _admit(self):
        """Seat queued requests in free slots; returns how many joined."""
        joined = 0
        for i in range(self.slots):
            if self._slots[i] is not None:
                continue
            try:
                fut = self._join_q.get_nowait()
            except queue.Empty:
                break
            joined += 1
            self._seated += 1
            slot = _Slot(fut, self._seated, self._steps + 1)
            self._slots[i] = slot
            if self.prefix_enabled and fut.prefix_len > 0:
                key = tuple(fut.prompt[:fut.prefix_len])
                entry = self._prefix.get(key)
                if entry is not None:
                    self._prefix.move_to_end(key)
                    self._implant_slot(i, entry)
                    slot.pos = entry["len"]
                    slot.pending = list(fut.prompt[entry["len"]:])
                    slot.next_token = slot.pending.pop(0)
                    slot.prefix_hit = entry["len"]
                    self.health.record_prefix_hit()
                    _obs.instant("decode_prefix_hit", req=fut.rid, slot=i,
                                 plen=entry["len"])
                else:
                    slot.producing = (key, fut.prefix_len)
            self.health.record_join()
        return joined

    def _implant_slot(self, i, entry):
        s = self._dev_scalar(i)
        t = entry["target"]
        with self._state_lock:
            self._state = self._implant_c(self._state, s, t)
        if self.spec_k and entry["draft"] is not None:
            self._draft_state = self._implant_draft_c(
                self._draft_state, s, entry["draft"])

    def _maybe_harvest(self, i):
        """Prefix-cache producer path: once this slot has teacher-forced
        past its declared prefix, copy the slab out and publish it."""
        slot = self._slots[i]
        if slot is None or slot.producing is None:
            return
        key, plen = slot.producing
        if slot.pos < plen:
            return
        slot.producing = None
        if key in self._prefix:        # a co-rider raced us to it
            self._prefix.move_to_end(key)
            return
        s = self._dev_scalar(i)
        slab = self._extract_c(self._state, s)
        entry = {"len": plen, "target": slab, "draft": None}
        if self.spec_k:
            entry["draft"] = self._extract_draft_c(self._draft_state, s)
        self._prefix[key] = entry
        while len(self._prefix) > self.prefix_max:
            self._prefix.popitem(last=False)   # LRU eviction
        self.health.record_prefix_prefill()
        _obs.instant("decode_prefix_store", slot=i, plen=plen)

    def _run(self):
        from .. import faults as _faults
        try:
            while not self._closed:
                t_admit = time.perf_counter()
                joined = self._admit()
                if joined:      # a span only where it seated someone
                    _obs.complete("decode_admit",
                                  time.perf_counter() - t_admit,
                                  step=self._steps + 1, joined=joined)
                if all(s is None for s in self._slots):
                    if self._inflight is not None:
                        # every slot was dispatched for the last time:
                        # nothing to run ahead of, so settle that step
                        self._drain()
                    else:
                        self._idle()
                    continue
                act = _faults.fire("serve.decode_die")
                if act == "die":
                    raise MXNetError(
                        "injected decode-loop death (serve.decode_die)")
                self._step()
        except BaseException as e:   # shed, then die visibly
            self.dead = e
            self._shed(ServingClosedError(
                "decode loop died: %r — request shed" % (e,)))
            # post-mortem before the thread exits (docs/observability.md);
            # dump() never raises into this failure path
            from ..obs import flight as _flight
            _flight.dump("decode loop died: %r" % (e,),
                         extra={"health": self.health.report()})
            return

    def _idle(self):
        """The empty loop's wait: no slot is seated, no step is in flight.
        ONE ``loop_idle`` span a stretch, from the poll that found the loop
        so until someone waits to be seated (or the loop is closed),
        however many polls of 50 ms that takes: the device's idle in it is
        the traffic's, and a trace says so (docs/observability.md). It
        ends before the ``_admit`` that seats the newcomer begins."""
        t0 = time.perf_counter()
        while not self._closed and self._join_q.empty():
            self._wake.wait(timeout=0.05)
            self._wake.clear()
        _obs.complete("loop_idle", time.perf_counter() - t0,
                      step=self._steps)

    def _step(self):
        self._steps += 1
        body = self._step_spec if self.spec_k else self._step_inner
        if not _obs.active():
            self._cpu_ns = None
            self._span_sent = False
            body(_obs.NOOP)     # no span is live: nothing is built for one
            return
        occ = [s for s in self._slots if s is not None]
        with _obs.span("decode_step", step=self._steps,
                       reqs=[s.fut.rid for s in occ]) as sp:
            pos = [s.pos for s in occ]
            had = [s.sent for s in occ]
            body(sp)
            # of the step DISPATCHED in this span, aligned with reqs (a
            # slot that left keeps its counts): where each request stood,
            # the positions the step commits and the tokens it emits. A
            # slot-step the loop knows to be trash by now has 0 and 0
            sp.set(pos=pos, n=[s.pos - p for s, p in zip(occ, pos)],
                   emit=[s.sent - h for s, h in zip(occ, had)])
            # the thread's CPU clock is a system call (20-40 us each in a
            # process with JAX's threads; chip run, PR 27): read once a
            # step, and for the trace file only, not for the recorder
            cpu = time.thread_time_ns() if _obs.enabled() else None
            if cpu is not None and self._cpu_ns is not None:
                sp.set(cpu_us=(cpu - self._cpu_ns) // 1000)
            self._cpu_ns = cpu
        if cpu is None:
            self._span_sent = False
            return
        # for the trace file only: once a trace the programs' names and
        # the step's table of instruction -> scope, and every
        # COUNTER_SPAN_STEPS steps the architecture's device counters
        # (never once a step): copied behind step n, read in span n + 1
        if not self._span_sent:
            self._span_sent = True
            self._program_span()
        if self._counter_names and self._steps % COUNTER_SPAN_STEPS < 2:
            if self._steps % COUNTER_SPAN_STEPS:
                self._deliver_counters()
            else:
                self._snapshot_counters()

    def _snapshot_counters(self):
        """Enqueue a copy of the device counters behind the step just
        dispatched and start its way to the host; nothing waits.
        ``counter_totals()`` here would: ``np.asarray`` of the state's own
        arrays returns when the step in flight has, and the next step is
        then gathered, put and dispatched with the device idle (a gap of
        4-6 ms every 32 steps in every cell with counters, most of the
        idle a traced run recorded there; PERF.md, PR 38). The copies are
        the snapshot program's own outputs: the next dispatch donates the
        state's arrays and leaves them alone."""
        self._deliver_counters()
        with self._state_lock:
            snap = self._snapshot_c({k: self._state[k]
                                     for k in self._counter_names})
        for v in snap.values():
            v.copy_to_host_async()
        self._counter_snap = (self._steps, snap)

    def _deliver_counters(self):
        """The ``loop_counters`` span of the snapshot taken behind step n,
        emitted where step n's tokens have been read back (span n + 1, or
        the drain): the copies are on the host by then, or a few
        microseconds away while the device runs step n + 1. A snapshot
        whose turn passed (tracing went off in between) is dropped."""
        snap, self._counter_snap = self._counter_snap, None
        if snap is None or snap[0] < self._steps - 1:
            return
        t0 = time.perf_counter()
        counts = {k: np.asarray(v).tolist() for k, v in snap[1].items()}
        _obs.complete("loop_counters", time.perf_counter() - t0,
                      step=snap[0], **counts)

    def _gather_sampling(self):
        """Host-side per-slot dispatch arrays (and consume reseed marks)."""
        n = self.slots
        arrs = {"tokens": np.zeros(n, np.int32),
                "pos": np.zeros(n, np.int32),
                "temp": np.zeros(n, np.float32),
                "top_k": np.zeros(n, np.int32),
                "top_p": np.ones(n, np.float32),
                "fresh": np.zeros(n, np.uint32),
                "reseed": np.zeros(n, np.bool_),
                "live": np.zeros(n, np.bool_)}
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            arrs["live"][i] = True
            arrs["tokens"][i] = slot.next_token
            arrs["pos"][i] = slot.pos
            arrs["temp"][i] = slot.fut.temperature
            arrs["top_k"][i] = slot.fut.top_k
            arrs["top_p"][i] = slot.fut.top_p
            if slot.reseed:
                arrs["fresh"][i] = slot.fut.seed
                arrs["reseed"][i] = True
                slot.reseed = False
        return arrs

    def _prefill(self, sp):
        """At most ONE prefill pass, dispatched ahead of the step. A slot
        is DUE with :data:`MIN_PREFILL` prompt positions or more to feed
        BEFORE its prompt's last; the pass writes ``next_token`` and what
        follows it in ``pending`` into the slot's rows, never ``pending``'s
        last: the step feeds that one and samples the first token, as ever.

        A ONE-SLOT pass takes up to ``chunk`` positions of the longest
        seated due slot, at once. A PACKED pass is filled from every due
        slot, longest seated first, until it is full (a slot whose rest
        does not fit gives what fits), and is dispatched only if it is
        full or carries :data:`PASS_PAYS` times the generating slots'
        number of positions; otherwise nothing is dispatched and the due
        slots ride the step, as without a pass, so none can starve.

        All by count: the pass hands nothing back, so the step behind it
        is dispatched at once and the run-ahead is as it was. Returns the
        positions the pass commits (0: none was dispatched), which the
        step's span and the counters take as prompt positions like any
        other."""
        packed = self._arch.packed_prefill
        due = sorted((slot.seat, i) for i, slot in enumerate(self._slots)
                     if slot is not None and len(slot.pending) >= MIN_PREFILL)
        room, take = self._chunk, []
        for _, i in due if packed else due[:1]:
            n = min(room, len(self._slots[i].pending))
            take.append((i, n))
            room -= n
            if not room:
                break
        if not take:
            return 0
        total = self._chunk - room
        if packed and room and total < max(MIN_PREFILL, PASS_PAYS * sum(
                1 for s in self._slots if s is not None and not s.pending)):
            return 0    # held back: it does not pay for its read yet
        tokens = np.zeros(self._chunk, np.int32)
        where = np.zeros((2, self._chunk), np.int32)    # slot, position a row
        at, entries = 0, []
        for i, n in take:
            slot = self._slots[i]
            tokens[at] = slot.next_token
            tokens[at + 1:at + n] = slot.pending[:n - 1]
            where[0, at:at + n] = i
            where[1, at:at + n] = np.arange(slot.pos, slot.pos + n)
            entries.append([slot.fut.rid, i, slot.pos, n])
            at += n
        dev = self._dev([tokens] + ([where[0], where[1]] if packed else
                                    [where[0, 0], where[1, 0]])
                        + [np.int32(total)])
        with self._state_lock:
            self._state = self._prefill_c(self._state, self._params, *dev)
        del dev     # as in _step_inner: released while the device works
        for i, n in take:
            slot = self._slots[i]
            slot.pos += n
            slot.next_token = slot.pending[n - 1]
            del slot.pending[:n]
            slot.prefill[0] += 1
            slot.prefill[1] += n
            self._maybe_harvest(i)
        self.health.record_prefill(total, len(take))
        sp.lap("decode_prefill")
        sp.set(prefill=entries)
        return total

    def _step_inner(self, sp):
        """One step, one step AHEAD of its readback: step n is fed and
        dispatched, THEN step n-1's tokens are read back and committed,
        so the device runs n while the host does that and gets n+1 ready.
        What n's dispatch needs of n-1 the host has as a count (``pos``,
        ``pending``, ``max_new``, ``max_len``) or the device kept
        (:data:`FED_BACK`); only ``eos_id`` needs the token's value, and
        is learned a step late (:meth:`_commit`).

        ``sp`` is the step's span (or the no-op): each phase ends in a lap
        of it (docs/observability.md "Span catalogue"):
        ``decode_prefill`` where a prefill pass went ahead of the step
        (:meth:`_prefill`), ``decode_gather``, ``decode_h2d``,
        ``decode_dispatch`` of step n, then ``decode_readback``,
        ``decode_commit`` of step n-1 (of length 0 where no step is in
        flight: the first after an empty loop)."""
        from .. import faults as _faults
        chunked = self._prefill(sp) if self._prefill_c is not None else 0
        a = self._gather_sampling()
        sp.lap("decode_gather")
        _faults.fire("serve.sample")
        dev = self._dev([a["tokens"], a["pos"], a["temp"], a["top_k"],
                         a["top_p"], a["fresh"], a["reseed"]]
                        + [a["live"]] * self._arch.wants_live)
        sp.lap("decode_h2d")
        before = self._inflight
        with self._state_lock:
            self._state, toks = self._step_c(self._state, self._params,
                                             *dev)
        # this step's copy to the host starts behind IT: asked for only
        # when it is read, after the next dispatch, it would wait for that
        toks.copy_to_host_async()
        # the argument buffers are released here, while the device works,
        # and the token buffer with the readback: left to this function's
        # end, eight releases fall into the device's idle time after the
        # commit, in no phase (chip run, PR 27)
        del dev
        rows, prompt = self._schedule()
        self._inflight = [toks, rows]
        del toks
        self._count_step(sp, a, 0, prompt + chunked,
                         ahead=int(before is not None))
        sp.lap("decode_dispatch")
        self._commit(sp, before)

    def _schedule(self):
        """Advance every seated slot over the step just dispatched, by
        count: ``[(slot index, slot, emits, last)]`` and how many of them
        fed a prompt position. A slot dispatched for the last time
        (``max_new`` tokens sent, or the cache full) LEAVES here, free for
        :meth:`_admit`; its request settles when the step is read back."""
        rows, prompt = [], 0
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            slot.pos += 1
            emits = not slot.pending
            if emits:
                slot.sent += 1
                slot.next_token = FED_BACK
            else:
                # prompt still feeding: next input is teacher-forced
                slot.next_token = slot.pending.pop(0)
                prompt += 1
            last = (emits and slot.sent >= slot.fut.max_new) \
                or slot.pos >= self.max_len
            rows.append((i, slot, emits, last))
            if last:
                self._slots[i] = None
                slot.steps[2] = self._steps
            else:
                self._maybe_harvest(i)
        return rows, prompt

    def _commit(self, sp, rec):
        """Read back and commit ``rec``, the step dispatched before the
        one now in flight (or the last one, from :meth:`_drain`): hand
        each emitting slot its token, stamped now, when the host has it,
        and settle the requests this was the last step of.

        A token equal to ``eos_id`` ends its request here, ONE STEP LATE:
        the slot is in the step in flight already. That slot-step is
        trash: its token is dropped when it is read (``trash_slot_steps``),
        and its cache write went to the slot's own next row, which the
        next occupant rewrites before any of its queries attends it."""
        if rec is None:
            sp.lap("decode_readback")
            sp.lap("decode_commit")
            return
        host_toks = np.asarray(rec[0])   # the one per-step readback
        rec[0] = None      # its buffer is released inside this phase
        rows = rec[1]
        sp.lap("decode_readback")
        now = time.perf_counter()
        emitted = trash = 0
        leaving = []
        for i, slot, emits, last in rows:
            if slot.fut.done():     # its eos was read a step ago
                trash += 1
                continue
            if emits:
                tok = int(host_toks[i])
                slot.emitted.append(tok)
                slot.fut.token_times.append(now)
                emitted += 1
                last = last or (self.eos_id is not None
                                and tok == self.eos_id)
            if not last:
                continue
            leaving.append((i, slot))
            if self._slots[i] is slot:
                # eos: still seated, so in the step in flight, which now
                # commits no position and emits no token of its own
                self._slots[i] = None
                slot.pos -= 1
                slot.sent -= 1
                slot.steps[2] = self._steps - 1   # the step read back here
        self.health.record_tokens(emitted, trash)
        for i, slot in leaving:
            self._settle(i, slot)
        sp.lap("decode_commit")

    def _drain(self):
        """Settle the step in flight with no step to run ahead of it: the
        loop goes idle (or seats whoever was waiting for these tokens)."""
        t0 = time.perf_counter()
        rec, self._inflight = self._inflight, None
        self._commit(_obs.NOOP, rec)
        _obs.complete("loop_drain", time.perf_counter() - t0,
                      step=self._steps)
        self._deliver_counters()

    def _step_spec(self, sp):
        """One draft-K-then-verify round: K+1 cheap draft passes chain
        the proposals (teacher-forced wherever the prompt already knows
        the token, so the draft cache stays position-synced), then ONE
        batched target pass samples every window position; the host
        replays the window through exactly the single-token accounting,
        committing samples until the first mismatch with the window's
        inputs (docs/serving.md "Speculative decoding")."""
        from .. import faults as _faults
        window = self.spec_k + 1
        draft, verify = {"pass": "draft"}, {"pass": "verify"}
        a = self._gather_sampling()
        w = np.zeros((self.slots, window), np.int32)
        w[:, 0] = a["tokens"]
        dfill = np.zeros((self.slots, window), np.bool_)
        pend0 = [list(s.pending) if s is not None else []
                 for s in self._slots]
        _faults.fire("serve.sample")
        no_reseed = np.zeros(self.slots, np.bool_)
        for j in range(window):
            sp.lap("decode_gather")
            dev = self._dev([w[:, j].copy(),
                             (a["pos"] + j).astype(np.int32), a["temp"],
                             a["top_k"], a["top_p"], a["fresh"],
                             a["reseed"] if j == 0 else no_reseed]
                            + [a["live"]] * self._draft_arch.wants_live)
            sp.lap("decode_h2d")
            self._draft_state, d_toks = self._draft_c(
                self._draft_state, self._draft_params, *dev)
            sp.lap("decode_dispatch", **draft)
            if j + 1 >= window:
                break
            d_host = np.asarray(d_toks)
            sp.lap("decode_readback", **draft)
            for i, slot in enumerate(self._slots):
                if slot is None:
                    continue
                if j < len(pend0[i]):
                    w[i, j + 1] = pend0[i][j]     # prompt knows this one
                else:
                    w[i, j + 1] = d_host[i]       # draft proposal
                    dfill[i, j + 1] = True
        _faults.fire("serve.spec_verify")
        dev = self._dev([w, a["pos"], a["temp"], a["top_k"], a["top_p"],
                         a["fresh"], a["reseed"]]
                        + [a["live"]] * self._arch.wants_live)
        sp.lap("decode_h2d")
        with self._state_lock:
            self._state, samples = self._verify_c(self._state, self._params,
                                                  *dev)
        del dev, d_toks     # as in _step_inner: released inside a phase
        sp.lap("decode_dispatch", **verify)
        s = np.asarray(samples)        # (slots, window) int32
        del samples
        sp.lap("decode_readback", **verify)
        now = time.perf_counter()
        accepted = judged = emitted = prompt = 0
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            for j in range(window):
                slot.pos += 1
                if slot.pending:
                    nxt = slot.pending.pop(0)
                    prompt += 1
                else:
                    tok = int(s[i, j])
                    slot.emitted.append(tok)
                    if not slot.sent:   # the round that ends the prompt
                        slot.steps[1] = self._steps
                    slot.sent += 1
                    slot.fut.token_times.append(now)
                    emitted += 1
                    nxt = tok
                    if (len(slot.emitted) >= slot.fut.max_new
                            or (self.eos_id is not None
                                and tok == self.eos_id)):
                        self._retire(i)
                        break
                if slot.pos >= self.max_len:
                    self._retire(i)
                    break
                if j + 1 >= window:
                    slot.next_token = nxt
                    break
                if nxt != int(w[i, j + 1]):
                    # window diverged from the committed stream: rows past
                    # slot.pos hold speculative garbage the next round
                    # rewrites before any query can attend it
                    if dfill[i, j + 1]:
                        judged += 1    # proposal reached a verdict: rejected
                    slot.next_token = nxt
                    break
                if dfill[i, j + 1]:
                    judged += 1
                    accepted += 1      # draft proposal confirmed
            if self._slots[i] is not None:
                self._maybe_harvest(i)
        # only proposals the target actually RULED ON count: positions a
        # retire/length break left unverified would deflate the acceptance
        # rate a perfect draft earns (drafted == accepted by construction)
        self.health.record_spec_round(judged, accepted)
        self._count_step(sp, a, emitted, prompt, passes=window)
        sp.lap("decode_commit")

    def _count_step(self, sp, a, emitted, prompt, ahead=0, passes=1):
        """The dispatched step's counts, for the health report and its
        span. Rows whose ``temp`` is above 0 sample: any at all and the
        step's program took the sampler's branch (sampling.py, rule 3).
        ``ahead``: 1 where the step before was still unread. ``rows``: the
        prefix of the cache's rows its attention covered, as the program
        picked it from the ``pos`` it was fed (:func:`.blocks.filled_rung`;
        pass j of a speculative window's ``passes`` stands j deeper),
        summed over the passes; 0 for a model without such a cache.
        ``ring_rows``, of an architecture with a ring only: the same over
        the ring's own ladder, its whole depth once the deepest slot has
        wrapped it (a ring refuses speculation: one pass)."""
        sampled = int((a["temp"] > 0).sum())
        top = int(a["pos"].max())
        rows = sum(rows_covered(self._ladder, top + j)
                   for j in range(passes))
        sp.set(sampled=sampled, ahead=ahead, rows=rows)
        self.health.record_decode_step(emitted, prompt, sampled, ahead,
                                       rows, passes * self._rows)
        if self._ring_rows:
            ring = rows_covered(self._ring_ladder, top)
            sp.set(ring_rows=ring)
            self.health.record_ring_step(
                ring, self._ring_rows,
                int((a["pos"][a["live"]] >= self._ring_rows).sum()))

    def _retire(self, i):
        slot = self._slots[i]
        self._slots[i] = None
        slot.steps[2] = self._steps
        self._settle(i, slot)

    def _settle(self, i, slot):
        """Hand a request that left slot ``i`` its tokens."""
        self.health.record_retire()
        eos = self.eos_id is not None and slot.emitted[-1:] == [self.eos_id]
        self._request_done(slot.fut, "eos" if eos else "done", i, slot)
        slot.fut.fulfill(list(slot.emitted))

    def _request_done(self, fut, outcome, i=-1, slot=None):
        """The ONE record of a request's life, where the loop ends it
        (``slot`` ``None``: it was never seated), and the operator's
        latency counters from the same stamps. Once a request: the step
        stamps nothing for it that it did not stamp before.

        The record is an async pair ``decode_request`` keyed by the
        request's id (docs/observability.md "Span catalogue" has the
        arguments): its own track in Perfetto, in the flight recorder's
        ring, and no complete span, so no reader of the loop thread's
        spans meets one that lasts seconds. Times are microseconds from
        ``submit``, the submission on ``time.perf_counter()``: the clock
        of ``token_times``."""
        if fut.outcome is not None:
            return
        fut.outcome = outcome
        t0 = fut.t_submit
        token_us = [int(round((t - t0) * 1e6)) for t in fut.token_times]
        seat_us = -1 if slot is None else int(round((slot.t_seat - t0) * 1e6))
        if token_us:
            self.health.record_request_latency(seat_us, token_us)
        if not _obs.active():
            return
        steps, prefill, hit = [0, 0, 0], [0, 0], 0
        if slot is not None:
            first, done, last = slot.steps
            # a request that left by the schedule emitted a token in every
            # step from its prompt's last position to its last
            steps = [first if first <= self._steps else 0,
                     done or (last - slot.sent + 1 if last and slot.sent
                              else 0), last]
            prefill, hit = list(slot.prefill), slot.prefix_hit
        _obs.async_complete(
            "decode_request", time.perf_counter() - t0, id=fut.rid,
            req=fut.rid, slot=i, prompt_len=len(fut.prompt),
            emitted=len(token_us), outcome=outcome, submit=t0,
            seat_us=seat_us, token_us=token_us, steps=steps,
            prefill=prefill, prefix_hit=hit)

    # ------------------------------------------------------------------
    def counter_totals(self):
        """Host copies of the architecture's device counters, cumulative
        since the loop was built (``{}`` for an architecture without, or
        once a dead loop's state is gone). Safe from any thread: the lock
        keeps the step from donating the state while it is read, which
        holds the loop up for a step at the most."""
        if not self._counter_names:
            return {}
        try:
            with self._state_lock:
                return {k: np.asarray(self._state[k])
                        for k in self._counter_names}
        except Exception as e:     # the state died with the loop
            logging.warning("%s: counters unreadable (%r)", self.name, e)
            return {}

    def _report_counters(self):
        """:class:`ServingHealth`'s source: what the device counted since
        the last report goes into the health counters, as the
        architecture maps it."""
        with self._counter_lock:
            counts = self.counter_totals()
            if counts:
                self._arch.record_counters(self.health, counts,
                                           self._counter_base)
                self._counter_base = counts

    def state_arrays(self):
        """``{array: [layers, rows, width, dtype, bytes]}`` of the slots'
        state as allocated (the rows in whole tiles; the bytes of the whole
        array, all slots): what the state costs on the device, by kind."""
        return {k: list(v) for k, v in self._state_arrays.items()}

    def program_scopes(self):
        """``{instruction name: scope}`` of the step program (the verify
        program of a speculative loop): for every instruction of the
        compiled executable outside its fusions, the ``jax.named_scope``
        path it was traced under (``layer/moe/experts``), from the
        executable's own metadata. A device trace names the operations it
        timed by these instruction names."""
        import re
        from ..memcheck import _COMP_RE, _OPNAME_RE
        name_re = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
        comp = self._verify_c if self.spec_k else self._step_c
        scopes, fused = {}, False
        for line in comp.as_text().splitlines():
            head = _COMP_RE.match(line)
            if head:
                fused = head.group("name").startswith("fused_")
                continue
            name = None if fused else name_re.match(line)
            op = name and _OPNAME_RE.search(line)
            if not op:
                continue
            path = [c for c in op.group(1).split("/")[:-1]
                    if c and "(" not in c]
            if path:
                scopes[name.group(1)] = "/".join(path)
        return scopes

    def _program_span(self):
        t0 = time.perf_counter()
        try:
            scopes = self.program_scopes()
        except Exception as e:   # an executable that cannot surface HLO
            logging.warning("%s: no scope table (%r)", self.name, e)
            return
        # the module a prefill pass runs as, by name, where the loop has
        # one (no scope table of its own until a reader wants one)
        pass_args = {} if self._prefill_c is None else {
            "prefill_program": "jit_prefill_fn", "prefill_chunk": self._chunk}
        _obs.complete("loop_program", time.perf_counter() - t0,
                      program="jit_verify_fn" if self.spec_k
                      else "jit_decode_fn", scopes=scopes,
                      state=self.state_arrays(), **pass_args)

    # ------------------------------------------------------------------
    def memory_report(self, top=8):
        """Static memory profile of EVERY compiled decode program
        (docs/static_analysis.md "Memory lints"): ``{program_name:
        MemoryReport}`` from the already-compiled executables — donated
        state alias accounting included, and the draft+target pair (plus
        the prefix programs) all present so the resident-set lint prices
        their co-residency. An executable that cannot report memory is
        skipped with a warning (mirrors
        ``ServingEngine.memory_report``)."""
        from .. import memcheck as _mc
        reports = {}
        for name, (comp, structs, donate) in sorted(
                self._programs.items()):
            try:
                reports[name] = _mc.analyze_compiled(
                    comp, name, args=structs, donate_argnums=donate,
                    top=top)
            except Exception as e:
                logging.warning(
                    "DecodeLoop: %s cannot report memory (%s) — skipped "
                    "from the memory audit", name, e)
        return reports

    def comms_report(self):
        """Static collective inventory of every compiled decode program
        (``{program_name: CommsReport}``) — the per-token partitioning
        bill of a sharded loop; zero collectives single-chip. Mirrors
        :meth:`ServingEngine.comms_report` (skip-with-warning on
        executables that cannot surface HLO text)."""
        from .. import commscheck as _cc
        reports = {}
        for name, (comp, _structs, _donate) in sorted(
                self._programs.items()):
            try:
                reports[name] = _cc.analyze_compiled(comp, name,
                                                     mesh=self._mesh)
            except Exception as e:
                logging.warning(
                    "DecodeLoop: %s cannot report its collectives (%s) — "
                    "skipped from the comms audit", name, e)
        return reports

    def check(self, const_bytes=None, memory=False, budget=None,
              comms=False, min_eff=0.0):
        """Static-analyze the registered decode programs; returns
        findings (the CI serving gate asserts none — docs/serving.md).
        ``memory=True`` adds the memory lints over every compiled body
        plus the ``resident-set`` lint over the whole set — with
        speculative decoding on, that is the draft+target co-residency
        audit; ``comms=True`` the communication lints (``min_eff``
        defaults to 0 like :meth:`ServingEngine.check` — the efficiency
        floor is a training-scale gate)."""
        from .. import tracecheck as _tc
        findings = _tc.check_registered(const_bytes=const_bytes,
                                        match=self.name + "/")
        if memory:
            from .. import memcheck as _mc
            reports = self.memory_report()
            for rep in reports.values():
                findings += _mc.lint_report(rep, budget=budget)
            findings += _mc.lint_resident_set(
                reports.values(), "%s/resident-set" % self.name,
                budget=budget)
        if comms:
            from .. import commscheck as _cc
            for rep in self.comms_report().values():
                findings += _cc.lint_report(rep, min_eff=min_eff)
        return findings
