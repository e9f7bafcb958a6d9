"""In-graph token sampling for the decode loop (docs/serving.md
"Sampling").

Three design rules make every decode feature on top of this composable:

1. **Stateless per-(seed, position) randomness.** The uniform driving a
   slot's sample at position ``p`` is ``uniform(fold_in(PRNGKey(seed),
   p))`` — a pure function of the slot's seed and the absolute cache
   position, independent of which co-riders share the batch, how the
   sequence was scheduled, or whether its prefix was implanted from the
   prefix cache. Same seed => same token stream, always
   (tests/test_decode_stack.py).

2. **Inverse-CDF sampling.** The token at a position is the
   deterministic image of that position's uniform under the (sorted,
   temperature-scaled, top-k/top-p-filtered) distribution. Because the
   sample is a function of (prefix, u) only, speculative decoding needs
   no stochastic accept/reject correction: the verify pass recomputes
   the SAME function and the emitted stream is token-identical to
   target-only decoding (docs/serving.md "Speculative decoding").

3. **A step pays for sampling only if a row samples.** The sampler's
   value chain (scale, sort, gather, softmax, the top-k/top-p mask, two
   cumulative sums, the inverse CDF) stands in one branch of a
   ``lax.cond`` on ``any(temp > 0)``, a scalar the program computes from
   its own input; the other branch hands the ``argmax`` through. Still
   ONE compiled program with the same arguments: no second executable,
   no recompile when a sampled request joins, no knob. On the chip the
   ``cond`` is an HLO ``conditional`` and one branch runs: the sort and
   the gather over slots x vocabulary were 48% of a 64-slot Kimi-K2 step
   whose requests were all greedy (PERF.md, PR 30). THE CLIFF: one
   sampled row costs every row of the step the sort, as every step did
   before; what a row draws does not depend on which branch its
   co-riders force (tests/test_sampling_branch.py holds the branch
   against the straight-line body, bitwise).

``sample_rows`` is the ONE row-wise sampler shared by the single-token
decode body and the multi-position verify body, so a position sampled
through either body draws the identical token, and both get rule 3 from
this one place.

Per-row knobs (all traced, so the decode body stays one program):
``temp`` (0 = greedy argmax, bitwise the pre-sampling decode path),
``top_k`` (0 = off), ``top_p`` (1 = off).
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError


def validate_sampling(temperature, top_k, top_p, who="generate"):
    """Host-side knob validation (the in-graph sampler clamps nothing —
    a nonsense knob must fail its caller, not silently skew a stream)."""
    t, k, p = float(temperature), int(top_k), float(top_p)
    if not np.isfinite(t) or t < 0.0:
        raise MXNetError("%s: temperature must be finite and >= 0, got %r"
                         % (who, temperature))
    if k < 0:
        raise MXNetError("%s: top_k must be >= 0 (0 disables), got %r"
                         % (who, top_k))
    if not (0.0 < p <= 1.0):
        raise MXNetError("%s: top_p must be in (0, 1], got %r"
                         % (who, top_p))
    return t, k, p


def position_uniforms(seeds, pos):
    """The per-slot RNG stream: u[i] = uniform(fold_in(PRNGKey(seeds[i]),
    pos[i])). Traced (in-graph); both decode bodies call this, so a
    (seed, position) pair maps to ONE uniform everywhere."""
    import jax

    def one(seed, p):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), p)
        return jax.random.uniform(key, (), np.float32)

    return jax.vmap(one)(seeds, pos)


def sample_rows(logits, u, temp, top_k, top_p):
    """Sample one token per row from ``logits`` (n, vocab) via inverse
    CDF on ``u`` (n,). Rows with ``temp == 0`` return ``argmax(logits)``
    — bitwise the greedy path (no scaling, no sort in the value chain).

    Filtering is the standard order: temperature-scale, sort descending,
    keep the top-k ranks, keep the smallest prefix whose EXCLUSIVE
    cumulative probability is < top_p (so the head token always
    survives), renormalize implicitly by sampling u * kept_mass.

    A call whose rows are all greedy runs the argmax alone (rule 3 of
    the module docstring): everything else stands in ``sampler``, the
    true branch of the ``cond`` on ``any(temp > 0)``.
    """
    import jax
    import jax.numpy as jnp

    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampler():
        safe_t = jnp.where(temp > 0, temp, jnp.float32(1.0))
        scaled = logits / safe_t[:, None]
        order = jnp.argsort(-scaled, axis=-1)      # stable: ties by index
        probs = jax.nn.softmax(
            jnp.take_along_axis(scaled, order, axis=-1), axis=-1)

        ranks = jnp.arange(vocab, dtype=jnp.int32)[None, :]
        k_eff = jnp.where(top_k > 0, top_k, jnp.int32(vocab))[:, None]
        cum = jnp.cumsum(probs, axis=-1)
        keep = (ranks < k_eff) & ((cum - probs) < top_p[:, None])
        kept = jnp.where(keep, probs, jnp.float32(0.0))

        cdf = jnp.cumsum(kept, axis=-1)
        target = u[:, None] * cdf[:, -1:]
        hit = cdf > target
        # float-edge guard (u ~ 1.0): if no strict crossing, take the last
        # kept rank — ``keep`` is a prefix mask, so that is count-1
        rank = jnp.where(jnp.any(hit, axis=-1),
                         jnp.argmax(hit, axis=-1),
                         jnp.sum(keep.astype(jnp.int32), axis=-1) - 1)
        sampled = jnp.take_along_axis(order, rank[:, None],
                                      axis=-1)[:, 0].astype(jnp.int32)
        return jnp.where(temp > 0, sampled, greedy)

    return jax.lax.cond(jnp.any(temp > 0), sampler, lambda: greedy)
