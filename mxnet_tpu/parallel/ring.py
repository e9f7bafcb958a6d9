"""Ring attention / sequence-context parallelism over the ICI mesh.

The reference's only long-context stories are bucketing, fused RNN kernels and
layer-per-device model parallelism (SURVEY.md §5). This module supplies the
genuinely-new TPU pieces: blockwise ring attention (K/V rotate around the
'seq' mesh axis via ppermute while queries stay resident) and Ulysses-style
head-sharded attention (all-to-all). Round-1 scope: numerically-stable
blockwise attention core + single-host ring step; full multichip wiring lands
with the transformer/LSTM flagship.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _block_attn(q, k, v, m_prev, l_prev, acc, scale):
    """One blockwise-softmax accumulation step (log-sum-exp streaming)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[..., None])
    l_corr = l_prev * jnp.exp(m_prev - m_new)
    l_new = l_corr + jnp.sum(p, axis=-1)
    acc = acc * jnp.exp(m_prev - m_new)[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, acc


def blockwise_attention(q, k, v, block_size=None, causal=False):
    """Memory-efficient attention via streaming softmax over K/V blocks.

    q,k,v: (batch, heads, seq, dim). Equivalent to softmax(qk^T/sqrt(d))v but
    never materializes the full (seq, seq) matrix — the single-chip half of
    ring attention.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = 1.0 / (d ** 0.5)
    if block_size is None:
        block_size = min(512, sk)
    nblocks = (sk + block_size - 1) // block_size
    pad = nblocks * block_size - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, h, nblocks, block_size, d)
    vb = v.reshape(b, h, nblocks, block_size, d)

    def body(carry, inputs):
        m, l, acc = carry
        (kblk, vblk, blk_idx) = inputs
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kblk) * scale
        # mask padding and causal positions
        kpos = blk_idx * block_size + jnp.arange(block_size)
        pad_mask = kpos < sk
        mask = pad_mask[None, None, None, :]
        if causal:
            qpos = jnp.arange(sq)
            mask = mask & (kpos[None, :] <= qpos[:, None])[None, None]
        s = jnp.where(mask, s, -jnp.inf)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.where(mask, jnp.exp(s - m_safe[..., None]), 0.0)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vblk)
        return (m_new, l_new, acc_new), None

    # carries derived from q keep any shard_map varying manual axes
    m0 = jnp.full_like(q[..., 0], -jnp.inf)
    l0 = jnp.zeros_like(q[..., 0])
    acc0 = jnp.zeros_like(q)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (jnp.moveaxis(kb, 2, 0), jnp.moveaxis(vb, 2, 0), jnp.arange(nblocks)))
    return acc / jnp.maximum(l, 1e-20)[..., None]


def ring_attention(q, k, v, axis_name="seq", causal=False):
    """Ring attention inside shard_map over the 'seq' mesh axis: each device
    holds a sequence shard of q/k/v; K/V shards rotate via ppermute while the
    local q accumulates blockwise-softmax statistics. Communication rides ICI
    neighbor links — bandwidth-optimal for long context.
    """
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    scale = 1.0 / (d ** 0.5)
    sk = k.shape[2]

    def step(carry, i):
        m, l, acc, kr, vr = carry
        src_idx = (my - i) % n  # which shard we currently hold
        s = jnp.einsum("bhqd,bhkd->bhqk", q, kr) * scale
        if causal:
            qpos = my * sq + jnp.arange(sq)
            kpos = src_idx * sk + jnp.arange(sk)
            mask = (kpos[None, :] <= qpos[:, None])[None, None]
            s = jnp.where(mask, s, -jnp.inf)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vr)
        # rotate K/V to the next device around the ring
        perm = [(j, (j + 1) % n) for j in range(n)]
        kr = jax.lax.ppermute(kr, axis_name, perm)
        vr = jax.lax.ppermute(vr, axis_name, perm)
        return (m_new, l_new, acc_new, kr, vr), None

    # derive carries from q so they inherit the 'seq' varying manual axis
    # (shard_map requires scan carry in/out types to match)
    m0 = jnp.full_like(q[..., 0], -jnp.inf)
    l0 = jnp.zeros_like(q[..., 0])
    acc0 = jnp.zeros_like(q)
    (m, l, acc, _, _), _ = jax.lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(n))
    return acc / jnp.maximum(l, 1e-20)[..., None]


def ulysses_attention(q, k, v, axis_name="seq", attn_fn=None):
    """Ulysses-style sequence parallelism: all-to-all converts sequence
    sharding into head sharding, full-sequence attention runs locally per
    head group, then the layout is restored."""
    n = jax.lax.axis_size(axis_name)

    def a2a(x, split_axis, concat_axis):
        return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)

    # (b, h, s/n, d) -> (b, h/n, s, d)
    qh = a2a(q, 1, 2)
    kh = a2a(k, 1, 2)
    vh = a2a(v, 1, 2)
    if attn_fn is None:
        attn_fn = functools.partial(blockwise_attention)
    out = attn_fn(qh, kh, vh)
    # back to sequence sharding
    return a2a(out, 2, 1)
