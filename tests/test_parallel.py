"""Parallelism tests: mesh helpers, blockwise/ring/Ulysses attention over the
virtual device mesh (the long-context story, SURVEY.md §5)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel import make_mesh, data_parallel_mesh, grad_sync
from mxnet_tpu.parallel.ring import (blockwise_attention, ring_attention,
                                     ulysses_attention)


def _naive_attention(q, k, v, causal=False):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _qkv(b=2, h=2, s=32, d=8, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
    return mk(), mk(), mk()


def test_blockwise_attention_matches_naive():
    q, k, v = _qkv()
    out = blockwise_attention(q, k, v, block_size=8)
    ref = _naive_attention(q, k, v)
    assert np.allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_blockwise_attention_causal():
    q, k, v = _qkv()
    out = blockwise_attention(q, k, v, block_size=8, causal=True)
    ref = _naive_attention(q, k, v, causal=True)
    assert np.allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_blockwise_attention_ragged():
    q, k, v = _qkv(s=30)  # not a multiple of the block size
    out = blockwise_attention(q, k, v, block_size=8)
    ref = _naive_attention(q, k, v)
    assert np.allclose(out, ref, rtol=1e-4, atol=1e-5)


def _seq_mesh(n):
    devs = jax.devices()[:n]
    return jax.sharding.Mesh(np.array(devs), ("seq",))


def test_ring_attention_matches_full():
    """Ring attention over a 4-device 'seq' axis == full attention."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    n = 4
    mesh = _seq_mesh(n)
    q, k, v = _qkv(s=32)
    ref = _naive_attention(q, k, v)

    fn = shard_map(lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
                   mesh=mesh,
                   in_specs=(P(None, None, "seq", None),) * 3,
                   out_specs=P(None, None, "seq", None))
    out = fn(q, k, v)
    assert np.allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_ring_attention_causal():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    n = 4
    mesh = _seq_mesh(n)
    q, k, v = _qkv(s=32, seed=3)
    ref = _naive_attention(q, k, v, causal=True)
    fn = shard_map(lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                                  causal=True),
                   mesh=mesh,
                   in_specs=(P(None, None, "seq", None),) * 3,
                   out_specs=P(None, None, "seq", None))
    out = fn(q, k, v)
    assert np.allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_ulysses_attention_matches_full():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    n = 2
    mesh = _seq_mesh(n)
    q, k, v = _qkv(b=1, h=4, s=16, seed=5)
    ref = _naive_attention(q, k, v)
    fn = shard_map(lambda q, k, v: ulysses_attention(q, k, v,
                                                     axis_name="seq"),
                   mesh=mesh,
                   in_specs=(P(None, None, "seq", None),) * 3,
                   out_specs=P(None, None, "seq", None))
    out = fn(q, k, v)
    assert np.allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_make_mesh_and_grad_sync():
    mesh = make_mesh({"data": 4, "model": 2})
    assert mesh.shape == {"data": 4, "model": 2}
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    dp = data_parallel_mesh(4)

    def f(g):
        return grad_sync({"w": g}, "data")["w"]

    fn = shard_map(f, mesh=dp, in_specs=P("data"), out_specs=P("data"))
    g = jnp.arange(8.0)
    out = fn(g)
    # psum over 4 shards of 2: every element = sum of its shard-position peers
    expect = np.tile(np.array([0 + 2 + 4 + 6, 1 + 3 + 5 + 7]), 4)
    assert np.allclose(out, expect)


def test_mesh_size_mismatch_error():
    import mxnet_tpu as mx
    with pytest.raises(mx.MXNetError):
        make_mesh({"data": 16})  # more than available devices
