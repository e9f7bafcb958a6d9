"""Symbol-level attention ops: the long-context flagship surface.

The reference's long-context stories are bucketing, fused RNN kernels and
layer-per-device model parallelism (SURVEY.md §5; the superseded pattern is
example/model-parallel-lstm/lstm.py:48-112). This module is the TPU-native
replacement: a MultiHeadAttention operator whose core is blockwise
(flash-style) attention, with optional sequence/context parallelism over
the mesh 'seq' axis — ring attention (K/V shards rotate over ICI neighbor
links via ppermute) or Ulysses (all-to-all head sharding). The parallel
modes activate under an ambient mesh (parallel.mesh.MeshScope / TrainStep
mesh) that has a 'seq' axis; single-chip execution uses the same blockwise
core, so numerics match across modes (tests/test_attention.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..base import attr_bool, attr_int, attr_float, attr_str, MXNetError
from .registry import OpDef, register_def


def _mha_attrs(attrs):
    num_heads = attr_int(attrs["num_heads"])
    causal = attr_bool(attrs.get("causal", False), False)
    no_bias = attr_bool(attrs.get("no_bias", False), False)
    seq_par = attr_str(attrs.get("seq_parallel", ""), "")
    block = attr_int(attrs.get("block_size", 0), 0)
    if seq_par not in ("", "ring", "ulysses"):
        raise MXNetError("MultiHeadAttention: seq_parallel must be "
                         "'', 'ring', or 'ulysses'")
    return num_heads, causal, no_bias, seq_par, block


def _mha_inputs(attrs):
    no_bias = attr_bool(attrs.get("no_bias", False), False)
    if no_bias:
        return ["data", "qkv_weight", "out_weight"]
    return ["data", "qkv_weight", "qkv_bias", "out_weight", "out_bias"]


def _mha_infer(attrs, in_shapes):
    num_heads, _, no_bias, _, _ = _mha_attrs(attrs)
    data = in_shapes[0]
    if data is None:
        raise MXNetError("MultiHeadAttention: data shape required")
    if len(data) != 3:
        raise MXNetError("MultiHeadAttention: data must be "
                         "(batch, seq, embed), got %s" % (data,))
    e = data[2]
    if e % num_heads:
        raise MXNetError("MultiHeadAttention: embed %d %% num_heads %d != 0"
                         % (e, num_heads))
    shapes = [tuple(data), (3 * e, e)]
    if not no_bias:
        shapes.append((3 * e,))
    shapes.append((e, e))
    if not no_bias:
        shapes.append((e,))
    return shapes, [tuple(data)], []


def _seq_mesh():
    """Ambient mesh carrying a 'seq' axis, if any."""
    from ..parallel import mesh as _mesh
    m = _mesh.current_mesh()
    if m is not None and _mesh.AXIS_SEQ in m.axis_names:
        return m
    return None


def _attend(q, k, v, causal, block, seq_par):
    """(b, h, s, d) -> (b, h, s, d); dispatches the parallel mode."""
    from ..parallel import ring as _ring
    block = block or None
    if seq_par:
        mesh = _seq_mesh()
        if mesh is None:
            raise MXNetError(
                "MultiHeadAttention(seq_parallel=%r) needs an ambient mesh "
                "with a 'seq' axis (parallel.mesh.MeshScope / TrainStep "
                "mesh)" % seq_par)
        from jax.sharding import PartitionSpec as P
        from ..parallel.mesh import check_axis_divides
        b, h, s, _ = q.shape
        # divisibility prechecks that NAME the failing axis (the shard_map
        # partitioner's complaint would not): seq dim over 'seq', batch
        # over 'data' when composed, heads over 'seq' for Ulysses' head
        # all-to-all
        check_axis_divides(mesh, "seq", s,
                           "MultiHeadAttention: sequence dim")
        check_axis_divides(mesh, "data", b, "MultiHeadAttention: batch dim")
        if seq_par == "ulysses":
            check_axis_divides(
                mesh, "seq", h,
                "MultiHeadAttention(seq_parallel='ulysses'): num_heads")
        # batch stays sharded over 'data' when the mesh carries both axes
        # (dp x sp); heads/dim replicated — ring/Ulysses communicate over
        # 'seq' only
        bax = "data" if "data" in mesh.axis_names else None
        spec = P(bax, None, "seq", None)
        if seq_par == "ring":
            if block:
                # ring shards K/V across devices; there is no intra-shard
                # blocking to honor — refuse rather than silently ignore
                # the user's memory bound
                raise MXNetError(
                    "MultiHeadAttention: block_size is not supported with "
                    "seq_parallel='ring' (K/V are already sharded per "
                    "device); unset block_size or use 'ulysses'")
            fn = functools.partial(_ring.ring_attention, axis_name="seq",
                                   causal=causal)
        else:
            fn = functools.partial(
                _ring.ulysses_attention, axis_name="seq",
                attn_fn=functools.partial(_ring.blockwise_attention,
                                          block_size=block, causal=causal))
        return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec)(q, k, v)
    return _ring.blockwise_attention(q, k, v, block_size=block,
                                     causal=causal)


def _mha(op_ctx, attrs, inputs, aux):
    num_heads, causal, no_bias, seq_par, block = _mha_attrs(attrs)
    if no_bias:
        x, wqkv, wout = inputs
        bqkv = bout = None
    else:
        x, wqkv, bqkv, wout, bout = inputs
    b, s, e = x.shape
    d = e // num_heads
    qkv = jnp.einsum("bse,fe->bsf", x, wqkv)
    if bqkv is not None:
        qkv = qkv + bqkv
    qkv = qkv.reshape(b, s, 3, num_heads, d)
    q, k, v = (jnp.transpose(qkv[:, :, i], (0, 2, 1, 3)) for i in range(3))
    out = _attend(q, k, v, causal, block, seq_par)
    out = jnp.transpose(out, (0, 2, 1, 3)).reshape(b, s, e)
    out = jnp.einsum("bse,fe->bsf", out, wout)
    if bout is not None:
        out = out + bout
    return (out,)


_MHA = register_def(OpDef(
    "MultiHeadAttention", _mha,
    inputs=("data", "qkv_weight", "qkv_bias", "out_weight", "out_bias"),
    infer_shape=_mha_infer))
_MHA.list_inputs = _mha_inputs


# ---------------------------------------------------------------------------
# LayerNorm (transformer building block; API matches the post-0.9 reference
# op of the same name)
# ---------------------------------------------------------------------------
def _ln_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        raise MXNetError("LayerNorm: data shape required")
    axis = attr_int(attrs.get("axis", -1), -1) % len(data)
    c = data[axis]
    return [tuple(data), (c,), (c,)], [tuple(data)], []


def _layer_norm(op_ctx, attrs, inputs, aux):
    eps = attr_float(attrs.get("eps", 1e-5), 1e-5)
    x, gamma, beta = inputs
    axis = attr_int(attrs.get("axis", -1), -1) % x.ndim
    mean = jnp.mean(x, axis=axis, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=axis, keepdims=True)
    xhat = (x - mean) * jax.lax.rsqrt(var + eps)
    bshape = tuple(-1 if i == axis else 1 for i in range(x.ndim))
    return (xhat * gamma.reshape(bshape) + beta.reshape(bshape),)


register_def(OpDef("LayerNorm", _layer_norm,
                   inputs=("data", "gamma", "beta"),
                   infer_shape=_ln_infer))
