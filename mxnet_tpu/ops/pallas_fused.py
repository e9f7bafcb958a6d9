"""Pallas TPU kernel: matmul with BatchNorm-statistics epilogue.

The perf story (docs/perf.md): ResNet training on v5e is HBM-bound, and the
BN batch-statistics pass is the largest non-essential traffic source — the
stats reduction re-reads the full conv output that the conv just wrote. A
1x1 convolution in NHWC is exactly a matmul, so this kernel computes

    y = x @ w        (MXU, f32 accumulation)
    s1 = sum(y)      per output channel   (VPU, from the f32 accumulator)
    s2 = sum(y*y)    per output channel

in ONE pass: the stats come for free out of VMEM while the tile is still
resident, eliminating the separate full-tensor read. The executor's fusion
pass (executor.py) rewrites Convolution(1x1)->BatchNorm pairs onto this
kernel at trace time; BatchNorm then consumes (s1, s2, count) directly
(ops/nn.py fused_stats path).

Replaces the role of the reference's cuDNN fused conv+BN epilogues
(ref: src/operator/cudnn_batch_norm-inl.h + convolution autotuning); the
backward is plain XLA matmuls with the stats cotangents folded into the
output cotangent (dy_eff = dy + ds1 + 2*y*ds2), which XLA fuses into the
matmul operand reads.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


def _tile_m(m, cap=1024):
    """Largest divisor of m that is <= cap and sublane-aligned (mult of 16).
    Returns None when m has no aligned divisor (caller skips fusion)."""
    best = None
    for t in range(16, min(m, cap) + 1, 16):
        if m % t == 0:
            best = t
    return best


def _acc_dtype(dt):
    """Stats/accumulator dtype: f32 except for f64 inputs (numeric tests)."""
    return jnp.float64 if dt == jnp.float64 else jnp.float32


def _kernel(x_ref, w_ref, y_ref, ps_ref):
    acc = jnp.dot(x_ref[...], w_ref[...],
                  preferred_element_type=ps_ref.dtype)
    y_ref[...] = acc.astype(y_ref.dtype)
    ps_ref[0, 0, :] = jnp.sum(acc, axis=0)
    ps_ref[0, 1, :] = jnp.sum(acc * acc, axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _matmul_stats_raw(x, w, interpret=False):
    """x (M, K) @ w (K, N) -> y (M, N), s1 (N,), s2 (N,) f32."""
    m, k = x.shape
    n = w.shape[1]
    acc_dt = _acc_dtype(x.dtype)
    tm = _tile_m(m)
    tn = n if n <= 256 else 256
    if tm is None or n % tn or n % 128:
        # shape outside the kernel's envelope: plain XLA, and said once per
        # shape (trace time; the warnings filter dedups) so a run under
        # the knob cannot pass for fused where it is not
        warnings.warn(
            "MXTPU_FUSE_CONV_BN: the conv1x1+BN-stats kernel does not cover "
            "a (%d, %d) x (%d, %d) product (rows need a multiple-of-16 "
            "divisor <= 1024, channels a multiple of 128); this layer runs "
            "plain XLA" % (m, k, k, n))
        yacc = jnp.dot(x, w, preferred_element_type=acc_dt)
        return (yacc.astype(x.dtype), jnp.sum(yacc, axis=0),
                jnp.sum(yacc * yacc, axis=0))
    grid = (m // tm, n // tn)
    y, ps = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tm, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((k, tn), lambda i, j: (0, j))],
        out_specs=[pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
                   pl.BlockSpec((1, 2, tn), lambda i, j: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((m, n), x.dtype),
                   jax.ShapeDtypeStruct((grid[0], 2, n), acc_dt)],
        interpret=interpret,
    )(x, w)
    return y, ps[:, 0, :].sum(axis=0), ps[:, 1, :].sum(axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def matmul_stats(x, w, interpret=False):
    """Differentiable fused matmul+stats; cotangents on the stats flow back
    into x and w (the BN batch statistics are functions of the data)."""
    return _matmul_stats_raw(x, w, interpret)


def _mm_fwd(x, w, interpret):
    out = _matmul_stats_raw(x, w, interpret)
    return out, (x, w, out[0])


def _mm_bwd(interpret, res, cots):
    x, w, y = res
    dy, ds1, ds2 = cots
    # d/dy [ <dy,y> + <ds1, sum(y)> + <ds2, sum(y^2)> ]
    acc_dt = ds1.dtype
    dy_eff = (dy.astype(acc_dt) + ds1[None, :]
              + 2.0 * y.astype(acc_dt) * ds2[None, :]).astype(x.dtype)
    dx = jnp.dot(dy_eff, w.T)
    dw = jnp.dot(x.T, dy_eff)
    return dx, dw


matmul_stats.defvjp(_mm_fwd, _mm_bwd)


# ---------------------------------------------------------------------------
# fusion-pass predicates and driver (used by executor._build_graph_runner)
# ---------------------------------------------------------------------------
def conv1x1_fusable(conv_attrs):
    """True when a Convolution node is a pure NHWC 1x1 matmul this kernel
    covers: kernel (1,1), stride 1, no pad/dilation/groups/bias."""
    from ..base import attr_bool, attr_int, attr_tuple, attr_str
    try:
        if attr_str(conv_attrs.get("layout", ""), "") != "NHWC":
            return False
        if attr_tuple(conv_attrs["kernel"]) != (1, 1):
            return False
        if attr_tuple(conv_attrs.get("stride", (1, 1)), (1, 1)) != (1, 1):
            return False
        if attr_tuple(conv_attrs.get("pad", (0, 0)), (0, 0)) != (0, 0):
            return False
        if attr_tuple(conv_attrs.get("dilate", (1, 1)), (1, 1)) != (1, 1):
            return False
        if attr_int(conv_attrs.get("num_group", 1), 1) != 1:
            return False
        if not attr_bool(conv_attrs.get("no_bias", False), False):
            return False
    except Exception:
        return False
    return True


def bn_fusable(bn_attrs):
    """BN can consume producer stats: channel-last axis, batch stats."""
    from ..base import attr_bool, attr_int
    if attr_bool(bn_attrs.get("use_global_stats", False), False):
        return False
    return attr_int(bn_attrs.get("axis", 1), 1) in (-1, 3)


def apply_conv1x1_stats(x, w, interpret=False):
    """NHWC activation x (..., C), OIHW weight w (F, C, 1, 1) ->
    (y (..., F), (s1, s2, count))."""
    k = x.shape[-1]
    f = w.shape[0]
    x2 = x.reshape(-1, k)
    w2 = w.reshape(f, k).T
    y2, s1, s2 = matmul_stats(x2, w2, interpret)
    return y2.reshape(x.shape[:-1] + (f,)), (s1, s2, float(x2.shape[0]))
