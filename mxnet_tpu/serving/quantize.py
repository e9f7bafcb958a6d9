"""Weight-only quantization for the serving tier (docs/serving.md
"Quantized weights").

Modes (``MXTPU_SERVE_QUANT`` / the ``quantize=`` ctor arg):

- ``"none"``  — f32 weights as trained (default).
- ``"bf16"``  — every float weight stored bf16, upcast in-graph. 2×
  HBM win, no scales.
- ``"int8"``  — per-channel (axis 0) symmetric int8 for every float
  weight with ndim >= 2; scale = max|w| / 127 per output channel.
  1-D params (biases, LN gains) stay f32 — they are a rounding error
  of the footprint and disproportionately quality-sensitive. ~4× HBM
  win on the matmul weights.

A quantized tree swaps each eligible leaf for ``{"q": int8, "s": f32
(out,)}``; ``dequant_leaf`` runs in-graph so the engine's forward is
still ONE program and memcheck sees int8 resident bytes. The scale
vector lies along axis 0 — the same axis ``auto_spec`` shards first —
so a sharded engine holds 1/N of the *quantized* bytes per chip and
the scale shards right beside its weight.

Quality is gated, not assumed: ``quality_report`` runs a probe batch
through the f32 and quantized forwards and reports top-1 agreement;
``check_quality`` raises ``MXNetError`` below the floor
(``MXTPU_SERVE_QUANT_MIN_AGREE``, default 0.98).
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError, env_float

QUANT_MODES = ("none", "bf16", "int8")
_INT8_LEAF_KEYS = frozenset(("q", "s"))


def resolve_mode(mode):
    m = str(mode or "none").lower()
    if m not in QUANT_MODES:
        raise MXNetError("quantize mode must be one of %s, got %r"
                         % (QUANT_MODES, mode))
    return m


def is_quantized_leaf(leaf):
    """True for an int8 ``{"q","s"}`` leaf (treated atomically in trees)."""
    return isinstance(leaf, dict) and set(leaf) == _INT8_LEAF_KEYS


def _eligible(arr, mode):
    if not np.issubdtype(np.asarray(arr).dtype, np.floating):
        return False
    return arr.ndim >= 2 if mode == "int8" else True


def quantize_array(arr, mode):
    """Quantize one host array; returns the stored form (ndarray or
    ``{"q","s"}`` dict). Ineligible arrays pass through as f32."""
    if mode == "bf16" and str(getattr(arr, "dtype", "")) == "bfloat16":
        return arr      # stored as it came, host or device: no round trip
    a = np.asarray(arr)
    if mode == "none" or not _eligible(a, mode):
        return a
    if mode == "bf16":
        import jax.numpy as jnp
        return np.asarray(jnp.asarray(a, jnp.bfloat16))
    amax = np.max(np.abs(a.astype(np.float32)),
                  axis=tuple(range(1, a.ndim)))
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(a / scale.reshape((-1,) + (1,) * (a.ndim - 1))),
                -127, 127).astype(np.int8)
    return {"q": q, "s": scale}


def quantize_tree(params, mode):
    """Quantize a flat name->array dict. ``mode == "none"`` is identity
    (modulo f32 cast), so callers can run unconditionally."""
    mode = resolve_mode(mode)
    return {k: quantize_array(v, mode) for k, v in params.items()}


def dequant_leaf(leaf):
    """In-graph upcast of one stored leaf back to f32 (traced). An
    already-f32 (or non-float) leaf passes through UNTOUCHED — no convert
    op, so an unquantized program stays bitwise what it always was."""
    import jax.numpy as jnp
    if is_quantized_leaf(leaf):
        s = leaf["s"].reshape((-1,) + (1,) * (leaf["q"].ndim - 1))
        return leaf["q"].astype(jnp.float32) * s
    leaf = jnp.asarray(leaf)
    if jnp.issubdtype(leaf.dtype, jnp.floating) \
            and leaf.dtype != jnp.float32:
        return leaf.astype(jnp.float32)
    return leaf


def dequant_tree(params):
    return {k: dequant_leaf(v) for k, v in params.items()}


def _leaf_arrays(tree):
    for v in tree.values():
        if is_quantized_leaf(v):
            yield v["q"]
            yield v["s"]
        else:
            yield v


def tree_bytes(tree):
    """Resident weight bytes of a (possibly quantized) param tree —
    from shape/dtype metadata only, so device arrays are never pulled
    to host."""
    return int(sum(np.dtype(a.dtype).itemsize * int(np.prod(a.shape, dtype=np.int64))
                   for a in _leaf_arrays(tree)))


def quality_report(ref_logits, quant_logits):
    """Compare f32 vs quantized forward outputs on a probe batch.
    Both are (n, classes) host arrays from the SAME inputs."""
    ref = np.asarray(ref_logits, np.float32)
    got = np.asarray(quant_logits, np.float32)
    if ref.shape != got.shape:
        raise MXNetError("quality_report: shape mismatch %s vs %s"
                         % (ref.shape, got.shape))
    agree = float(np.mean(np.argmax(ref, -1) == np.argmax(got, -1)))
    return {"top1_agreement": agree,
            "max_abs_err": float(np.max(np.abs(ref - got))),
            "probe_rows": int(ref.shape[0])}


def check_quality(report, min_agree=None, who="quantize"):
    """Gate: raise unless top-1 agreement clears the floor
    (``MXTPU_SERVE_QUANT_MIN_AGREE``, default 0.98)."""
    if min_agree is None:
        min_agree = env_float("MXTPU_SERVE_QUANT_MIN_AGREE", 0.98)
    agree = float(report["top1_agreement"])
    if agree < float(min_agree):
        raise MXNetError(
            "%s: quantization quality gate FAILED — top-1 agreement "
            "%.4f < floor %.4f over %d probe rows (max|dlogit|=%.3g). "
            "Use bf16 or quantize=none for this model."
            % (who, agree, float(min_agree), report["probe_rows"],
               report["max_abs_err"]))
    return agree
