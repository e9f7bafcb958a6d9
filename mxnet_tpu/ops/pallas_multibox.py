"""Pallas TPU escape-hatch kernel: the MultiBox greedy-NMS suppression
sweep (docs/perf.md "Packed accumulators" — MultiBox A/B).

Why this op is the escape-hatch candidate (per the r4 fusion post-mortem
discipline: hand-fuse ONLY what XLA genuinely cannot): MultiBoxDetection's
suppression is a sequentially-dependent sweep — anchor i may only suppress
anchor j>i if i itself is still alive — which XLA lowers as a k-trip While
loop over HBM-resident (k, k) masks; every trip re-reads the suppression
matrix row and the alive vector. This kernel keeps the IOU matrix, the
class mask and the alive vector VMEM-RESIDENT for the whole sweep: one
pallas_call, one HBM read of the boxes/scores, one write of the final
mask (k = nms_topk ≤ 400, padded to 512 lanes → the (k, k) f32
suppression matrix is 1 MiB, well inside the ~16 MiB VMEM envelope).

Gated OFF by default behind ``MXTPU_PALLAS_MULTIBOX`` ("1" on TPU,
"interpret" for CPU tests — the same spelling as MXTPU_FUSE_CONV_BN);
docs/perf.md records the measured A/B. Ship-only-if-it-wins: the knob
stays opt-in until a chip-host measurement shows a win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _nms_kernel(rows_ref, cols_ref, alive_ref, sup_ref, *, nms_thresh,
                force):
    # Mosaic wants 2-D values with the long axis on the lanes: the same
    # (x1, y1, x2, y2, score, cls) table arrives twice, as lane vectors
    # rows_ref (8, k) and as sublane columns cols_ref (k, 8), so the
    # (k, k) pairwise terms are plain (k, 1) x (1, k) broadcasts
    k = rows_ref.shape[1]
    rx1, ry1, rx2, ry2 = (rows_ref[i:i + 1, :] for i in range(4))
    cx1, cy1, cx2, cy2 = (cols_ref[:, i:i + 1] for i in range(4))
    inter = (jnp.maximum(jnp.minimum(cx2, rx2) - jnp.maximum(cx1, rx1), 0.0)
             * jnp.maximum(jnp.minimum(cy2, ry2) - jnp.maximum(cy1, ry1),
                           0.0))
    rarea = jnp.maximum((rx2 - rx1) * (ry2 - ry1), 0.0)
    carea = jnp.maximum((cx2 - cx1) * (cy2 - cy1), 0.0)
    union = carea + rarea - inter
    iou = jnp.where(union > 0, inter / union, 0.0)
    same = cols_ref[:, 5:6] == rows_ref[5:6, :]
    if force:
        same = jnp.ones_like(same)
    # sup[i, j] = 1: anchor i (sublane) suppresses anchor j (lane). Held in
    # a VMEM scratch ref because a ROW of it is read by a traced index,
    # which Mosaic lowers for refs (pl.ds) and not for values
    sup_ref[...] = jnp.where((iou > nms_thresh) & same, 1.0, 0.0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def body(i, alive):
        # row i suppresses strictly-later anchors, but only while i
        # itself is still alive — the sequential dependence that keeps
        # this a sweep rather than one reduction
        row = sup_ref[pl.ds(i, 1), :]
        ai = jnp.max(jnp.where(lane == i, alive, 0.0))
        return alive * (1.0 - row * ai * jnp.where(lane > i, 1.0, 0.0))

    alive_ref[...] = jax.lax.fori_loop(
        0, k, body, jnp.where(rows_ref[4:5, :] > 0, 1.0, 0.0))


@functools.partial(jax.jit,
                   static_argnames=("nms_thresh", "force", "interpret"))
def nms_alive(sboxes, sscore, scls, nms_thresh, force=False,
              interpret=False):
    """Greedy class-aware NMS survival mask over score-sorted anchors:
    ``sboxes`` (k, 4) corners, ``sscore`` (k,), ``scls`` (k,) ->
    float32 (k,) 1.0/0.0 mask, semantics identical to the XLA
    fori_loop formulation in ops/contrib.py (parity-tested). ``k`` is
    padded to a lane multiple with zero-score anchors, which are never
    alive and so suppress nothing."""
    k = sboxes.shape[0]
    kp = -(-k // 128) * 128
    cols = jnp.concatenate(
        [sboxes.astype(jnp.float32), sscore.astype(jnp.float32)[:, None],
         scls.astype(jnp.float32)[:, None], jnp.zeros((k, 2), jnp.float32)],
        axis=1)
    cols = jnp.pad(cols, ((0, kp - k), (0, 0)))
    kern = functools.partial(_nms_kernel, nms_thresh=float(nms_thresh),
                             force=bool(force))
    alive = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((1, kp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((kp, kp), jnp.float32)],
        interpret=interpret,
    )(cols.T, cols)
    return alive[0, :k]


def mode():
    """The MXTPU_PALLAS_MULTIBOX knob: '' (off, default), '1' (kernel
    compiled for the chip; fails where there is none), 'interpret' (the
    Pallas interpreter — CPU tests/A-B)."""
    import os
    v = os.environ.get("MXTPU_PALLAS_MULTIBOX", "0").strip().lower()
    return "" if v in ("", "0", "false", "off", "no") else v


def enabled():
    return mode() != ""


def interpret_requested():
    return mode() == "interpret"
