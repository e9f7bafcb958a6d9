"""Pallas TPU kernel: a Mamba-2 matrix state carried over the segments of a
packed prefill pass, in place.

A Granite 4.0-H mixer (``serving/granite_hybrid.py``) keeps, a slot and a
layer, a float32 state ``S`` of ``d_state`` rows by ``heads * d_head``
lanes (the heads along the lanes; ``ops/pallas_ssm.py`` steps it one
position a step). A packed prefill pass (``GraniteHybridArch.
build_prefill_pass``) carries several slots' prompts, each slot's rows a
SEGMENT, and runs the state in the chunked (SSD) form: what reads no state
is computed beside this kernel (``granite_hybrid.segment_ssd``); this
kernel reads each carried slot's state once, gives the segment's rows their
read-out of it and writes the state at the segment's end back into the same
buffer:

    y_t   = e_t * (C_t S0)                  t in the segment
    S_end = dec * S0 + sum_u B_u (x) w_u    u in the segment

A grid step a segment, the slots by scalar prefetch: only the carried
slots' blocks of the state are read and written, 2 MiB a layer each way
for each segment at Granite-4.0-H-Micro's widths, however many rows they
carry, never the other slots'. The whole ``(layers, slots, d_state,
lanes)`` array goes in and comes out aliased (donate it), and the layer
comes by scalar prefetch as ``pallas_ssm.ssm_step``'s does. Float32
throughout, its products on the matrix unit at ``HIGHEST``: float32
operands, float32 sums.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: lanes a product of the kernel takes at a time
LANES = 512


def _segments_kernel(n_ref, slot_ref, lo_ref, hi_ref, nseg_ref, dec_ref,
                     c_ref, e_ref, bt_ref, w_ref, s_ref, out_ref, y_ref, *,
                     lanes):
    del n_ref, slot_ref            # they chose the blocks
    j = pl.program_id(0)
    nseg = nseg_ref[0]
    f32 = jnp.float32
    highest = jax.lax.Precision.HIGHEST

    @pl.when(j == 0)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, f32)

    @pl.when((j == 0) & (nseg == 0))
    def _():
        out_ref[...] = s_ref[...]  # nothing carried: the block goes back

    @pl.when(j < nseg)
    def _():
        rows, width = y_ref.shape
        at = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 0)
        mine = (at >= lo_ref[j]) & (at < hi_ref[j])
        for l in range(width // lanes):
            sl = slice(l * lanes, (l + 1) * lanes)
            s0 = s_ref[:, sl]
            read = jnp.dot(c_ref[...], s0, precision=highest,
                           preferred_element_type=f32)
            y_ref[:, sl] = jnp.where(mine, e_ref[:, sl] * read, y_ref[:, sl])
            out_ref[:, sl] = dec_ref[:, sl] * s0 + jnp.dot(
                bt_ref[...], jnp.where(mine, w_ref[:, sl], 0.0),
                precision=highest, preferred_element_type=f32)


@functools.partial(jax.jit, static_argnames=("lanes", "interpret"))
def ssm_segments(state, n, slot, lo, hi, nseg, dec, c, e, b, w, lanes=LANES,
                 interpret=False):
    """Layer ``n`` of ``state`` (``(layers, S, rows, W)`` float32) carried
    over the segments of a packed prefill pass, in place, and each
    segment's rows' read-out of the state it started from.

    Segment ``j < nseg`` is rows ``lo[j] .. hi[j] - 1`` of the pass, slot
    ``slot[j]`` (each slot at most once). ``dec`` ``(J, W)`` is a segment's
    decay of its stored state to its last row (0 where it starts a
    request); ``c``, ``b`` ``(R, rows)`` and ``e``, ``w`` ``(R, W)`` are a
    row's: ``e`` weighs its read-out ``C S0``, ``w`` is what it adds to the
    segment's end state times ``B`` (:func:`..serving.granite_hybrid.
    segment_ssd`). Returns ``(state, y)``: ``S <- dec S + sum_u B_u (x)
    w_u`` over the segment's rows ``u``, written once a segment into the
    same buffer, and ``y`` ``(R, W)``: ``e_t (C_t S0)`` on a segment's rows,
    0 on the rest. Only the carried slots' blocks are read and written: a
    grid step a segment, the steps past ``nseg`` on the last one's block,
    which they neither fetch again nor touch. Float32 throughout, the
    products at ``HIGHEST``. Jitted, the layer an operand: every layer's
    call is one trace and one lowering of the kernel."""
    _, _, rows, width = state.shape
    nrows, nj = c.shape[0], slot.shape[0]
    tl = lanes if width % lanes == 0 else width
    seg = jnp.arange(nj) < nseg
    # the steps past the last segment stay on its block
    slot = jnp.where(seg, slot, slot[jnp.maximum(nseg - 1, 0)])

    def whole(*shape):      # an operand every grid step reads whole
        return pl.BlockSpec(shape, lambda j, *_: (0,) * len(shape))

    block = pl.BlockSpec((None, None, rows, width),
                         lambda j, n, s, *_: (n[0], s[j], 0, 0))
    vmem = 4 * (4 * rows * width + 6 * nrows * width + 4 * nrows * rows)
    return pl.pallas_call(
        functools.partial(_segments_kernel, lanes=tl),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(nj,),
            in_specs=[pl.BlockSpec((None, 1, width), lambda j, *_: (j, 0, 0)),
                      whole(nrows, rows), whole(nrows, width),
                      whole(rows, nrows), whole(nrows, width), block],
            out_specs=[block, whole(nrows, width)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((nrows, width), state.dtype)],
        input_output_aliases={10: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem + (16 << 20)),
        interpret=interpret,
    )(jnp.reshape(n, (1,)).astype(jnp.int32), slot.astype(jnp.int32),
      lo.astype(jnp.int32), hi.astype(jnp.int32),
      jnp.reshape(nseg, (1,)).astype(jnp.int32), dec[:, None, :], c, e, b.T,
      w, state)
