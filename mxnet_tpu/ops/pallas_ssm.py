"""Pallas TPU kernel: one step of a Mamba-2 matrix state, in place.

A Granite 4.0-H mixer (``serving/granite_hybrid.py``) keeps, a slot and a
layer, a float32 state ``S`` of ``d_state`` rows by ``heads * d_head``
lanes (the heads along the lanes). One position steps it and reads it out:

    S <- decay * S + b (x) u        decay, u: a lane each; b: a row each
    y  = sum_rows(S * c)            c: a row each

As XLA compiles those lines, the read-out is a fusion of its own that reads
the OLD state again and recomputes the update beside the in-place write: the
state crosses the memory one and a half times a step. This kernel reads a
block of the state into fast memory once, steps it, writes it back into the
same buffer and reduces it against ``c`` before it leaves: a read and a
write, no more.

The whole ``(layers, slots, d_state, lanes)`` array goes in and comes out
aliased (donate it), and the layer comes by scalar prefetch: every layer's
call is one kernel body and one compile, and the other layers' bytes are
never touched. Everything is float32 and the read-out is the vector unit's
products summed down the rows: no matrix-unit pass that would round the
state to bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: slots and lanes of a block (tuned on one v5e at 64 slots, PERF.md)
SLOTS, LANES = 8, 512


def _kernel(n_ref, dec_ref, u_ref, b_ref, c_ref, s_ref, out_ref, y_ref):
    del n_ref                      # it chose the blocks; nothing else to do
    for j in range(s_ref.shape[0]):
        s = dec_ref[j:j + 1, :] * s_ref[j] \
            + b_ref[0, :, j:j + 1] * u_ref[j:j + 1, :]
        out_ref[j] = s
        y_ref[j:j + 1, :] = jnp.sum(s * c_ref[0, :, j:j + 1], axis=0,
                                    keepdims=True)


def ssm_step(state, n, decay, u, b, c, slots=SLOTS, lanes=LANES,
             interpret=False):
    """Step layer ``n`` of ``state`` (``(layers, S, rows, W)`` float32) by
    one position and read it out.

    ``decay`` and ``u`` are ``(S, W)``: a lane's decay (``pos > 0`` folded
    in: 0 gives a fresh state) and ``dt x``; ``b`` and ``c`` are ``(S,
    rows)``. Returns ``(state, y)``: the array with layer ``n`` stepped in
    place and ``y = sum_rows(S_n * c)``, ``(S, W)``. ``slots`` and ``lanes``
    are a block's where they divide their dimension; where not, a block
    takes the whole dimension (the one block the chip's tiling allows
    whatever the slot count)."""
    _, nslots, rows, width = state.shape
    ts = slots if nslots % slots == 0 else nslots
    tl = lanes if width % lanes == 0 else width
    # b and c by slot block with the slots along the lanes: a slot's
    # column is a static lane of its block
    bg, cg = (v.reshape(nslots // ts, ts, rows).transpose(0, 2, 1)
              for v in (b, c))
    rowvec = pl.BlockSpec((ts, tl), lambda i, l, n: (i, l))
    col = pl.BlockSpec((1, rows, ts), lambda i, l, n: (i, 0, 0))
    block = pl.BlockSpec((None, ts, rows, tl), lambda i, l, n: (n[0], i, 0, l))
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nslots // ts, width // tl),
            in_specs=[rowvec, rowvec, col, col, block],
            out_specs=[block, rowvec]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((nslots, width), state.dtype)],
        input_output_aliases={5: 0},
        # the state's block in and out, double-buffered, and room to spare
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=4 * ts * rows * tl * 4 + (8 << 20)),
        interpret=interpret,
    )(jnp.reshape(n, (1,)).astype(jnp.int32), decay, u, bg, cg, state)
