"""SPMD pipeline parallelism over the 'pipe' mesh axis.

The reference pipelines an LSTM across GPUs by *placing* each layer on its
own device and letting the dependency engine overlap timesteps
(ref: example/model-parallel-lstm/lstm.py:48-112,
docs/how_to/model_parallel_lstm.md). The TPU/SPMD formulation: stack the
per-stage parameters along a leading stage dimension sharded over the
'pipe' axis (one stage per device), split the batch into microbatches, and
run the classic GPipe schedule as a single ``lax.scan`` — on every tick all
stages compute in parallel on their in-flight microbatch, then activations
hop to the next stage via ``ppermute`` over neighbor ICI links. The bubble
is (S-1)/(S-1+M) and shrinks with more microbatches.

Requires all stages to share one structure (true for stacked LSTM/transformer
layers). Works inside jit/shard_map; differentiable, so the same schedule
serves training.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P


def pipeline_spmd(stage_fn, stacked_params, microbatches, axis_name="pipe"):
    """Run microbatches through a pipeline of stages — call INSIDE shard_map.

    stage_fn(params, x) -> y        one stage's computation; y.shape == x.shape
    stacked_params: pytree whose leaves have leading dim 1 (this device's
        stage, i.e. the global (S, ...) stack sharded over ``axis_name``)
    microbatches: (M, ...) array, identical on every device (replicated)

    Returns (M, ...) outputs of the LAST stage, identical on every device.
    """
    S = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = microbatches.shape[0]
    local_params = jax.tree_util.tree_map(lambda p: p[0], stacked_params)
    fwd = [(j, (j + 1) % S) for j in range(S)]
    zero = jnp.zeros_like(microbatches[0])

    def tick(carry, t):
        state, out_buf = carry
        # stage 0 ingests microbatch t (zeros once the feed is exhausted —
        # bubble ticks compute on garbage that is never read)
        feed = jax.lax.dynamic_index_in_dim(
            microbatches, jnp.minimum(t, M - 1), axis=0, keepdims=False)
        x = jnp.where(idx == 0, feed, state)
        y = stage_fn(local_params, x)
        # last stage banks its result at output slot t-(S-1)
        slot = jnp.clip(t - (S - 1), 0, M - 1)
        bank = jnp.logical_and(idx == S - 1, t >= S - 1)
        cur = jax.lax.dynamic_index_in_dim(out_buf, slot, 0, keepdims=False)
        out_buf = jax.lax.dynamic_update_index_in_dim(
            out_buf, jnp.where(bank, y, cur), slot, 0)
        # activations hop one stage forward around the ring
        state = jax.lax.ppermute(y, axis_name, fwd)
        return (state, out_buf), None

    out0 = jnp.zeros_like(microbatches)
    (_, out_buf), _ = jax.lax.scan(
        tick, (zero, out0), jnp.arange(S + M - 1))
    # only the last stage holds real outputs; share them with every stage
    mask = (idx == S - 1).astype(out_buf.dtype)
    return jax.lax.psum(out_buf * mask, axis_name)


def pipeline_apply(stage_fn, stacked_params, batch, mesh, axis_name="pipe",
                   num_microbatches=None, batch_axis=None):
    """jit-able wrapper: shard stacked params over ``axis_name``, split the
    batch into microbatches, run the GPipe schedule, and re-assemble.

    stacked_params leaves have leading dim S == mesh.shape[axis_name];
    batch is (B, ...) with B divisible by num_microbatches (default S).

    ``batch_axis`` composes pipeline with data parallelism on one mesh:
    when set (normally 'data'), each microbatch's batch dimension stays
    sharded over that axis inside the schedule — the pipe ring hops and
    the final psum ride ``axis_name`` only, so a data x pipe mesh runs
    dp shards of the same pipeline side by side.
    """
    from ..base import MXNetError
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    M = num_microbatches or S
    B = batch.shape[0]
    if B % M:
        raise MXNetError(
            "pipeline_apply: batch dim %d does not divide into %d "
            "microbatches over the %d-way %r mesh axis — pad the batch "
            "or pass a num_microbatches that divides it" % (B, M, S,
                                                            axis_name))
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[0] != S:
            raise MXNetError(
                "pipeline_apply: stacked-parameter stage dim %d does not "
                "match the %d-way %r mesh axis — stack one stage per "
                "device (or reshape a layer stack to (stages, "
                "layers_per_stage, ...) before the call)"
                % (leaf.shape[0], S, axis_name))
    micro = batch.reshape((M, B // M) + batch.shape[1:])
    if batch_axis is not None:
        from .mesh import data_axis_size
        dp = data_axis_size(mesh, batch_axis)
        if (B // M) % dp:
            raise MXNetError(
                "pipeline_apply: microbatch dim %d does not divide the "
                "%d-way %r mesh axis — every shard must be equal"
                % (B // M, dp, batch_axis))
    bspec = P() if batch_axis is None else P(None, batch_axis)

    pspec = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    fn = jax.shard_map(
        functools.partial(pipeline_spmd, stage_fn, axis_name=axis_name),
        mesh=mesh,
        in_specs=(pspec, bspec),
        out_specs=bspec,
        check_vma=False)
    out = fn(stacked_params, micro)
    return out.reshape((B,) + out.shape[2:])
