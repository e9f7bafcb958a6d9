"""Device-mesh helpers.

The reference enumerates devices by (device_type, dev_id) and hand-routes
communication (CommDevice GPU reduce, comm.h:211-373; ps-lite across hosts).
Here placement is declarative: build a Mesh with named axes — 'data' (dp),
'model' (tp), 'pipe' (pp), 'seq' (sp), 'expert' (ep) — annotate shardings,
and XLA inserts the collectives that ride ICI within a slice and DCN across
slices (the "How to Scale Your Model" recipe).
"""
from __future__ import annotations

import threading

import jax
import numpy as np

from ..base import MXNetError

P = jax.sharding.PartitionSpec

_scope = threading.local()

AXIS_DATA = "data"
AXIS_MODEL = "model"
AXIS_PIPE = "pipe"
AXIS_SEQ = "seq"
AXIS_EXPERT = "expert"

#: every axis name a multi-axis training mesh may carry, in canonical
#: order (the order ``parse_mesh_axes`` normalizes specs into)
AXIS_NAMES = (AXIS_DATA, AXIS_MODEL, AXIS_PIPE, AXIS_SEQ, AXIS_EXPERT)


def make_mesh(axis_shapes, devices=None):
    """Create a Mesh from {'data': 4, 'model': 2, ...}.

    Axis order follows insertion order; total size must equal device count.
    """
    if devices is None:
        devices = jax.devices()
    names = tuple(axis_shapes.keys())
    shape = tuple(int(axis_shapes[n]) for n in names)
    n = int(np.prod(shape))
    if n != len(devices):
        if n < len(devices):
            devices = devices[:n]
        else:
            raise MXNetError("mesh needs %d devices, have %d"
                             % (n, len(devices)))
    arr = np.array(devices).reshape(shape)
    return jax.sharding.Mesh(arr, names)


def data_parallel_mesh(num=None, devices=None):
    if devices is None:
        devices = jax.devices()
    if num is not None:
        devices = devices[:num]
    return make_mesh({AXIS_DATA: len(devices)}, devices)


def model_parallel_mesh(num=None, devices=None):
    """One-axis 'model' mesh — the serving tier's bigger-than-one-chip
    substrate: a ServingEngine/DecodeLoop built over N contexts compiles
    each program with params sharded over this axis
    (docs/serving.md "Model-parallel replicas")."""
    if devices is None:
        devices = jax.devices()
    if num is not None:
        if num > len(devices):
            raise MXNetError(
                "model_parallel_mesh: %d devices requested, %d visible "
                "(on CPU, raise XLA_FLAGS=--xla_force_host_platform_"
                "device_count)" % (num, len(devices)))
        devices = devices[:num]
    return make_mesh({AXIS_MODEL: len(devices)}, devices)


def parse_mesh_axes(spec):
    """Parse a mesh-axes spec — ``"data=2,seq=4"`` or a ``{"data": 2,
    "seq": 4}`` dict — into an ordered ``{axis: size}`` dict (insertion
    order preserved; that order becomes the mesh axis order). Axis names
    must come from :data:`AXIS_NAMES`; sizes must be positive integers.
    Raises :class:`MXNetError` naming the offending token."""
    if isinstance(spec, dict):
        items = list(spec.items())
    else:
        items = []
        for tok in str(spec).split(","):
            tok = tok.strip()
            if not tok:
                continue
            if "=" not in tok:
                raise MXNetError(
                    "mesh axes spec %r: token %r is not 'axis=N' "
                    "(e.g. 'data=2,seq=4')" % (spec, tok))
            name, _, num = tok.partition("=")
            items.append((name.strip(), num.strip()))
    axes = {}
    for name, num in items:
        if name not in AXIS_NAMES:
            raise MXNetError(
                "mesh axes spec %r: unknown axis %r (valid: %s)"
                % (spec, name, ", ".join(AXIS_NAMES)))
        try:
            n = int(num)
        except (TypeError, ValueError):
            raise MXNetError("mesh axes spec %r: axis %r size %r is not "
                             "an integer" % (spec, name, num))
        if n < 1:
            raise MXNetError("mesh axes spec %r: axis %r size must be "
                             ">= 1, got %d" % (spec, name, n))
        if name in axes:
            raise MXNetError("mesh axes spec %r: axis %r given twice"
                             % (spec, name))
        axes[name] = n
    if not axes:
        raise MXNetError("mesh axes spec %r names no axes" % (spec,))
    return axes


def mesh_from_spec(spec, devices=None):
    """Build a multi-axis Mesh from a spec (:func:`parse_mesh_axes`
    accepts strings and dicts) over the first ``prod(sizes)`` visible
    devices. A device shortfall fails actionably with the
    ``XLA_FLAGS`` recipe instead of :func:`make_mesh`'s bare count."""
    axes = parse_mesh_axes(spec)
    if devices is None:
        devices = jax.devices()
    need = int(np.prod(list(axes.values())))
    if need > len(devices):
        raise MXNetError(
            "mesh %s needs %d devices but only %d are visible — on CPU "
            "raise the count with XLA_FLAGS=--xla_force_host_platform_"
            "device_count=%d"
            % ("x".join("%s=%d" % kv for kv in axes.items()), need,
               len(devices), need))
    return make_mesh(axes, list(devices)[:need])


def check_axis_divides(mesh, axis, value, what):
    """Divisibility precheck for one mesh axis: ``value`` (the dimension
    the axis will shard) must divide evenly over the axis. Raises
    :class:`MXNetError` NAMING the failing axis and the offending
    dimension — the error a user can act on, instead of the XLA
    partitioner's shape complaint three layers down. No-op when the mesh
    lacks the axis (size 1 divides everything)."""
    n = data_axis_size(mesh, axis)
    if n > 1 and int(value) % n:
        raise MXNetError(
            "%s %d does not divide the %d-way %r mesh axis — every shard "
            "must be equal (pad %s or pick a size divisible by %d)"
            % (what, int(value), n, axis, what, n))


class MeshScope(object):
    """with MeshScope(mesh): — sets the ambient mesh for Module/KVStore."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        self._old = getattr(_scope, "mesh", None)
        _scope.mesh = self.mesh
        return self.mesh

    def __exit__(self, *a):
        _scope.mesh = self._old


def current_mesh():
    return getattr(_scope, "mesh", None)


def replicate(tree, mesh):
    """device_put a pytree replicated over the mesh."""
    s = jax.sharding.NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, s), tree)


def shard_batch(tree, mesh, axis=AXIS_DATA):
    """device_put a pytree with dim-0 sharded along the given mesh axis."""
    def put(x):
        spec = P(axis) if getattr(x, "ndim", 0) >= 1 else P()
        return jax.device_put(x, jax.sharding.NamedSharding(mesh, spec))
    return jax.tree_util.tree_map(put, tree)


def data_axis_size(mesh, axis=AXIS_DATA):
    """Number of shards along the mesh's data axis (1 when absent) — the
    divisor every global batch dimension must honor."""
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return int(mesh.shape[axis])


def superbatch_sharding(mesh, axis=AXIS_DATA, seq=False):
    """NamedSharding for stacked (k, batch, ...) superbatch arrays: the
    step axis replicated, the batch axis sharded along ``axis``. This is
    the sharding ``SuperBatchIter`` lands its H2D with, so each chip
    receives only its own batch shard and the dispatch loop never pays a
    resharding copy (the dist_sync data partition, one level up: the unit
    is a whole K-step dispatch).

    ``seq=True`` additionally splits dim 2 (the token dim of a stacked
    (k, batch, seq) LM batch) over the 'seq' axis when the mesh carries
    one — the multi-axis variant; only valid when EVERY array the
    sharding will land is rank >= 3 stacked (SuperBatchIter applies one
    sharding to all slots)."""
    if mesh is None:
        return None
    if seq and AXIS_SEQ in mesh.axis_names:
        bax = axis if axis in mesh.axis_names else None
        return jax.sharding.NamedSharding(mesh, P(None, bax, AXIS_SEQ))
    if axis not in mesh.axis_names:
        return None
    return jax.sharding.NamedSharding(mesh, P(None, axis))


def is_multiprocess(mesh):
    """True when the mesh spans more than one jax process (multi-host)."""
    if mesh is None:
        return False
    return len({d.process_index for d in mesh.devices.flat}) > 1


def global_data_mesh(axis_name=AXIS_DATA, local_devices=None):
    """Mesh over devices of ALL processes along one data axis — the
    dist_sync substrate: batch shards ride 'data' across hosts and XLA's
    gradient psum rides DCN/ICI (the ps-lite replacement, SURVEY §2.4).

    ``local_devices`` restricts the mesh to the given devices of THIS
    process plus the same positions on every other process (workers are
    assumed symmetric — the reference's assumption too: every worker runs
    the same script with the same device list)."""
    devices = jax.devices()  # global list, all processes
    if local_devices is not None:
        mine = jax.local_devices()
        keep = sorted({mine.index(d) for d in local_devices})
        by_proc = {}
        for d in devices:
            by_proc.setdefault(d.process_index, []).append(d)
        devices = [p_devs[i] for _, p_devs in sorted(by_proc.items())
                   for i in keep if i < len(p_devs)]
    return jax.sharding.Mesh(np.array(devices), (axis_name,))


def host_to_global(mesh, spec, local_value):
    """Build a global jax.Array from per-process host data.

    For dims sharded across processes ``local_value`` is THIS process's
    portion (e.g. its batch shard); for replicated specs every process
    passes the same full value.
    """
    s = jax.sharding.NamedSharding(mesh, spec)
    return jax.make_array_from_process_local_data(s, np.asarray(local_value))


def host_broadcast0(mesh, value):
    """Broadcast rank-0's host value to every process (returns a host
    array): the dist kvstore init semantics — one authoritative copy, like
    the reference server's single stored weight (ref: kvstore_dist_server.h).
    Implemented as a masked global sum so it rides the same collective path
    as everything else."""
    import jax.numpy as jnp
    me = jax.process_index()
    n_local = sum(1 for d in mesh.devices.flat if d.process_index == me)
    local = np.asarray(value)
    # only rank 0's FIRST device slot contributes the value — no division,
    # so integer dtypes survive and every rank builds the same-typed array
    zero = np.zeros_like(local)
    tile = np.stack([local if (me == 0 and j == 0) else zero
                     for j in range(n_local)])
    axis = mesh.axis_names[0]
    sharded = jax.sharding.NamedSharding(mesh, P(axis))
    repl = jax.sharding.NamedSharding(mesh, P())
    garr = jax.make_array_from_process_local_data(sharded, tile)
    out = jax.jit(lambda a: jnp.sum(a, axis=0), out_shardings=repl)(garr)
    return np.asarray(out)


def local_view(arr):
    """This process's slice of a global array, as one host-order array
    (the per-worker view of batch-sharded outputs: each worker computes
    metrics on its own shard, like the reference's per-worker eval)."""
    import jax.numpy as jnp
    if getattr(arr, "is_fully_addressable", True):
        return arr
    if arr.is_fully_replicated:
        return jnp.asarray(np.asarray(arr))
    shards = sorted(arr.addressable_shards,
                    key=lambda s: [sl.start or 0 for sl in s.index])
    return jnp.concatenate([s.data for s in shards], axis=0)


def grad_sync(grads, axis_name=AXIS_DATA):
    """Explicit gradient all-reduce for shard_map-style training steps —
    the dist_sync kv.push+pull semantics as one psum over ICI
    (ref: kvstore_dist.h sync mode; SURVEY.md §2.4)."""
    return jax.tree_util.tree_map(
        lambda g: jax.lax.psum(g, axis_name), grads)
