"""Executor: binds a Symbol to devices and runs forward/backward.

Re-design of the reference GraphExecutor (ref: src/executor/graph_executor.cc,
include/mxnet/executor.h, python/mxnet/executor.py). The reference compiles
the graph itself — Gradient pass, PlaceDevice, InferShape/Type, PlanMemory,
op bulking (graph_executor.cc:336-759). Here the DAG lowers to one pure JAX
function and XLA performs all of those roles: ``forward`` is a jitted call,
``backward`` differentiates the same function with ``jax.vjp`` (no per-op
backward graph), memory planning/fusion/bulking are XLA's, and gradient
accumulation honors grad_req write/add/null semantics
(ref: OpReqType kWriteTo/kAddTo/kNullOp, include/mxnet/op_attr_types.h).

Laziness: ``forward()`` snapshots inputs and defers compute; reading
``.outputs`` forces a forward-only jit, while calling ``backward()`` first
runs a single fused forward+backward jit — so a fit() step costs exactly one
XLA invocation, mirroring the reference's engine overlap for free.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError
from .context import Context, current_context
from .ndarray import NDArray
from .ops.registry import OpContext
from .symbol import Symbol, _topo
from . import random as _random


def _build_graph_runner(symbol, placement=None, node_constraint=None):
    """Lower the symbol DAG to a pure function
    run(arg_vals: dict, aux_vals: dict, key, is_train) -> (outputs, aux_updates).

    ``placement`` (parallel.placement.GroupPlacement) lowers ctx_group
    annotations to per-node sharding constraints — the SPMD analog of the
    reference's PlaceDevice pass + _CrossDeviceCopy insertion
    (ref: src/executor/graph_executor.cc:244-334).

    ``node_constraint`` (callable ``(node, outs) -> outs``, trace-time) is
    a caller-supplied sharding hook applied to every non-variable node's
    outputs — the serving tier uses it to keep activations replicated at
    the graph edges of a model-axis-sharded engine (docs/serving.md
    "Model-parallel replicas") without annotating the symbol."""
    nodes = _topo(symbol._out_nodes())
    node_groups = {}
    if placement is not None:
        from .parallel.placement import node_group, param_groups
        node_groups = {id(n): node_group(n) for n in nodes}
        var_groups = param_groups(nodes)

    # Conv(1x1 NHWC)+BN fusion pass (the Pallas conv+stats epilogue — see
    # ops/pallas_fused.py). The TPU analog of the reference's cuDNN fused
    # epilogues; peephole over the DAG like nnvm's DetectInplaceAddTo
    # (ref: src/executor/inplace_addto_detect_pass.cc pattern).
    # OPT-IN: measured 2x slower than letting XLA fuse on v5e
    # (docs/perf.md r4) — "1" compiles the kernel for the chip (and fails
    # where there is none), "interpret" runs the Pallas interpreter.
    fuse_mode = os.environ.get("MXTPU_FUSE_CONV_BN", "0")
    fused_convs = {}        # id(conv node) -> conv node
    bn_stats_src = {}       # id(bn node) -> id(conv node)
    if fuse_mode != "0" and placement is None:
        from .ops import pallas_fused as _pf
        for node in nodes:
            if node.is_variable or node.op.name != "BatchNorm":
                continue
            if not node.inputs or not _pf.bn_fusable(node.attrs):
                continue
            src, src_idx = node.inputs[0]
            if (src_idx == 0 and not src.is_variable
                    and src.op.name == "Convolution"
                    and _pf.conv1x1_fusable(src.attrs)):
                fused_convs[id(src)] = src
                bn_stats_src[id(node)] = id(src)

    def run(arg_vals, aux_vals, key, is_train):
        use_fusion = bool(fused_convs) and is_train
        if use_fusion:
            from .ops import pallas_fused as _pf
            interp = fuse_mode == "interpret"
        env = {}
        stats_env = {}
        aux_updates = {}
        for k, node in enumerate(nodes):
            if node.is_variable:
                v = arg_vals[node.name]
                if placement is not None:
                    g = var_groups.get(node.name)
                    if g is not None:
                        # is_param: confirm the allocation-time layout
                        # (first-dim rule) rather than forcing an
                        # activation-style reshard of every weight per step
                        v = placement.constrain(g, v, is_param=True)
                env[(id(node), 0)] = v
                continue
            ins = [env[(id(n), i)] for n, i in node.inputs]
            aux_names = node.op.list_aux(node.attrs)
            aux_in = [aux_vals["%s_%s" % (node.name, a)] for a in aux_names]
            rng = None
            if node.op.needs_rng and key is not None:
                rng = jax.random.fold_in(key, k)
            fused_stats = (stats_env.get(bn_stats_src.get(id(node)))
                           if use_fusion else None)
            op_ctx = OpContext(is_train=is_train, rng=rng,
                               fused_stats=fused_stats)
            # named_scope threads op names into XLA metadata so profiler
            # traces show MXNet op names, not anonymous fusions (ref:
            # PROFILER_MESSAGE threading names through every engine push,
            # include/mxnet/base.h:79-83)
            if use_fusion and id(node) in fused_convs:
                with jax.named_scope("ConvBNStats:%s" % node.name):
                    y, stats = _pf.apply_conv1x1_stats(ins[0], ins[1],
                                                       interpret=interp)
                stats_env[id(node)] = stats
                outs, aux_up = (y,), None
            else:
                with jax.named_scope("%s:%s" % (node.op.name, node.name)):
                    outs, aux_up = node.op.apply(op_ctx, node.attrs, ins,
                                                 aux_in)
            if node_constraint is not None:
                outs = node_constraint(node, outs)
            g = node_groups.get(id(node))
            if g is not None:
                outs = [placement.constrain(g, o) for o in outs]
            for i, o in enumerate(outs):
                env[(id(node), i)] = o
            if aux_up is not None:
                for a, u in zip(aux_names, aux_up):
                    aux_updates["%s_%s" % (node.name, a)] = u
        outputs = [env[(id(n), i)] for n, i in symbol._outputs]
        return outputs, aux_updates

    return run, nodes


class _LazyOutputs(object):
    """Sequence proxy returned by forward(is_train=True): preserves the
    reference contract (forward returns outputs) without forcing computation
    unless the caller actually reads it — so fit()'s forward+backward still
    fuses into one XLA call."""

    __slots__ = ("_exec",)

    def __init__(self, executor):
        self._exec = executor

    def _force(self):
        return self._exec.outputs

    def __getitem__(self, i):
        return self._force()[i]

    def __len__(self):
        return len(self._exec.output_names)

    def __iter__(self):
        return iter(self._force())

    def __repr__(self):
        return "<LazyOutputs of %d outputs>" % len(self)


class Executor(object):
    """Executor over a bound symbol (ref: python/mxnet/executor.py)."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None):
        self._symbol = symbol
        self._ctx = ctx if isinstance(ctx, Context) else Context(ctx)
        # ctx_group model parallelism: lower group annotations to mesh
        # sharding constraints (see parallel/placement.py); simple_bind
        # passes an already-resolved GroupPlacement
        from .parallel import placement as _placement
        if isinstance(group2ctx, _placement.GroupPlacement):
            self._placement = group2ctx
            self._group2ctx = dict(group2ctx.raw)
        else:
            self._group2ctx = group2ctx or {}
            self._placement = _placement.resolve(self._group2ctx)
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()

        self.arg_dict = self._normalize(args, self.arg_names, "args")
        self.arg_arrays = [self.arg_dict[n] for n in self.arg_names]
        if args_grad is None:
            self.grad_dict = {}
        else:
            self.grad_dict = self._normalize(args_grad, self.arg_names,
                                             "args_grad", allow_missing=True)
        self.grad_arrays = [self.grad_dict.get(n) for n in self.arg_names]
        self.aux_dict = self._normalize(aux_states, self.aux_names, "aux",
                                        allow_missing=False) if self.aux_names else {}
        self.aux_arrays = [self.aux_dict[n] for n in self.aux_names]

        if isinstance(grad_req, str):
            self._grad_req = {n: (grad_req if n in self.grad_dict else "null")
                              for n in self.arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self.arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, "null") for n in self.arg_names}
        for n in self.arg_names:
            if self._grad_req.get(n, "null") != "null" and n not in self.grad_dict:
                if not jnp.issubdtype(self.arg_dict[n].data.dtype,
                                      jnp.floating):
                    # integer inputs have no gradient (reference kNullOp)
                    self._grad_req[n] = "null"
                    continue
                raise MXNetError("grad_req %r for %s but no grad array bound"
                                 % (self._grad_req[n], n))

        self._run, self._nodes = _build_graph_runner(symbol, self._placement)
        self._diff_args = [n for n in self.arg_names
                           if self._grad_req.get(n, "null") != "null"]
        # group diff args by grad-buffer identity: a buffer shared across
        # several arguments (weight tying) receives the SUM of their
        # gradients, written once (ref: DeduplicateVarHandle + kAddTo
        # semantics, include/mxnet/engine.h:231-249)
        self._grad_groups = []   # list of (buffer, [arg names])
        _by_buf = {}
        for n in self._diff_args:
            buf = self.grad_dict[n]
            if id(buf) in _by_buf:
                self._grad_groups[_by_buf[id(buf)]][1].append(n)
            else:
                _by_buf[id(buf)] = len(self._grad_groups)
                self._grad_groups.append((buf, [n]))
        self._has_add = any(self._grad_req.get(n) == "add"
                            for n in self._diff_args)
        self._needs_rng = any((not n.is_variable) and n.op.needs_rng
                              for n in self._nodes)
        self._base_key = _random.split()
        self._step = 0
        self._monitor_callback = None

        # pending forward snapshot
        self._pending = None       # (arg_vals, aux_vals, key, is_train)
        self._outputs_nd = None
        self._jit_fwd = {}
        self._jit_fused = {}

    # ------------------------------------------------------------------
    def _normalize(self, arrays, names, what, allow_missing=False):
        if arrays is None:
            arrays = {}
        if isinstance(arrays, (list, tuple)):
            if len(arrays) != len(names):
                raise MXNetError("%s: expected %d arrays, got %d"
                                 % (what, len(names), len(arrays)))
            return {n: a for n, a in zip(names, arrays) if a is not None}
        out = {}
        for n in names:
            if n in arrays:
                out[n] = arrays[n]
            elif not allow_missing and what in ("args", "aux"):
                raise MXNetError("%s: missing array for %r" % (what, n))
        return out

    # ------------------------------------------------------------------
    @property
    def outputs(self):
        self._ensure_forward()
        return self._outputs_nd

    def forward(self, is_train=False, **kwargs):
        # deferred MXNET_PROFILER_AUTOSTART (docs/observability.md): the
        # device trace starts at the FIRST dispatch, after any
        # profiler_set_config — one boolean check once resolved
        from . import profiler as _profiler
        _profiler.maybe_autostart()
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError("forward: unknown argument %r" % k)
            if isinstance(v, NDArray):
                self.arg_dict[k]._set_data(v.data)
            else:
                self.arg_dict[k]._set_data(jnp.asarray(np.asarray(v)))
        key = None
        if self._needs_rng:
            key = jax.random.fold_in(self._base_key, self._step)
            self._step += 1
        arg_vals = {n: self.arg_dict[n].data for n in self.arg_names}
        aux_vals = {n: self.aux_dict[n].data for n in self.aux_names}
        self._pending = (arg_vals, aux_vals, key, bool(is_train))
        self._outputs_nd = None
        if self._monitor_callback is not None:
            self._ensure_forward()
            return self._outputs_nd
        if not is_train:
            # eval path: force now (async dispatch, does not block)
            self._ensure_forward()
            return self._outputs_nd
        # training path stays lazy so backward() fuses fwd+bwd into one jit;
        # the proxy forces computation only if the caller actually reads it
        return _LazyOutputs(self)

    def _ensure_forward(self):
        if self._outputs_nd is not None:
            return
        if self._pending is None:
            raise MXNetError("call forward() first")
        arg_vals, aux_vals, key, is_train = self._pending
        if self._monitor_callback is not None:
            self._forward_monitored(arg_vals, aux_vals, key, is_train)
            return
        if is_train not in self._jit_fwd:
            run = self._run

            def fwd(arg_vals, aux_vals, key):
                return run(arg_vals, aux_vals, key, is_train)

            self._jit_fwd[is_train] = jax.jit(fwd)
        outs, aux_up = self._jit_fwd[is_train](arg_vals, aux_vals, key)
        self._finish(outs, aux_up, is_train)

    def _finish(self, outs, aux_up, is_train):
        self._outputs_nd = [NDArray(o) for o in outs]
        if is_train:
            for n, u in aux_up.items():
                self.aux_dict[n]._set_data(u)

    def _forward_monitored(self, arg_vals, aux_vals, key, is_train):
        """Un-jitted per-node execution invoking the monitor callback on every
        op output (ref: GraphExecutor::SetMonitorCallback,
        graph_executor.cc:63-70,:761-781)."""
        env = {}
        aux_updates = {}
        for k, node in enumerate(self._nodes):
            if node.is_variable:
                env[(id(node), 0)] = arg_vals[node.name]
                continue
            ins = [env[(id(n), i)] for n, i in node.inputs]
            aux_names = node.op.list_aux(node.attrs)
            aux_in = [aux_vals["%s_%s" % (node.name, a)] for a in aux_names]
            rng = (jax.random.fold_in(key, k)
                   if node.op.needs_rng and key is not None else None)
            outs, aux_up = node.op.apply(OpContext(is_train, rng),
                                         node.attrs, ins, aux_in)
            for i, (oname, o) in enumerate(zip(node.output_names(), outs)):
                env[(id(node), i)] = o
                self._monitor_callback(oname, NDArray(o))
            if aux_up is not None:
                for a, u in zip(aux_names, aux_up):
                    aux_updates["%s_%s" % (node.name, a)] = u
        outs = [env[(id(n), i)] for n, i in self._symbol._outputs]
        self._finish(outs, aux_updates, is_train)

    # ------------------------------------------------------------------
    def backward(self, out_grads=None):
        """Run backward; fills bound gradient arrays honoring grad_req.

        If outputs were not yet forced, runs ONE fused forward+backward jit.
        """
        if self._pending is None:
            raise MXNetError("call forward(is_train=True) before backward()")
        arg_vals, aux_vals, key, is_train = self._pending
        if not is_train:
            raise MXNetError("backward called on forward(is_train=False)")
        if not self._diff_args:
            self._ensure_forward()
            return
        if out_grads is not None and not isinstance(out_grads, (list, tuple)):
            out_grads = [out_grads]
        use_default_head = out_grads is None
        jkey = (use_default_head,)
        if jkey not in self._jit_fused:
            self._jit_fused[jkey] = self._make_fused(use_default_head)
        head_vals = ([] if use_default_head
                     else [g.data if isinstance(g, NDArray) else jnp.asarray(g)
                           for g in out_grads])
        prev_grads = ([buf.data for buf, _names in self._grad_groups]
                      if self._has_add else [])
        outs, grads, aux_up = self._jit_fused[jkey](
            arg_vals, aux_vals, key, head_vals, prev_grads)
        self._finish(outs, aux_up, is_train=True)
        for (buf, _names), g in zip(self._grad_groups, grads):
            buf._set_data(g)

    def _make_fused(self, use_default_head):
        run = self._run
        diff_args = list(self._diff_args)
        grad_req = dict(self._grad_req)
        groups = [tuple(names) for _buf, names in self._grad_groups]

        def fused(arg_vals, aux_vals, key, head_vals, prev_grads):
            def f(diff_vals):
                full = dict(arg_vals)
                for n, v in zip(diff_args, diff_vals):
                    full[n] = v
                outs, aux_up = run(full, aux_vals, key, True)
                return outs, aux_up

            primal_in = [arg_vals[n] for n in diff_args]
            (outs, aux_up), vjp_fn = jax.vjp(f, primal_in, has_aux=False)
            # vjp over the (outs, aux_up) pair: zero-cotangent the aux part
            cots_aux = jax.tree_util.tree_map(jnp.zeros_like, aux_up)
            if use_default_head:
                cots = [jnp.ones_like(o) for o in outs]
            else:
                cots = list(head_vals)
            (dgrads,) = vjp_fn((cots, cots_aux))
            by_name = dict(zip(diff_args, dgrads))
            final = []
            for gi, names in enumerate(groups):
                g = by_name[names[0]]
                for n in names[1:]:
                    g = g + by_name[n]
                if grad_req[names[0]] == "add":
                    g = prev_grads[gi] + g
                final.append(g)
            return outs, final, aux_up

        donate = (4,) if self._has_add else ()
        return jax.jit(fused, donate_argnums=donate)

    # ------------------------------------------------------------------
    def set_monitor_callback(self, callback):
        self._monitor_callback = callback

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for name, array in arg_params.items():
            if name in self.arg_dict:
                self.arg_dict[name]._set_data(
                    array.data if isinstance(array, NDArray)
                    else jnp.asarray(np.asarray(array)))
            elif not allow_extra_params:
                raise MXNetError("copy_params_from: %r not an argument" % name)
        if aux_params:
            for name, array in aux_params.items():
                if name in self.aux_dict:
                    self.aux_dict[name]._set_data(
                        array.data if isinstance(array, NDArray)
                        else jnp.asarray(np.asarray(array)))
                elif not allow_extra_params:
                    raise MXNetError("copy_params_from: %r not aux" % name)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Return a new executor bound to new input shapes, sharing parameter
        arrays whose shapes are unchanged (ref: executor.py reshape — the
        bucketing re-bind path; jit caching makes this cheap).

        Flag semantics match the reference: without ``partial_shaping`` only
        the explicitly passed inputs may change shape — a derived (weight/
        aux) shape change raises; without ``allow_up_sizing`` a resized
        array may not grow beyond its current element count."""
        new_shapes = {}
        for n in self.arg_names:
            if n in kwargs:
                new_shapes[n] = tuple(kwargs[n])

        def _resize(name, cur, sh, explicit):
            if not (explicit or partial_shaping):
                raise MXNetError(
                    "reshape: %r changes shape %s -> %s; pass "
                    "partial_shaping=True to allow reshaping arguments "
                    "beyond the given inputs" % (name, tuple(cur.shape),
                                                 tuple(sh)))
            new_size = int(np.prod(sh)) if sh else 1
            cur_size = cur.size
            if new_size > cur_size and not allow_up_sizing:
                raise MXNetError(
                    "reshape: %r grows %d -> %d elements; pass "
                    "allow_up_sizing=True to allocate larger arrays"
                    % (name, cur_size, new_size))
            return NDArray(jnp.zeros(sh, cur.data.dtype))

        arg_shapes, _, aux_shapes = self._symbol.infer_shape_partial(**new_shapes)
        args = {}
        grads = {}
        for n, sh in zip(self.arg_names, arg_shapes):
            cur = self.arg_dict[n]
            if sh is None or tuple(cur.shape) == tuple(sh):
                args[n] = cur
                if n in self.grad_dict:
                    grads[n] = self.grad_dict[n]
            else:
                args[n] = _resize(n, cur, sh, n in kwargs)
                if n in self.grad_dict:
                    grads[n] = NDArray(jnp.zeros(sh, cur.data.dtype))
        aux = {}
        for n, sh in zip(self.aux_names, aux_shapes):
            cur = self.aux_dict[n]
            aux[n] = (cur if sh is None or tuple(cur.shape) == tuple(sh)
                      else _resize(n, cur, sh, False))
        return Executor(self._symbol, self._ctx, args, grads or None,
                        self._grad_req, aux,
                        group2ctx=(self._placement if self._placement
                                   is not None else self._group2ctx))

    @property
    def symbol(self):
        return self._symbol

    def debug_str(self):
        lines = ["Symbol outputs: %s" % ", ".join(self.output_names)]
        for node in self._nodes:
            if node.is_variable:
                lines.append("Variable:%s" % node.name)
            else:
                lines.append("Op:%s, Name=%s" % (node.op.name, node.name))
        return "\n".join(lines)


def simple_bind(symbol, ctx, grad_req="write", type_dict=None, group2ctx=None,
                shared_exec=None, **kwargs):
    """Allocate all arrays from inferred shapes then bind
    (ref: python/mxnet/symbol.py:1114 simple_bind)."""
    arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**kwargs)
    if arg_shapes is None:
        raise MXNetError("simple_bind: cannot infer shapes from %r" % kwargs)
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    # complete dtypes through the graph: one typed input (bf16 data, int32
    # label) types every parameter the way the reference's InferType pass
    # does (ref: c_api_symbolic.cc infer-type; tests/python/train/test_dtype)
    type_dict = dict(type_dict or {})
    arg_types, _out_t, aux_types = symbol.infer_type_partial(**type_dict)
    for n, t in zip(arg_names, arg_types):
        if n not in type_dict and t is not None:
            type_dict[n] = t
    aux_type_of = dict(zip(aux_names, aux_types))

    # group2ctx: allocate each group's parameters SHARDED over the mesh so
    # weight memory distributes across devices (the capacity win that
    # motivated the reference's layer-per-GPU placement)
    from .parallel import placement as _placement
    gp = _placement.resolve(group2ctx)
    pgroups = (_placement.param_groups(_topo(symbol._out_nodes()))
               if gp is not None else {})

    def _alloc(n, sh, dt):
        arr = jnp.zeros(sh, dt)
        g = pgroups.get(n)
        if g is not None:
            spec = gp.param_spec(g, sh)
            if spec is not None:
                arr = jax.device_put(
                    arr, jax.sharding.NamedSharding(gp.mesh, spec))
        return NDArray(arr)

    def _shared(pool, n, sh, dt):
        # reuse the shared executor's arrays when shape AND dtype match
        # (ref: shared_exec memory pool, graph_executor.cc:352-355,:505-512 —
        # bucketing executors share parameter storage)
        if shared_exec is not None and n in pool \
                and tuple(pool[n].shape) == tuple(sh) \
                and pool[n].dtype == dt:
            return pool[n]
        return None

    args = {}
    grads = {}
    for n, sh in zip(arg_names, arg_shapes):
        dt = np.dtype(type_dict.get(n, np.float32))
        shared = _shared(shared_exec.arg_dict if shared_exec else {}, n, sh, dt)
        args[n] = shared if shared is not None else _alloc(n, sh, dt)
        req = grad_req if isinstance(grad_req, str) else (
            grad_req[arg_names.index(n)] if isinstance(grad_req, (list, tuple))
            else grad_req.get(n, "null"))
        # integer inputs (labels, lookup ids) carry no gradient, matching
        # the reference's kNullOp for non-float storage types
        if req != "null" and np.issubdtype(dt, np.floating):
            sg = _shared(shared_exec.grad_dict if shared_exec else {}, n, sh,
                         dt)
            grads[n] = sg if sg is not None else _alloc(n, sh, dt)
    aux = {}
    for n, sh in zip(aux_names, aux_shapes):
        adt = np.dtype(aux_type_of.get(n) or np.float32)
        sa = _shared(shared_exec.aux_dict if shared_exec else {}, n, sh, adt)
        aux[n] = sa if sa is not None else NDArray(jnp.zeros(sh, adt))
    return Executor(symbol, ctx, args, grads or None, grad_req, aux,
                    group2ctx=gp if gp is not None else group2ctx,
                    shared_exec=shared_exec)
