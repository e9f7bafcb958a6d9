"""Fused training step: forward + backward + optimizer update in ONE jit.

This is the TPU-native analog of everything the reference engine pipeline did
per batch — RunOps over bulked segments, gradient reduce, updater
(ref: call stack SURVEY.md §3.1) — collapsed into a single donated XLA
computation. Module uses the lazy executor path for API fidelity; this module
is the performance path: ``Module.fit(steps_per_dispatch=k)`` (what
``benchmark/entries/`` and ``chip_smoke.py`` drive), the autotuner's trials,
the multichip dry-run, and any training loop that wants max throughput.

Sharding: pass a Mesh plus optional per-parameter PartitionSpecs. Batch
arrays are sharded along ``data``; parameters default to replicated
(pure DP — XLA inserts the gradient psum exactly where the reference ran its
CommDevice reduce) and any parameter given a spec with a ``model`` axis is
tensor-parallel sharded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError, env_str
from .executor import _build_graph_runner
from .initializer import Xavier, InitDesc
from .ndarray import NDArray
from .ops import registry as _reg
from . import optimizer as _opt
from .optimizer import Optimizer
from . import random as _random

P = jax.sharding.PartitionSpec

# rng stream offset so optimizer noise keys (SGLD) never collide with the
# graph runner's per-node fold_in(key, node_index) streams
_OPT_KEY_OFFSET = 1 << 20


class StepMetrics(object):
    """Device-resident metric accumulators for one K-step dispatch.

    Holds the packed accumulator array produced on device by
    ``TrainStep.run_steps``; the first property access performs the ONE
    host readback for the whole dispatch (and doubles as the sync point
    per-step training got from reading outputs every batch).

    Without a ``spec`` the layout is the legacy default
    ``[loss_sum, top1_correct, num_samples]``. With a
    :class:`~mxnet_tpu.metric.DeviceSumSpec` (the packed-accumulator
    protocol, docs/perf.md "Packed accumulators") the layout is the
    spec's declared slots — read them by name via :meth:`values`; the
    ``loss_sum``/``num_samples`` properties then read the spec's
    ``loss_slots`` pair (NaN / 0 when the spec declares none, which
    makes the TrainingGuard skip its loss watch rather than observe
    garbage).

    A GUARDED dispatch (``run_steps(..., guard=True)``) extends the packed
    array to ``[..., skipped, last_grad_norm]`` — the training-health
    sentinels ride back with the metric sums in the same single readback,
    and skipped (non-finite) steps are already excluded from every
    declared accumulator.
    """

    __slots__ = ("device", "guarded", "spec", "_host")

    def __init__(self, device_array, guarded=False, spec=None):
        self.device = device_array
        self.guarded = guarded
        self.spec = spec
        self._host = None

    def _vals(self):
        if self._host is None:
            self._host = np.asarray(self.device)
        return self._host

    def fetch(self):
        """Perform the dispatch's one host readback NOW (idempotent) and
        return self. The packed device array is a future: ``fit``'s
        dispatch pipeline (docs/perf.md "Host off the critical path")
        defers this call until the NEXT dispatch has been enqueued, so the
        readback stall overlaps device compute instead of serializing it."""
        self._vals()
        return self

    @property
    def fetched(self):
        """True once the host readback has happened (property access or
        :meth:`fetch`) — reading it never syncs the device."""
        return self._host is not None

    @property
    def _n_slots(self):
        return 3 if self.spec is None else len(self.spec.slots)

    def values(self):
        """Slot-name -> float dict of the dispatch's accumulated sums
        (spec layout; the legacy layout maps to loss_sum/top1_correct/
        num_samples)."""
        v = self._vals()
        if self.spec is None:
            return {"loss_sum": float(v[0]), "top1_correct": float(v[1]),
                    "num_samples": float(v[2])}
        return {s: float(v[i]) for i, s in enumerate(self.spec.slots)}

    def _loss_pair(self):
        v = self._vals()
        if self.spec is None:
            return float(v[0]), float(v[2])
        if self.spec.loss_slots is None:
            return float("nan"), 0.0
        idx = {s: i for i, s in enumerate(self.spec.slots)}
        ls, ns = self.spec.loss_slots
        return float(v[idx[ls]]), float(v[idx[ns]])

    @property
    def loss_sum(self):
        """Summed watchable loss over every sample in the dispatch (the
        spec's declared loss pair; in-scan CE on the legacy layout)."""
        return self._loss_pair()[0]

    @property
    def top1_correct(self):
        """Count of top-1 correct predictions (legacy layout only; NaN
        under a spec — read :meth:`values` by slot name instead)."""
        if self.spec is not None:
            return float("nan")
        return float(self._vals()[1])

    @property
    def num_samples(self):
        return int(round(self._loss_pair()[1]))

    @property
    def accuracy(self):
        n = self.num_samples
        return self.top1_correct / n if n else float("nan")

    @property
    def loss_avg(self):
        n = self.num_samples
        return self.loss_sum / n if n else float("nan")

    @property
    def skipped(self):
        """Count of device-side no-op (non-finite) steps in the dispatch;
        0 for an unguarded dispatch."""
        return int(self._vals()[self._n_slots]) if self.guarded else 0

    @property
    def last_grad_norm(self):
        """Global gradient norm of the dispatch's LAST step (guarded only;
        NaN/Inf when that step was the poisoned one — informative)."""
        if not self.guarded:
            return None
        return float(self._vals()[self._n_slots + 1])

    def __repr__(self):
        if self.spec is None:
            s = ("StepMetrics(loss_sum=%.6g, top1_correct=%g, "
                 "num_samples=%d"
                 % (self.loss_sum, self.top1_correct, self.num_samples))
        else:
            s = "StepMetrics(%s" % ", ".join(
                "%s=%.6g" % kv for kv in sorted(self.values().items()))
        if self.guarded:
            s += ", skipped=%d, last_grad_norm=%g" % (self.skipped,
                                                      self.last_grad_norm)
        return s + ")"


def _metric_step_sums(outs, labels, zero):
    """One step's device metric sums (CE loss, top-1 correct) over every
    (rank-2 output, rank-1 label) pair, positionally. ONE definition shared
    by the unguarded scan, the guarded scan and the guarded single step —
    they are parity-tested against each other and against host
    metric.CrossEntropy (eps 1e-8) / metric.Accuracy (argmax axis=1), so
    the accumulation must never drift between paths. ``labels`` pairs with
    ``outs`` positionally (None entries skip)."""
    loss = zero
    correct = zero
    for o, lbl in zip(outs, labels):
        if (lbl is not None and getattr(o, "ndim", 0) == 2
                and lbl.ndim == 1 and o.shape[0] == lbl.shape[0]):
            li = lbl.astype(jnp.int32)
            # take_along_axis, NOT o[arange(bs), li]: the batch dim of both
            # operand and indices stays aligned, so under a data-parallel
            # mesh GSPMD keeps the gather fully per-shard. The arange
            # fancy-index looks identical but loses that alignment and
            # lowers to THREE all-gathers inside the scan body (the
            # collective-in-scan lint pins this); on one device both forms
            # gather the same elements and are bitwise identical
            p = jnp.take_along_axis(o, li[:, None], axis=1)[:, 0] \
                .astype(jnp.float32)
            # eps pinned f32: a bare Python 1e-8 is weak-typed and would
            # promote to f64 under jax_enable_x64 (tracecheck dtype lint);
            # on the default config the pin is bitwise-identical
            loss = loss + jnp.sum(-jnp.log(p + jnp.float32(1e-8)))
            correct = correct + jnp.sum(
                (jnp.argmax(o, axis=1).astype(jnp.int32) == li)
                .astype(jnp.float32))
    return loss, correct


def _stable_sig(sig):
    """Project a spec signature onto run-to-run-stable atoms for program
    NAMING (the jit cache itself keys on the raw signature): function
    objects — a CustomMetric's step_sums — repr with their memory
    address, so they collapse to their qualname here."""
    if isinstance(sig, tuple):
        return tuple(_stable_sig(s) for s in sig)
    if isinstance(sig, (str, int, float, bool)) or sig is None:
        return sig
    return getattr(sig, "__qualname__", type(sig).__name__)


def _default_slot_sums(outs, labels, batch_size):
    """The legacy packed layout ``(ce_loss, top1_correct, num_samples)``
    as a slot tuple — what ``run_steps`` accumulates when no
    packed-accumulator spec is passed (TrainStep API users, the autotuner,
    the multichip gate). Bit-for-bit the pre-protocol scan accumulation."""
    zero = jnp.zeros((), jnp.float32)
    loss, correct = _metric_step_sums(outs, labels, zero)
    return (loss, correct, jnp.float32(batch_size))


def _with_guard_loss(spec, batch_size):
    """Augment a packed-accumulator spec that declares NO watchable loss
    pair with two hidden slots — the in-scan CE loss and sample count the
    TrainingGuard's divergence EMA has always observed. The metric's own
    fold never sees the hidden slots; ``StepMetrics.loss_sum`` and the
    guard do."""
    from .metric import DeviceSumSpec
    if spec is None or spec.loss_slots is not None:
        return spec
    base_slots = spec.slots
    base_step = spec.step_sums
    base_fold = spec.fold

    def step_sums(outs, labels):
        vals = tuple(base_step(outs, labels))
        zero = jnp.zeros((), jnp.float32)
        loss, _ = _metric_step_sums(outs, labels, zero)
        return vals + (loss, jnp.float32(batch_size))

    def fold(metric, values):
        base_fold(metric, {s: values[s] for s in base_slots})

    return DeviceSumSpec(
        base_slots + ("__guard_loss", "__guard_n"), step_sums, fold,
        ("guardloss",) + (spec.signature if isinstance(spec.signature,
                                                       tuple)
         else (spec.signature,)),
        loss_slots=("__guard_loss", "__guard_n"), tag=spec.tag)


class TrainStep(object):
    """Compiled train step over a symbol.

    state = {params, aux, opt, step}; ``step(state, batch)`` returns
    (new_state, outputs) and donates the old state buffers.

    ``optimizer`` may be a registry name (created with learning_rate /
    momentum / wd) or an Optimizer instance — any optimizer in the zoo with
    ``fused_supported`` works, including lr_mult/wd_mult from symbol attrs
    and an lr_scheduler (evaluated host-side per step, fed in as a traced
    scalar so schedules never retrace).
    """

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), optimizer="sgd",
                 learning_rate=0.01, momentum=0.9, wd=0.0, rescale_grad=None,
                 mesh=None, param_shardings=None, dtype=np.float32,
                 compute_dtype=None, remat=False, frozen_param_names=None,
                 group2ctx=None):
        self.symbol = symbol
        self.data_names = list(data_names)
        self.label_names = list(label_names)
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.param_names = [n for n in self.arg_names
                            if n not in self.data_names + self.label_names]
        self.frozen_param_names = set(frozen_param_names or ())
        if isinstance(optimizer, Optimizer):
            self._opt = optimizer
            # an instance's rescale_grad is authoritative (even 1.0): the
            # imperative updater applies it verbatim, so the fused path must
            # too; the 1/batch_size default exists only for the
            # string-optimizer convenience constructor. A left-at-default
            # 1.0 almost always means batch-SUMMED gradients at full lr —
            # warn like Module.init_optimizer does (ref: module.py:460-463)
            if rescale_grad is None:
                rescale_grad = optimizer.rescale_grad
                if rescale_grad == 1.0:
                    import logging
                    logging.warning(
                        "TrainStep: optimizer instance has rescale_grad=1.0 "
                        "(gradients are batch sums); pass "
                        "rescale_grad=1/batch_size to the optimizer or to "
                        "TrainStep if per-example scaling is intended")
        else:
            kwargs = {"learning_rate": learning_rate, "wd": wd,
                      "sym": symbol}
            if optimizer.lower() in ("sgd", "nag", "ccsgd", "dcasgd"):
                kwargs["momentum"] = momentum
            self._opt = _opt.create(optimizer, **kwargs)
        if not self._opt.fused_supported:
            raise MXNetError("fused step: optimizer %r has no fused update"
                             % type(self._opt).__name__)
        self.optimizer = optimizer
        self.rescale_grad = rescale_grad
        self.mesh = mesh
        self.param_shardings = dict(param_shardings or {})
        self.dtype = np.dtype(dtype)
        # MXTPU_BF16_STATS (docs/perf.md next-steps item 2): store the
        # NON-parameter state in bf16 — any truthy value keeps BatchNorm
        # moving stats (aux states) in bf16; "opt"/"all" additionally
        # keeps optimizer state (momentum/Adam moments) in bf16. Halves
        # the non-param state traffic on a bandwidth-bound chip; params
        # keep f32 masters (bf16 params measured -12%, docs/perf.md r5).
        # Checkpoints still serialize f32 (bf16->f32->bf16 is exact), so
        # resume stays bitwise and save formats are unchanged.
        _bf16 = env_str("MXTPU_BF16_STATS").lower()
        self.bf16_stats = _bf16 not in ("", "0", "false", "off", "no")
        self.bf16_opt = _bf16 in ("opt", "all", "full")
        if compute_dtype is not None:
            self.compute_dtype = np.dtype(compute_dtype)
        elif self.dtype != np.dtype(np.float32):
            # params stored in a non-f32 dtype: batch inputs must be cast to
            # match (lax.conv requires equal dtypes), so the storage dtype IS
            # the compute dtype
            self.compute_dtype = self.dtype
        else:
            self.compute_dtype = None
        # ctx_group model parallelism: lower group annotations to sharding
        # constraints inside the step, and default each grouped parameter's
        # sharding from its group spec (explicit param_shardings win)
        from .parallel import placement as _placement
        self._placement = _placement.resolve(group2ctx, mesh)
        self._run, self._nodes = _build_graph_runner(symbol, self._placement)
        if self._placement is not None:
            if self.mesh is None:
                self.mesh = self._placement.mesh
            pgroups = _placement.param_groups(self._nodes)
            self._auto_group_params = {
                n: g for n, g in pgroups.items() if n in self.param_names
                and n not in self.param_shardings}
        else:
            self._auto_group_params = {}
        self._needs_rng = any((not n.is_variable) and n.op.needs_rng
                              for n in self._nodes)
        self.remat = remat
        if remat:
            self._run = self._wrap_remat(self._run)
        self._jit = {}  # keyed by batch size (rescale_grad depends on it)
        self._jit_scan = {}  # keyed by (batch_size, k) — see run_steps
        # guarded variants live in SEPARATE caches: enabling the guard must
        # never retrace (or change the jaxpr of) the unguarded fast path
        self._jit_g = {}
        self._jit_scan_g = {}
        self._base_key = None  # drawn lazily from the global seeded stream
        self._static_key = None  # cached no-rng key (one H2D, not per-step)
        # tracecheck runtime hooks (docs/static_analysis.md): every jit
        # cache entry registers with the program registry so the guard-on /
        # guard-off / scan program set is auditable as a unit, and every
        # dispatch records its call signature so an unexpected cache miss
        # logs (or raises, MXTPU_TRACECHECK=error) the cache-key diff
        self._watcher = None
        self.health = None  # per-run TrainingHealth (Module attaches it)
        # elastic dist training (docs/robustness.md): Module attaches the
        # kvstore's ring reducer here; the step then sums gradients across
        # worker processes through an ordered host callback INSIDE the
        # compiled program (so the K-step scan keeps its bulk dispatch).
        # Donation is disabled in dist mode: a dispatch that dies in the
        # ring must leave the input state buffers valid for the re-form.
        self.dist_reduce = None
        self.dist_error = None
        self.donate = True

    # ------------------------------------------------------------------
    def _ambient(self):
        """Ambient-mesh scope for jit trace/dispatch. Ops that dispatch on
        ``parallel.mesh.current_mesh()`` (MultiHeadAttention's 'seq' modes,
        TransformerStack's 'pipe' schedule) must see THIS TrainStep's mesh
        while the program traces; entering the scope on every dispatch
        keeps the first (tracing) call and steady-state calls identical,
        so the multi-axis program never depends on the caller remembering
        a ``with MeshScope(...)`` around ``fit``."""
        if self.mesh is None:
            import contextlib
            return contextlib.nullcontext()
        from .parallel.mesh import MeshScope
        return MeshScope(self.mesh)

    # ------------------------------------------------------------------
    def _wrap_remat(self, run):
        """Memory mirroring: recompute activations in backward
        (ref: MXNET_BACKWARD_DO_MIRROR, graph_executor.cc:213-226).

        remat=True: a single jax.checkpoint over the whole forward (minimum
        memory, full recompute). remat="conv": save only Convolution /
        FullyConnected outputs (the ``conv_out``/``fc_out`` checkpoint_name
        anchors in ops/nn.py) and recompute the elementwise chain between
        them (BN normalize, ReLU, pad/pool) in backward — on a
        bandwidth-bound chip this trades cheap VPU FLOPs for one fewer
        HBM round-trip per saved activation."""
        if self.remat == "conv":
            policy = jax.checkpoint_policies.save_only_these_names(
                "conv_out", "fc_out")
        else:
            policy = None

        def wrapped(arg_vals, aux_vals, key, is_train):
            def inner(arg_vals):
                return run(arg_vals, aux_vals, key, is_train)
            return jax.checkpoint(inner, policy=policy)(arg_vals)
        return wrapped

    # ------------------------------------------------------------------
    def init(self, data_shapes, label_shapes=None, initializer=None, seed=0):
        """Allocate and initialize state from inferred shapes.

        Runs under ``jax.transfer_guard("allow")``: init is setup, not the
        dispatch hot loop — host-to-device transfers are its job. The
        tracecheck runtime contract (``tracecheck``-marked tests under
        ``transfer_guard("disallow")``, docs/static_analysis.md) polices
        the per-dispatch path only."""
        with jax.transfer_guard("allow"):
            return self._init(data_shapes, label_shapes, initializer, seed)

    def _init(self, data_shapes, label_shapes, initializer, seed):
        shapes = dict(data_shapes)
        shapes.update(label_shapes or {})
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**shapes)
        shape_of = dict(zip(self.arg_names, arg_shapes))
        aux_shape_of = dict(zip(self.aux_names, aux_shapes))
        initializer = initializer or Xavier()
        attrs = self.symbol.attr_dict()
        # scoped seeding: deterministic init draws WITHOUT clobbering the
        # process-global stream (mx.random.seed set by the user must keep
        # governing dropout/SGLD keys drawn later in step())
        saved = _random.get_state()
        _random.seed(seed)
        try:
            params = {}
            for n in self.param_names:
                arr = NDArray(jnp.zeros(shape_of[n], self.dtype))
                initializer(InitDesc(n, attrs.get(n, {})), arr)
                params[n] = arr.data
            aux = {}
            for n in self.aux_names:
                arr = NDArray(jnp.zeros(aux_shape_of[n], self.dtype))
                initializer(InitDesc(n, attrs.get(n, {})), arr)
                aux[n] = arr.data
        finally:
            _random.set_state(saved)
        opt = self._init_opt_state(params)
        state = {"params": params, "aux": self.cast_stats(aux), "opt": opt,
                 "step": jnp.zeros((), jnp.int32)}
        if self.mesh is not None:
            state = self._shard_state(state)
        return state

    def cast_stats(self, aux):
        """MXTPU_BF16_STATS: aux (BatchNorm moving stats) storage cast —
        identity when the knob is off."""
        if not self.bf16_stats:
            return aux
        return {n: (v.astype(jnp.bfloat16)
                    if jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)
                    else v)
                for n, v in aux.items()}

    def cast_opt_state(self, opt):
        """MXTPU_BF16_STATS=opt|all: optimizer-state storage cast —
        identity when the knob is off."""
        if not self.bf16_opt:
            return opt
        return jax.tree_util.tree_map(
            lambda v: (v.astype(jnp.bfloat16)
                       if jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)
                       else v), opt)

    def _init_opt_state(self, params):
        return self.cast_opt_state(
            {n: self._opt.create_fused_state(v)
             for n, v in params.items()
             if n not in self.frozen_param_names})

    # ------------------------------------------------------------------
    def _param_spec(self, name, shape=None):
        if name in self.param_shardings:
            return self.param_shardings[name]
        g = self._auto_group_params.get(name)
        if g is not None and shape is not None:
            spec = self._placement.param_spec(g, tuple(shape))
            if spec is not None:
                return spec
        return P()

    def _shard_state(self, state):
        mesh = self.mesh
        # multi-host mesh: device_put cannot target non-addressable devices;
        # assemble global arrays from (identical) per-process host copies
        from .parallel.mesh import (is_multiprocess, host_to_global,
                                    host_broadcast0)
        if is_multiprocess(mesh):
            def put(v, spec):
                if spec == P():
                    # replicated state must be CONSISTENT across workers
                    # even if their host copies diverged (e.g. per-rank
                    # seeding): rank 0's copy is authoritative, like the
                    # reference server's single stored weight
                    v = host_broadcast0(mesh, v)
                return host_to_global(mesh, spec, v)
        else:
            def put(v, spec):
                return jax.device_put(
                    v, jax.sharding.NamedSharding(mesh, spec))

        def put_params(tree):
            return {n: put(v, self._param_spec(n, v.shape))
                    for n, v in tree.items()}

        out = dict(state)
        out["params"] = put_params(state["params"])
        # optimizer state pytrees shard exactly like their weight
        out["opt"] = {
            n: jax.tree_util.tree_map(
                lambda v, _n=n: put(v, self._param_spec(_n, v.shape)), st)
            for n, st in state["opt"].items()}
        out["aux"] = {n: put(v, P()) for n, v in state["aux"].items()}
        out["step"] = put(state["step"], P())
        return out

    def shard_batch(self, batch):
        """Place batch arrays with dim-0 sharded along the data axis; when
        the mesh also has a 'seq' axis, dim-1 of rank>=2 arrays is sharded
        along it (sequence/context parallelism — the token dim feeds the
        ring/Ulysses attention shards).

        On a multi-host mesh each process passes its LOCAL batch shard and
        the global batch is their concatenation — the dist_sync data
        partition (ref: kvstore num_workers/rank feeding ImageRecordIter
        part_index/num_parts)."""
        if self.mesh is None:
            return batch
        from .parallel.mesh import (is_multiprocess, host_to_global,
                                    data_axis_size, AXIS_SEQ)
        has_seq = AXIS_SEQ in self.mesh.axis_names
        bax = "data" if "data" in self.mesh.axis_names else None
        if bax is not None:
            n = data_axis_size(self.mesh)
            for k, v in batch.items():
                b = (v.shape if hasattr(v, "shape")
                     else np.asarray(v).shape)[0]
                if b % n:
                    raise MXNetError(
                        "shard_batch: %r batch dim %d does not divide the "
                        "%d-way 'data' mesh axis — pad the batch or pick a "
                        "divisible batch size" % (k, b, n))
        if has_seq:
            sp = data_axis_size(self.mesh, AXIS_SEQ)
            for k, v in batch.items():
                shp = (v.shape if hasattr(v, "shape")
                       else np.asarray(v).shape)
                if len(shp) >= 2 and shp[1] % sp:
                    raise MXNetError(
                        "shard_batch: %r sequence dim %d does not divide "
                        "the %d-way 'seq' mesh axis — pad the sequence or "
                        "pick a divisible seq_len" % (k, shp[1], sp))

        def spec_for(v):
            nd = getattr(v, "ndim", None)
            if nd is None:
                nd = np.asarray(v).ndim
            if has_seq and nd >= 2:
                return P(bax, AXIS_SEQ)
            return P(bax)

        if is_multiprocess(self.mesh):
            return {k: host_to_global(self.mesh, spec_for(v), v)
                    for k, v in batch.items()}
        return {k: jax.device_put(
            jnp.asarray(v),
            jax.sharding.NamedSharding(self.mesh, spec_for(v)))
            for k, v in batch.items()}

    # ------------------------------------------------------------------
    def _make_step_fn(self, batch_size, guard=False):
        """The fused fwd+bwd+update body, shared verbatim by the single-step
        jit (``step``) and the K-step ``lax.scan`` dispatch (``run_steps``)
        so both paths compute identical numbers.

        ``guard=True`` (docs/robustness.md "Numerical guardrails") adds
        on-device training-health sentinels: a global gradient norm and an
        all-finite flag over loss+grads (``jnp.isfinite`` reductions), and
        makes the update GUARDED — when the flag is false every
        param/opt/aux/step write ``jnp.where``-selects the old value, so the
        poisoned step is a device-side no-op (no ``lax.cond`` host
        round-trip). The guarded step_fn takes an extra traced ``poison``
        scalar (0.0 normally; NaN when the ``guard.grad_nan`` fault site
        fires) and returns ``(new_state, outs, (ok, grad_norm))``. With
        ``guard=False`` the trace is byte-for-byte the unguarded body — no
        sentinel ops, no retrace, jaxpr unchanged.

        An optimizer ``clip_global_norm`` is applied here across ALL
        parameter gradients at once (after rescale, before the per-optimizer
        elementwise ``clip_gradient``), reusing the same norm reduction as
        the sentinel."""
        run = self._run
        optzr = self._opt
        param_names = list(self.param_names)
        updated = [n for n in param_names if n not in self.frozen_param_names]
        rescale = (self.rescale_grad if self.rescale_grad is not None
                   else 1.0 / batch_size)
        compute_dtype = self.compute_dtype
        needs_key = getattr(optzr, "fused_needs_key", False)
        # per-parameter lr/wd multipliers resolved by name, matching
        # Optimizer._get_lr/_get_wd (ref: python/mxnet/optimizer.py)
        lr_mult = {n: optzr.lr_mult.get(n, 1.0) for n in updated}
        wd_mult = {n: optzr.wd_mult.get(n, 1.0) for n in updated}
        wd = optzr.wd
        clip_norm = getattr(optzr, "clip_global_norm", None)
        bf16_opt = self.bf16_opt

        def step_fn(state, batch, key, lr_base, poison=None):
            params, aux, opt = state["params"], state["aux"], state["opt"]
            # fold the state's OWN step counter into the key (traced, so no
            # host sync): restoring a checkpointed state reproduces the
            # dropout/SGLD noise stream implied by its step count, and two
            # states interleaved through one TrainStep never share noise
            key = jax.random.fold_in(key, state["step"].astype(jnp.uint32))

            def f(p):
                arg_vals = dict(batch)
                if compute_dtype is not None:
                    arg_vals = {
                        k: (v.astype(compute_dtype)
                            if jnp.issubdtype(v.dtype, jnp.floating) else v)
                        for k, v in arg_vals.items()}
                    p = {k: v.astype(compute_dtype) for k, v in p.items()}
                arg_vals.update(p)
                outs, aux_up = run(arg_vals, aux, key, True)
                return outs, aux_up

            # forward / backward / update: the names an operator searches
            # the device trace of the step (and of the K-step scan) for
            with jax.named_scope("forward"):
                (outs, aux_up), vjp_fn = jax.vjp(f, params)
            with jax.named_scope("backward"):
                cots = [jnp.ones_like(o) for o in outs]
                cots_aux = jax.tree_util.tree_map(jnp.zeros_like, aux_up)
                (grads,) = vjp_fn((cots, cots_aux))
            with jax.named_scope("update"):
                new_state, ok, gnorm = update(state, grads, outs, aux_up,
                                              key, lr_base, poison)
            if guard:
                return new_state, outs, (ok, gnorm)
            return new_state, outs

        def update(state, grads, outs, aux_up, key, lr_base, poison):
            params, aux, opt = state["params"], state["aux"], state["opt"]
            t = state["step"].astype(jnp.float32) + jnp.float32(1.0)
            gs = {n: grads[n].astype(params[n].dtype) * rescale
                  for n in updated}
            if poison is not None:
                # guard.grad_nan fault site: poison is 0.0 on clean steps
                # (identity) and NaN on the injected one — always threaded
                # through the guarded trace so faulted and unfaulted guarded
                # runs share ONE compiled program
                gs = {n: g + poison.astype(g.dtype) for n, g in gs.items()}
            if self.dist_reduce is not None:
                # cross-process sum AFTER the local poison (a poisoned
                # worker poisons every replica, so guarded skips stay
                # bitwise-identical) and BEFORE gnorm/clip/guard, which
                # must see the GLOBAL gradient
                gs = self._cross_grad_reduce(gs, updated)
            gnorm = None
            if guard or clip_norm is not None:
                gnorm = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in gs.values()))
            if clip_norm is not None:
                scale = jnp.minimum(
                    jnp.float32(1.0),
                    jnp.float32(clip_norm)
                    / jnp.maximum(gnorm, jnp.float32(1e-12)))
                gs = {n: g * scale.astype(g.dtype) for n, g in gs.items()}
            ok = None
            if guard:
                # all-finite over loss+grads: outputs feed the in-scan loss,
                # and any non-finite forward poisons the grads anyway
                flags = [jnp.all(jnp.isfinite(g)) for g in gs.values()]
                flags += [jnp.all(jnp.isfinite(o)) for o in outs]
                ok = flags[0]
                for fl in flags[1:]:
                    ok = jnp.logical_and(ok, fl)
            new_params = dict(params)
            new_opt = {}
            for i, n in enumerate(updated):
                w = params[n]
                g = gs[n]
                subkey = (jax.random.fold_in(key, _OPT_KEY_OFFSET + i)
                          if needs_key else None)
                new_w, new_s = optzr.fused_update(
                    n, w, g, opt[n], lr_base * lr_mult[n], wd * wd_mult[n],
                    t, key=subkey)
                if bf16_opt:
                    # bf16 optimizer state: the update computes in the
                    # promoted dtype, storage goes back to bf16 — BEFORE
                    # the guard select (the scan carry dtype must not
                    # change step-to-step)
                    new_s = jax.tree_util.tree_map(
                        lambda a, b: a.astype(b.dtype), new_s, opt[n])
                if guard:
                    new_w = jnp.where(ok, new_w, w)
                    new_s = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(ok, a, b), new_s, opt[n])
                new_params[n] = new_w
                new_opt[n] = new_s
            new_aux = dict(aux)
            for k, v in aux_up.items():
                nv = v.astype(aux[k].dtype)
                if guard:
                    nv = jnp.where(ok, nv, aux[k])
                new_aux[k] = nv
            # a skipped step is a FULL no-op: the step counter (and with it
            # the dropout/SGLD noise stream) does not advance either
            step_inc = ok.astype(jnp.int32) if guard else 1
            new_state = {"params": new_params, "aux": new_aux,
                         "opt": new_opt, "step": state["step"] + step_inc}
            return self._pin_state_sharding(new_state), ok, gnorm

        return step_fn

    def _cross_grad_reduce(self, gs, updated):
        """Sum the update set's gradients across worker processes inside
        the traced step: flatten to ONE f32 vector, hop to the host
        through an ordered ``io_callback`` for the control-plane ring
        allreduce, unflatten. One callback per step regardless of
        parameter count, and it composes with the K-step ``lax.scan`` —
        the bulked dispatch makes K ring exchanges without returning to
        Python. A lost worker cannot raise through XLA: the callback
        stashes the error on the TrainStep, returns NaN (a guarded step
        no-ops on it), and :meth:`_dist_sync_result` re-raises after the
        dispatch."""
        from jax.experimental import io_callback
        names = list(updated)
        if not names:
            return gs
        flat = jnp.concatenate([gs[n].astype(jnp.float32).reshape(-1)
                                for n in names])

        def host_sum(v):
            try:
                out = self.dist_reduce(np.asarray(v, np.float32))
                return np.asarray(out, np.float32).reshape(v.shape)
            except Exception as e:
                self.dist_error = e
                return np.full(v.shape, np.nan, np.float32)

        sds = jax.ShapeDtypeStruct(flat.shape, jnp.float32)
        kwargs = {}
        if self.mesh is not None and self.mesh.devices.size > 1:
            # pin the callback to one device so a multi-device local mesh
            # performs ONE ring exchange per step, not one per device
            kwargs["sharding"] = jax.sharding.SingleDeviceSharding(
                self.mesh.devices.ravel()[0])
        try:
            red = io_callback(host_sum, sds, flat, ordered=True, **kwargs)
        except TypeError:           # older jax: no sharding kwarg
            red = io_callback(host_sum, sds, flat, ordered=True)
        out = {}
        off = 0
        for n in names:
            size = int(np.prod(gs[n].shape)) if gs[n].shape else 1
            out[n] = (red[off:off + size].reshape(gs[n].shape)
                      .astype(gs[n].dtype))
            off += size
        return out

    def _dist_sync_result(self, out):
        """Dist-mode dispatch epilogue: block on the results and re-raise
        any error the ring callback stashed (WorkerLostError surfaces
        HERE, with the pre-dispatch state still intact — donation is off
        in dist mode). Single-process: identity, no block."""
        if self.dist_reduce is None:
            return out
        jax.block_until_ready(out)
        err, self.dist_error = self.dist_error, None
        if err is not None:
            raise err
        return out

    def _pin_state_sharding(self, state):
        """Constrain the OUTPUT state to the same shardings ``_shard_state``
        placed the input with. Without the pin, GSPMD is free to return the
        state under whatever sharding its solver picked for a multi-axis
        mesh — then dispatch 2's argument shardings differ from dispatch
        1's and the jit cache misses once (a retrace tracecheck rightly
        flags). Pinning closes the loop: state out == state in, every
        dispatch hits the first compile."""
        if self.mesh is None:
            return state
        from jax.sharding import NamedSharding, PartitionSpec as P

        def con(v, spec):
            return jax.lax.with_sharding_constraint(
                v, NamedSharding(self.mesh, spec))

        out = dict(state)
        out["params"] = {n: con(v, self._param_spec(n, v.shape))
                         for n, v in state["params"].items()}
        out["opt"] = {
            n: jax.tree_util.tree_map(
                lambda v, _n=n: con(v, self._param_spec(_n, v.shape)), st)
            for n, st in state["opt"].items()}
        out["aux"] = {n: con(v, P()) for n, v in state["aux"].items()}
        out["step"] = con(state["step"], P())
        return out

    def _state_out_shardings(self, state):
        """Prefix pytree of jit ``out_shardings`` for the state: params and
        optimizer state pinned to their placement spec (one spec per param
        covers its whole opt-state subtree), aux/step replicated — exactly
        what ``_shard_state`` placed the inputs with. The in-body
        ``_pin_state_sharding`` constraint alone does not survive the
        scan-carry unification on every backend (jax 0.4.x may hand back
        solver-chosen shardings from the While root), and an unpinned
        output misses the jit cache on the next dispatch. ``None`` when no
        mesh (and for the non-state outputs: propagation decides)."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding

        def ns(spec):
            return NamedSharding(self.mesh, spec)

        return {
            "params": {n: ns(self._param_spec(n, v.shape))
                       for n, v in state["params"].items()},
            "opt": {n: ns(self._param_spec(n, state["params"][n].shape))
                    for n in state["opt"]},
            "aux": {n: ns(P()) for n in state["aux"]},
            "step": ns(P()),
        }

    def _build(self, batch_size, state=None):
        outs = None
        if state is not None and self.mesh is not None:
            outs = (self._state_out_shardings(state), None)
        return jax.jit(self._make_step_fn(batch_size),
                       donate_argnums=(0,) if self.donate else (),
                       out_shardings=outs)

    def _build_guard_step(self, batch_size, state=None):
        """Guarded single-step jit: the fused body plus device sentinels,
        returning ``(new_state, outs, packed)`` where ``packed`` is the same
        ``[loss, correct, nsamp, skipped, grad_norm]`` layout the guarded
        scan accumulates (zeros for a skipped step, so metric consumers
        exclude it without a second readback)."""
        step_fn = self._make_step_fn(batch_size, guard=True)
        label_names = list(self.label_names)

        def fn(state, batch, key, lr, poison):
            new_st, outs, (ok, gnorm) = step_fn(state, batch, key, lr,
                                                poison)
            zero = jnp.zeros((), jnp.float32)
            loss, correct = _metric_step_sums(
                outs, [batch.get(n) for n in label_names], zero)
            okf = ok.astype(jnp.float32)
            packed = jnp.stack([
                jnp.where(ok, loss, zero), jnp.where(ok, correct, zero),
                okf * jnp.float32(batch_size), jnp.float32(1.0) - okf,
                gnorm.astype(jnp.float32)])
            return new_st, outs, packed

        outs_sh = None
        if state is not None and self.mesh is not None:
            outs_sh = (self._state_out_shardings(state), None, None)
        return jax.jit(fn, donate_argnums=(0,) if self.donate else (),
                       out_shardings=outs_sh)

    def _build_scan(self, batch_size, k, guard=False, metric_spec=None,
                    state=None):
        """K steps in ONE compiled dispatch: lax.scan of the fused step body
        over a stacked (k, batch, ...) superbatch, state donated across the
        whole scan. This is the reference engine's bulking — whole graph
        segments per engine dispatch (SURVEY.md §3.1) — applied to the train
        loop itself: Python dispatch and host readback amortize over K steps.

        Metric accumulators are carried through the scan so metrics cross
        the host boundary once per K steps. Without ``metric_spec`` the
        legacy layout (CE loss sum, top-1 correct count, sample count)
        pairs each rank-2 output with its label by position, matching
        metric.CrossEntropy (eps 1e-8) / metric.Accuracy (argmax axis=1)
        bit-for-bit over the same outputs. With a
        :class:`~mxnet_tpu.metric.DeviceSumSpec` (packed-accumulator
        protocol, docs/perf.md "Packed accumulators") the carry holds the
        spec's declared slots instead — any metric that declares a layout
        rides the same one-readback-per-K contract.

        ``guard=True`` threads the training-health sentinels through the
        scan: a per-step NaN poison vector rides in next to ``lrs``, skipped
        (non-finite) steps are excluded from every accumulator slot, and
        the packed result grows to ``[slots..., skipped, last_grad_norm]``
        — sentinels ride back with the metric sums in the SAME single
        readback. The ``guard=False`` trace is unchanged.
        """
        step_fn = self._make_step_fn(batch_size, guard=guard)
        label_names = list(self.label_names)
        spec = metric_spec
        if spec is not None:
            nslots = len(spec.slots)

            def slot_sums(outs, labels):
                return tuple(spec.step_sums(outs, labels))
        else:
            nslots = 3

            def slot_sums(outs, labels):
                return _default_slot_sums(outs, labels, batch_size)

        def scan_fn(state, superbatch, key, lrs, poisons=None):
            zero = jnp.zeros((), jnp.float32)

            def body(carry, xs):
                if guard:
                    st, accs = carry
                    slots, skipped, gnorm = \
                        accs[:nslots], accs[nslots], accs[nslots + 1]
                    batch, lr, poison = xs
                    new_st, outs, (ok, g_norm) = step_fn(st, batch, key, lr,
                                                         poison)
                else:
                    st, slots = carry
                    batch, lr = xs
                    new_st, outs = step_fn(st, batch, key, lr)
                step_vals = slot_sums(
                    outs, [batch.get(n) for n in label_names])
                if guard:
                    # skipped steps drop out of every accumulator: the
                    # metric denominators never see the poisoned batch
                    slots = tuple(a + jnp.where(ok, v, zero)
                                  for a, v in zip(slots, step_vals))
                    skipped = skipped + jnp.where(ok, zero, jnp.float32(1))
                    return (new_st, slots + (skipped,
                                             g_norm.astype(jnp.float32))), \
                        None
                slots = tuple(a + v for a, v in zip(slots, step_vals))
                return (new_st, slots), None

            if guard:
                zeros = tuple(zero for _ in range(nslots + 2))
                (state, accs), _ = jax.lax.scan(
                    body, (state, zeros), (superbatch, lrs, poisons))
                return state, jnp.stack(list(accs))
            zeros = tuple(zero for _ in range(nslots))
            (state, slots), _ = jax.lax.scan(
                body, (state, zeros), (superbatch, lrs))
            # one packed array => one host transfer for all K-step metrics
            return state, jnp.stack(list(slots))

        outs_sh = None
        if state is not None and self.mesh is not None:
            outs_sh = (self._state_out_shardings(state), None)
        return jax.jit(scan_fn, donate_argnums=(0,) if self.donate else (),
                       out_shardings=outs_sh)

    def _dispatch_key(self):
        if self._needs_rng or getattr(self._opt, "fused_needs_key", False):
            # base key rides the global seeded stream (mx.random.seed), so
            # dropout/SGLD respond to seeding and two TrainSteps never share
            # noise; per-step keys fold in the step counter
            if self._base_key is None:
                with jax.transfer_guard("allow"):  # one-time key creation
                    self._base_key = _random.split()
            return self._base_key  # per-step variation folds in state["step"]
        if self._static_key is None:
            # cached: creating a fresh key would cost an (implicit) H2D
            # per dispatch — the transfer-guard runtime lint flags exactly
            # this pattern inside the hot loop
            with jax.transfer_guard("allow"):
                self._static_key = jax.random.key(0)
        return self._static_key  # static; unused ops ignore it

    def _next_lr(self):
        # scheduler clock advances host-side; lr rides in as a traced scalar
        self._opt.num_update += 1
        if self._opt.lr_scheduler is not None:
            return self._opt.lr_scheduler(self._opt.num_update)
        return self._opt.lr

    def _poison_scalars(self, k):
        """Host-side ``guard.grad_nan`` firing, one shot per TRAINING step:
        a (k,) float32 of 0.0 (clean) / NaN (poisoned) that rides into the
        guarded trace (docs/robustness.md "Numerical guardrails")."""
        from . import faults as _faults
        return np.asarray(
            [float("nan") if _faults.fire_flag("guard.grad_nan") else 0.0
             for _ in range(k)], np.float32)

    def _tc_after(self, kind, cache_key, jitfn, call_args, result=None,
                  spec=None):
        """tracecheck runtime hook (docs/static_analysis.md), called right
        after a watched jit call: registers the program with the analyzer's
        registry (first call per cache entry — the guard-on / guard-off /
        scan program set is auditable as a unit via
        ``tracecheck.check_registered``) and feeds the call signature to the
        per-TrainStep retrace watcher, so an unexpected jit-cache miss logs
        — or raises under ``MXTPU_TRACECHECK=error`` — a diff naming the
        offending argument. Signature/struct capture is metadata-only
        (shape/dtype/weak-type), so the donated state buffers are safe to
        sign post-call; the dispatch is already enqueued, so this host work
        overlaps device compute."""
        from . import tracecheck as _tc
        if not _tc.enabled():
            return
        if self._watcher is None:
            # names are process-unique (tracecheck.make_watcher): two
            # TrainSteps over same-named symbols must not collide in the
            # program registry, or the second instance's programs would
            # never register and check_registered would silently audit the
            # wrong instance's program set
            self._watcher = _tc.make_watcher(
                "TrainStep(%s)" % (self.symbol.name,))
        if isinstance(cache_key, tuple):
            key = "%s[bs=%d,k=%d]" % (kind, cache_key[0], cache_key[1])
            if len(cache_key) > 2:
                # spec-keyed scan (packed-accumulator protocol): the
                # metric tag (+ signature digest — two eps variants of
                # one metric are distinct programs) keeps same-shape
                # programs with different packed layouts distinct in the
                # registry. crc32 over a STABILIZED repr, NOT hash():
                # tuple hashes are PYTHONHASHSEED-salted, and a raw repr
                # of a CustomMetric signature would embed its function
                # object's memory address — either way a run-to-run-
                # unstable program name silently unpins name-matched
                # suppressions and drifts committed baselines
                import zlib
                tag = spec.tag if spec is not None else "spec"
                key = "%s[bs=%d,k=%d,m=%s.%04x]" % (
                    kind, cache_key[0], cache_key[1], tag,
                    zlib.crc32(repr(_stable_sig(cache_key[2]))
                               .encode()) & 0xffff)
        else:
            key = "%s[bs=%d]" % (kind, cache_key)
        name = "%s/%s" % (self._watcher.name, key)
        if name not in _tc.PROGRAMS:
            _tc.register_program(name, jitfn, call_args,
                                 donate_argnums=(0,))
            if self.mesh is not None:
                # MXTPU_COMMSCHECK (docs/static_analysis.md
                # "Communication lints"): one-time collective audit of a
                # freshly compiled SHARDED program — off by default; warn/
                # error pay one extra compile at the first dispatch. The
                # call args are reduced to sharded structs inside, so the
                # just-donated state buffers are never read.
                from . import commscheck as _cc
                trips = (cache_key[1] if isinstance(cache_key, tuple)
                         else 1)
                _cc.maybe_audit_dispatch(name, jitfn, call_args,
                                         loop_trips=trips, mesh=self.mesh)
            # MXTPU_FLOPCHECK (docs/static_analysis.md "Roofline
            # lints"): one-time roofline audit of every freshly compiled
            # program (single-device too — a fusion regression needs no
            # mesh to hurt); same struct-args discipline as above.
            from . import flopcheck as _fc
            _fc.maybe_audit_dispatch(
                name, jitfn, call_args,
                loop_trips=(cache_key[1] if isinstance(cache_key, tuple)
                            else 1),
                mesh=self.mesh)
        try:
            self._watcher.after_call(key, jitfn, _tc.signature(call_args),
                                     health=self.health)
        except _tc.RetraceError as e:
            # the dispatch already ran and donated the old state: hand the
            # new state to the caller through the exception so it never
            # holds a reference to deleted buffers
            e.result = result
            raise

    def step(self, state, batch, guard=False):
        """One fused train step. ``batch``: dict name -> array.

        ``guard=True`` runs the guarded body (non-finite steps become
        device-side no-ops) and returns ``(new_state, outputs, packed)``
        where ``packed`` is the ``[loss, correct, nsamp, skipped,
        grad_norm]`` sentinel array (see :class:`StepMetrics`)."""
        bs = next(iter(batch.values())).shape[0]
        if guard:
            if bs not in self._jit_g:
                self._jit_g[bs] = self._build_guard_step(bs, state=state)
            fn = self._jit_g[bs]
            # 0-d np.asarray pins (see run_steps): explicit dtype + explicit
            # device transfer for the per-step lr/poison scalars (a bare
            # numpy SCALAR still rides the implicit-transfer path)
            call_args = (state, batch, self._dispatch_key(),
                         jnp.asarray(np.asarray(self._next_lr(),
                                                np.float32)),
                         jnp.asarray(np.asarray(
                             self._poison_scalars(1)[0], np.float32)))
            with self._ambient():
                out = self._dist_sync_result(fn(*call_args))
                self._tc_after("guard-step", bs, fn, call_args, result=out)
            return out
        if bs not in self._jit:
            self._jit[bs] = self._build(bs, state=state)
        fn = self._jit[bs]
        call_args = (state, batch, self._dispatch_key(),
                     jnp.asarray(np.asarray(self._next_lr(), np.float32)))
        with self._ambient():
            out = self._dist_sync_result(fn(*call_args))
            self._tc_after("step", bs, fn, call_args, result=out)
        return out

    def run_steps(self, state, superbatch, k=None, guard=False,
                  metric_spec=None):
        """Run K fused train steps in ONE compiled dispatch.

        ``superbatch``: dict name -> stacked array of shape (k, batch, ...)
        (build one with ``io.SuperBatchIter`` / ``DataIter.superbatch(k)``,
        or stack K batches yourself). The scheduler clock advances K host
        updates and the per-step lr schedule rides in as a traced (k,)
        vector, so schedules never retrace; the jit cache is keyed on
        (batch_size, k) — plus the metric spec's signature when one is
        passed — so a fixed K never recompiles across epochs.

        Returns ``(new_state, metrics)`` where ``metrics`` is a
        :class:`StepMetrics` holding the device-resident K-step
        accumulators — reading any of its properties performs the single
        host readback for the dispatch. Without ``metric_spec`` the
        accumulators are the legacy (loss sum, top-1 correct count, sample
        count); with a :class:`~mxnet_tpu.metric.DeviceSumSpec` they are
        the spec's declared slots (read by name via ``metrics.values()``,
        folded by ``metric.update_from_device_sums``).

        ``guard=True`` compiles the GUARDED scan (separate jit cache; the
        unguarded program is untouched): non-finite steps become device-side
        no-ops, are excluded from the metric accumulators, and the returned
        :class:`StepMetrics` additionally carries ``skipped`` and
        ``last_grad_norm`` in the same single readback. A spec with no
        watchable loss pair is augmented with the in-scan CE loss so the
        guard's divergence EMA keeps its observation.
        """
        vals = list(superbatch.values())
        if not vals:
            raise MXNetError("run_steps: empty superbatch")
        lead = vals[0].shape[0]
        if k is not None and k != lead:
            raise MXNetError("run_steps: k=%d but superbatch is stacked %d "
                             "deep" % (k, lead))
        k = lead
        if any(v.shape[0] != k or v.ndim < 2 for v in vals):
            raise MXNetError("run_steps: superbatch arrays must share a "
                             "(k, batch, ...) leading shape, got %r"
                             % {n: tuple(v.shape)
                                for n, v in superbatch.items()})
        bs = vals[0].shape[1]
        if guard and metric_spec is not None:
            metric_spec = _with_guard_loss(metric_spec, bs)
        cache = self._jit_scan_g if guard else self._jit_scan
        ckey = ((bs, k) if metric_spec is None
                else (bs, k, metric_spec.signature))
        if ckey not in cache:
            cache[ckey] = self._build_scan(bs, k, guard=guard,
                                           metric_spec=metric_spec,
                                           state=state)
        fn = cache[ckey]
        # lr vector pinned through np.float32 BEFORE the device transfer:
        # the explicit f32 pin keeps the trace weak-type-free under any
        # jax config (tracecheck dtype lint), and jnp.asarray of a host
        # numpy array is an EXPLICIT transfer — a bare Python list would
        # ride an implicit one, which the transfer-guard runtime lint
        # rejects in the dispatch hot loop
        lrs = jnp.asarray(np.asarray([self._next_lr() for _ in range(k)],
                                     np.float32))
        if guard:
            call_args = (state, superbatch, self._dispatch_key(), lrs,
                         jnp.asarray(self._poison_scalars(k)))
            with self._ambient():
                new_state, packed = self._dist_sync_result(fn(*call_args))
                sums = StepMetrics(packed, guarded=True, spec=metric_spec)
                self._tc_after("guard-scan", ckey, fn, call_args,
                               result=(new_state, sums), spec=metric_spec)
            return new_state, sums
        call_args = (state, superbatch, self._dispatch_key(), lrs)
        with self._ambient():
            new_state, packed = self._dist_sync_result(fn(*call_args))
            sums = StepMetrics(packed, spec=metric_spec)
            self._tc_after("scan", ckey, fn, call_args,
                           result=(new_state, sums), spec=metric_spec)
        return new_state, sums

    def shard_superbatch(self, superbatch):
        """Place stacked (k, batch, ...) arrays for the scan dispatch: dim 0
        is the step axis (never sharded), dim 1 is the batch axis sharded
        along 'data' — the superbatch analog of :meth:`shard_batch`.

        Arrays already carrying the right NamedSharding (a
        ``SuperBatchIter`` given ``sharding=`` lands them per-chip on the
        producer thread) pass through ``jax.device_put`` as a no-op — the
        dispatch hot loop then performs zero resharding copies."""
        def to_jnp(v):
            return v.data if isinstance(v, NDArray) else jnp.asarray(v)
        if self.mesh is None:
            return {n: to_jnp(v) for n, v in superbatch.items()}
        from .parallel.mesh import (is_multiprocess, data_axis_size,
                                    AXIS_SEQ)
        if is_multiprocess(self.mesh):
            raise MXNetError("shard_superbatch: multi-process meshes keep "
                             "per-step dispatch (use step())")
        has_seq = AXIS_SEQ in self.mesh.axis_names
        bax = "data" if "data" in self.mesh.axis_names else None
        if bax is not None:
            n = data_axis_size(self.mesh)
            for name, v in superbatch.items():
                b = getattr(v, "shape", (0, 0))[1]
                if b % n:
                    raise MXNetError(
                        "shard_superbatch: %r batch dim %d does not divide "
                        "the %d-way 'data' mesh axis" % (name, b, n))
        if has_seq:
            sp = data_axis_size(self.mesh, AXIS_SEQ)
            for name, v in superbatch.items():
                shp = getattr(v, "shape", ())
                if len(shp) >= 3 and shp[2] % sp:
                    raise MXNetError(
                        "shard_superbatch: %r sequence dim %d does not "
                        "divide the %d-way 'seq' mesh axis — pad the "
                        "sequence or pick a divisible seq_len"
                        % (name, shp[2], sp))

        def spec_for(v):
            if has_seq and v.ndim >= 3:
                return P(None, bax, AXIS_SEQ)
            return P(None, bax)

        return {n: jax.device_put(
            to_jnp(v), jax.sharding.NamedSharding(self.mesh, spec_for(v)))
            for n, v in superbatch.items()}


def data_parallel_spec(mesh_shape, n_devices=None, devices=None):
    """Helper: build a mesh dict for make-style calls."""
    from .parallel.mesh import make_mesh
    return make_mesh(mesh_shape, devices)
