"""AOT serving engine: shape-bucketed, ahead-of-time-compiled inference.

The reference ships inference as a standalone minimal surface
(``c_predict_api`` / amalgamation's ``MXNET_PREDICT_ONLY`` build — PAPER.md)
because serving has different needs than training. This module is that
surface rebuilt for the XLA substrate (docs/serving.md):

* the stripped-head forward is ``jax.jit(...).lower(...).compile()``-d at
  LOAD time for a fixed set of batch-size buckets, so the first request
  never pays a trace/compile;
* compiled executables can be serialized to disk and re-imported
  (``export_compiled`` / ``executables=``), so a re-deploy is
  cold-start-free;
* every bucket program registers with :mod:`mxnet_tpu.tracecheck`, so the
  serving program set rides the same host-sync / const-capture / dtype gate
  as the training programs (``ci/serve.sh``).

``infer`` pads a request batch up to the smallest covering bucket and
slices the pad rows back off. Inference is per-example independent (eval
BatchNorm uses moving stats, softmax is per-row), so padding can never leak
into real rows — asserted bitwise in tests/test_serving.py.
"""
from __future__ import annotations

import logging
import pickle

import numpy as np

from ..base import MXNetError, env_str
from ..executor import _build_graph_runner
from ..predictor import (_strip_loss_heads, load_symbol, load_param_dict,
                         pick_partial_outputs, check_missing_params)
from .health import ServingHealth, SERVING_HEALTH

#: default batch-size buckets (env: MXTPU_SERVE_BUCKETS="1,8,32")
_DEFAULT_BUCKETS = (1, 8, 32)


def default_buckets():
    spec = env_str("MXTPU_SERVE_BUCKETS", "")
    if not spec:
        return _DEFAULT_BUCKETS
    try:
        buckets = tuple(sorted({int(s) for s in spec.split(",") if s.strip()}))
    except ValueError:
        raise MXNetError("MXTPU_SERVE_BUCKETS must be a comma-separated "
                         "list of batch sizes, got %r" % spec)
    if not buckets or buckets[0] < 1:
        raise MXNetError("MXTPU_SERVE_BUCKETS needs positive batch sizes, "
                         "got %r" % spec)
    return buckets


def _model_mesh(contexts, who="ServingEngine"):
    """Resolve ``contexts=`` (int N, or a list of Context/jax.Device) to a
    one-axis 'model' mesh — or None for the default device (no contexts,
    or the int 1). A one-element list is a one-device mesh, so the replica
    lives on THAT device: N one-chip replicas behind a FleetRouter each
    name their own chip. The mesh is the unit one REPLICA serves from: a
    fleet runs N of these side by side (docs/serving.md "Model-parallel
    replicas")."""
    if not contexts:
        return None
    import jax
    from ..context import Context
    from ..parallel import mesh as _mesh
    if isinstance(contexts, int):
        if contexts <= 1:
            return None
        return _mesh.model_parallel_mesh(contexts, jax.local_devices())
    devs = [c.to_device() if isinstance(c, Context) else c
            for c in contexts]
    if len(set(devs)) != len(devs):
        raise MXNetError(
            "%s: contexts resolve to duplicate devices %r — each model "
            "shard needs its own chip" % (who, devs))
    return _mesh.make_mesh({_mesh.AXIS_MODEL: len(devs)}, devs)


def _leaf_devices(tree):
    """Devices the array leaves of ``tree`` live on, sorted by id — what
    ``ServingEngine.devices`` / ``DecodeLoop.devices`` report (read off
    the arrays, not off the request that placed them)."""
    import jax
    devs = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        devs |= leaf.devices()
    return sorted(devs, key=lambda d: d.id)


def _audit_load_comms(obj, who):
    """MXTPU_COMMSCHECK load-time hook shared by :class:`ServingEngine`
    and :class:`~mxnet_tpu.serving.decode.DecodeLoop`: run the
    communication lints over the freshly compiled (sharded) program set
    (``obj.comms_report()``) and warn — or raise, under ``error`` — on any
    unsuppressed finding. The ``comms-bound`` efficiency floor is NOT
    applied here (min_eff=0): that roofline gates training scale-out,
    while a model-parallel serving program deliberately trades predicted
    efficiency for fitting the model at all."""
    from ..engine import commscheck_mode
    mode = commscheck_mode()
    if mode == "off":
        return
    from .. import commscheck as _cc
    # resolve the knob BEFORE the analyzer guard (same contract as the
    # memory audit: operator errors propagate, analyzer failures skip)
    repl = _cc.repl_bytes()
    try:
        findings = []
        for rep in obj.comms_report().values():
            findings += _cc.lint_report(rep, repl_threshold=repl,
                                        min_eff=0.0)
        bad = [f for f in findings if not f.suppressed]
    except Exception as e:
        logging.warning("%s(%s): comms audit could not run (%r) — "
                        "skipped", who, obj.name, e)
        return
    if not bad:
        return
    msg = ("%s(%s): comms audit found %d problem(s) at load "
           "(MXTPU_COMMSCHECK=%s):\n%s"
           % (who, obj.name, len(bad), mode,
              "\n".join(f.format() for f in bad)))
    if mode == "error":
        raise MXNetError(msg)
    logging.warning(msg)


def _audit_load_memory(obj, who):
    """MXTPU_MEMCHECK load-time hook shared by :class:`ServingEngine` and
    :class:`~mxnet_tpu.serving.decode.DecodeLoop`: run the memory lints
    over the freshly compiled program set (``obj.memory_report()``) and
    warn — or raise, under ``error`` — on any unsuppressed finding."""
    from ..engine import memcheck_mode
    mode = memcheck_mode()
    if mode == "off":
        return
    from .. import memcheck as _mc
    # resolve the knobs BEFORE the analyzer guard: a malformed
    # MXTPU_MEMCHECK_BUDGET/_TEMP_MULT is an operator error that must
    # propagate, not silently disable the gate the operator just armed
    budget = _mc.budget_bytes()
    temp_mult = _mc.temp_multiple()
    try:
        reports = obj.memory_report()
        findings = []
        for rep in reports.values():
            findings += _mc.lint_report(rep, budget=budget,
                                        temp_mult=temp_mult)
        findings += _mc.lint_resident_set(
            reports.values(), "%s/resident-set" % obj.name, budget=budget)
        bad = _mc.unsuppressed(findings)
    except Exception as e:
        # an analyzer failure (a backend whose executables cannot report
        # memory, an HLO format drift) must never abort the deploy the
        # audit exists to protect — log and skip; only FINDINGS raise
        logging.warning("%s(%s): memory audit could not run (%r) — "
                        "skipped", who, obj.name, e)
        return
    if not bad:
        return
    msg = ("%s(%s): memory audit found %d problem(s) at load "
           "(MXTPU_MEMCHECK=%s):\n%s"
           % (who, obj.name, len(bad), mode,
              "\n".join(f.format() for f in bad)))
    if mode == "error":
        raise MXNetError(msg)
    logging.warning(msg)


class ServingEngine(object):
    """AOT-compiled, shape-bucketed forward over a saved checkpoint.

    ``input_shapes`` maps input name -> PER-EXAMPLE shape (no batch dim),
    e.g. ``{"data": (3, 224, 224)}``; ``buckets`` is the set of batch sizes
    compiled ahead of time (default :func:`default_buckets`). ``infer``
    accepts any request size: n <= max(buckets) dispatches one padded
    bucket, larger requests are chunked over the largest bucket.

    ``executables=`` points at a file previously written by
    :meth:`export_compiled`; when it loads cleanly the engine starts with
    ZERO compiles (cold-start-free deploy). A stale/mismatched file logs a
    warning and falls back to fresh AOT compilation.

    ``quantize=`` (or ``MXTPU_SERVE_QUANT``): ``"none"`` (default) |
    ``"bf16"`` | ``"int8"`` weight-only quantization at load — per-channel
    scales, dequant inside the compiled body, so memcheck's resident
    accounting shows the HBM weight-bytes win and a sharded engine holds
    1/N of the QUANTIZED bytes per chip. Gate quality with
    :meth:`quality_report` + :func:`mxnet_tpu.serving.quantize.check_quality`
    (docs/serving.md "Quantized weights").
    """

    def __init__(self, symbol_json_or_file, param_file_or_dict, input_shapes,
                 buckets=None, output_names=None, allow_missing=False,
                 input_dtypes=None, executables=None, health=None,
                 name=None, contexts=None, quantize=None):
        import jax
        from .. import tracecheck as _tc
        from .quantize import resolve_mode
        self.quant_mode = resolve_mode(
            quantize if quantize is not None
            else env_str("MXTPU_SERVE_QUANT", "none"))
        #: model-axis mesh when this engine is bigger than one chip
        #: (``contexts=``): params shard over 'model' per the
        #: parallel.placement first-divisible-dim rule, batch inputs stay
        #: replicated at the edges, and every bucket program compiles
        #: partitioned — equal to the single-chip engine up to the
        #: backend's per-shape kernel choice (the rule never splits a
        #: contraction dim)
        self._mesh = _model_mesh(contexts, who="ServingEngine")
        self._symbol = _strip_loss_heads(load_symbol(symbol_json_or_file))
        if output_names:
            self._symbol = pick_partial_outputs(self._symbol, output_names)
        arg_params, aux_params = load_param_dict(param_file_or_dict)
        if not allow_missing:
            check_missing_params(self._symbol, set(input_shapes),
                                 arg_params, aux_params, who="ServingEngine")
        self._input_names = list(input_shapes)
        self._input_shapes = {n: tuple(int(d) for d in s)
                              for n, s in input_shapes.items()}
        self._input_dtypes = {
            n: np.dtype((input_dtypes or {}).get(n, np.float32))
            for n in self._input_names}
        # bucket-set resolution (docs/perf.md "Autotuning"): explicit
        # ``buckets=`` > MXTPU_SERVE_BUCKETS env > tuning DB > built-in
        # default — a DB hit also stashes the entry's other serving knobs
        # (``_autotuned``) for the Batcher to resolve against, and is
        # logged once via the obs registry
        self._autotuned = None
        if buckets is None and not env_str("MXTPU_SERVE_BUCKETS"):
            from .. import autotune as _autotune
            entry_key, knobs = _autotune.resolve_serve_knobs(self._symbol)
            if knobs and knobs.get("buckets"):
                try:
                    # the DB must never be able to break the deploy it
                    # configures: a hand-edited/corrupt bucket spec falls
                    # back to defaults with a warning, like a stale schema
                    buckets = _autotune.parse_buckets(knobs["buckets"])
                    self._autotuned = knobs
                    _autotune.note_db_resolution(
                        logging, "ServingEngine", entry_key,
                        {"buckets": knobs["buckets"]})
                except MXNetError as e:
                    logging.warning(
                        "autotune: tuning-DB entry %s carries an unusable "
                        "bucket spec (%s) — built-in defaults apply",
                        entry_key, e)
                    buckets = None
        self.buckets = tuple(sorted(set(
            int(b) for b in (buckets or default_buckets()))))
        if not self.buckets or self.buckets[0] < 1:
            raise MXNetError("ServingEngine: buckets must be positive "
                             "batch sizes, got %r" % (self.buckets,))
        self.health = health or ServingHealth(parent=SERVING_HEALTH)
        self.name = _tc.unique_name(name or "serving(%s)"
                                    % (self._symbol.name,))

        # resolve parameter/aux arrays against shapes inferred at the
        # smallest bucket (param shapes are batch-independent)
        shapes_b0 = self._full_shapes(self.buckets[0])
        arg_shapes, out_shapes, aux_shapes = \
            self._symbol.infer_shape(**shapes_b0)
        shape_of = dict(zip(self._symbol.list_arguments(), arg_shapes))
        aux_shape_of = dict(zip(self._symbol.list_auxiliary_states(),
                                aux_shapes))
        import jax.numpy as jnp

        from .quantize import is_quantized_leaf, quantize_array

        def place(arr, sharded):
            """Model-mesh placement: params shard per the placement rule
            (first divisible dim = the OUTPUT dim of an (out, in) weight,
            so contraction dims never split and no sum changes its order
            against single-chip); aux stats replicate."""
            if self._mesh is None:
                return arr
            from ..parallel import placement as _pl
            from ..parallel.mesh import AXIS_MODEL
            P = jax.sharding.PartitionSpec
            spec = None
            if sharded:
                spec = _pl.auto_spec(AXIS_MODEL, tuple(arr.shape),
                                     self._mesh, prefer_first=True)
            return jax.device_put(
                arr, jax.sharding.NamedSharding(self._mesh, spec or P()))

        def store_param(host_arr):
            """Quantize (per ``quant_mode``) then place one parameter.
            An int8 leaf becomes ``{"q", "s"}``: the payload shards per
            the placement rule and the per-channel scale pins along the
            SAME axis-0 split, so each chip holds 1/N of the quantized
            bytes beside its own scales."""
            stored = quantize_array(np.asarray(host_arr), self.quant_mode)
            if not is_quantized_leaf(stored):
                return place(jnp.asarray(stored), True)
            if self._mesh is None:
                return {"q": jnp.asarray(stored["q"]),
                        "s": jnp.asarray(stored["s"])}
            from ..parallel import placement as _pl
            from ..parallel.mesh import AXIS_MODEL
            P = jax.sharding.PartitionSpec
            spec = _pl.auto_spec(AXIS_MODEL, tuple(stored["q"].shape),
                                 self._mesh, prefer_first=True)
            s_spec = None
            if spec is not None and len(spec) and spec[0]:
                s_spec = P(spec[0])
            put = lambda a, sp: jax.device_put(
                a, jax.sharding.NamedSharding(self._mesh, sp or P()))
            return {"q": put(stored["q"], spec),
                    "s": put(stored["s"], s_spec)}

        def as_dev(v, shape, sharded=True):
            data = getattr(v, "data", v)  # NDArray or raw array
            arr = np.asarray(data)
            if tuple(arr.shape) != tuple(shape):
                raise MXNetError(
                    "ServingEngine: parameter shape %s does not match the "
                    "graph's %s" % (tuple(arr.shape), tuple(shape)))
            if sharded:
                return store_param(arr)
            return place(jnp.asarray(arr), sharded)

        self._params = {}
        for n in self._symbol.list_arguments():
            if n in self._input_names:
                continue
            if n in arg_params:
                self._params[n] = as_dev(arg_params[n], shape_of[n])
            else:  # allow_missing=True: deliberate zero-fill
                self._params[n] = store_param(
                    np.zeros(shape_of[n], np.float32))
        self._aux = {}
        for n in self._symbol.list_auxiliary_states():
            if n in aux_params:
                self._aux[n] = as_dev(aux_params[n], aux_shape_of[n],
                                      sharded=False)
            else:
                self._aux[n] = place(
                    jnp.zeros(aux_shape_of[n], np.float32), False)

        node_constraint = None
        if self._mesh is not None:
            # activations REPLICATED at every op edge, params sharded: each
            # layer computes its output slice over the 'model' axis with
            # FULL contractions (operand replicated, weight sharded on its
            # output dim — the placement first-divisible-dim rule), then
            # all-gathers the slice. That is Megatron column-parallel +
            # gather, and it keeps the sharded engine equal to the
            # single-chip one to the last ulp or two: no reduction ever
            # spans shards, so float summation order never changes (what
            # remains is the backend picking kernels by shard shape). Letting
            # activations stay sharded between ops is faster on paper but
            # lets GSPMD split a later contraction (or a softmax row
            # reduction) into partial sums, and that drift grows with the
            # contraction instead of staying at rounding.
            _repl = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec())

            def node_constraint(node, outs, _repl=_repl):
                return [jax.lax.with_sharding_constraint(o, _repl)
                        for o in outs]

        run, nodes = _build_graph_runner(self._symbol,
                                         node_constraint=node_constraint)
        needs_rng = any((not n.is_variable) and n.op.needs_rng
                        for n in nodes)
        # eval-mode forward never consumes randomness, but ops declared
        # needs_rng still take a key argument; a tiny static key const is
        # baked in (well under the const-capture lint threshold)
        key = jax.random.key(0) if needs_rng else None

        qmode = self.quant_mode

        def _fwd(params, aux, batch):
            # weight-only dequant INSIDE the body: the resident arrays
            # (what memcheck prices) stay int8/bf16; the f32 views are
            # per-dispatch temporaries. Mode "none" bypasses entirely so
            # an unquantized engine's program is untouched.
            from .quantize import dequant_tree
            arg_vals = dict(batch)
            arg_vals.update(params if qmode == "none"
                            else dequant_tree(params))
            outs, _aux_up = run(arg_vals, aux, key, False)
            return tuple(outs)

        self._jfn = jax.jit(_fwd)
        self._compiled = {}
        loaded = False
        if executables is not None:
            loaded = self._try_import(executables)
        if not loaded:
            for b in self.buckets:
                self._compiled[b] = self._jfn.lower(
                    *self._bucket_structs(b)).compile()
        # register the whole bucket set with the static analyzer: the
        # serving programs are gated exactly like the train-step programs
        for b in self.buckets:
            _tc.register_program("%s/bucket[b=%d]" % (self.name, b),
                                 self._jfn, self._bucket_structs(b))
        # per-output row factor: outputs whose leading dim is a multiple of
        # the batch (e.g. the LM's (batch*seq, vocab) head) slice by it
        self._out_row_factor = []
        for s in out_shapes:
            lead = int(s[0]) if s else 0
            self._out_row_factor.append(
                lead // self.buckets[0]
                if lead and lead % self.buckets[0] == 0 else None)
        # MXTPU_MEMCHECK / MXTPU_COMMSCHECK: audit the freshly compiled
        # bucket set's memory and (for sharded engines) collective
        # inventory at LOAD time (docs/static_analysis.md) — a deploy that
        # cannot fit its budget, or whose partitioning reshards a declared
        # layout per request, fails here, not at the first full-batch
        # request
        _audit_load_memory(self, "ServingEngine")
        _audit_load_comms(self, "ServingEngine")

    # ------------------------------------------------------------------
    def _full_shapes(self, b):
        return {n: (b,) + self._input_shapes[n] for n in self._input_names}

    def _bucket_structs(self, b):
        import jax

        def sds(x):
            # structs carry the REAL shardings so the AOT lowering (and
            # the analyzers re-deriving the program from them) partition
            # exactly like the live arrays — the commscheck struct_args
            # contract
            sh = getattr(x, "sharding", None)
            if (self._mesh is not None
                    and isinstance(sh, jax.sharding.NamedSharding)):
                return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype,
                                            sharding=sh)
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)

        from .quantize import is_quantized_leaf
        params_s = {n: ({"q": sds(v["q"]), "s": sds(v["s"])}
                        if is_quantized_leaf(v) else sds(v))
                    for n, v in self._params.items()}
        aux_s = {n: sds(v) for n, v in self._aux.items()}
        repl = None
        if self._mesh is not None:
            repl = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec())
        batch_s = {}
        for n in self._input_names:
            shape = (b,) + self._input_shapes[n]
            if repl is not None:
                batch_s[n] = jax.ShapeDtypeStruct(
                    shape, self._input_dtypes[n], sharding=repl)
            else:
                batch_s[n] = jax.ShapeDtypeStruct(shape,
                                                  self._input_dtypes[n])
        return params_s, aux_s, batch_s

    @property
    def max_batch(self):
        return self.buckets[-1]

    @property
    def model_devices(self):
        """Number of chips one replica of this engine spans (1 =
        single-chip)."""
        return 1 if self._mesh is None else int(self._mesh.devices.size)

    @property
    def devices(self):
        """The devices holding this engine's parameters, by id."""
        return _leaf_devices(self._params)

    def bucket_for(self, n):
        """Smallest compiled bucket covering ``n`` examples."""
        for b in self.buckets:
            if b >= n:
                return b
        raise MXNetError("ServingEngine: no bucket covers %d examples "
                         "(buckets %s); chunk the request or add a bucket"
                         % (n, list(self.buckets)))

    # ------------------------------------------------------------------
    def update_params(self, arg_params, aux_params=None):
        """Hot-reload parameters under the LIVE engine with zero
        recompiles — the train-to-serve handoff (docs/serving.md "Hot
        reload"): a mid-training checkpoint swaps into a serving replica
        without recompiling, re-bucketing, or dropping a request.

        ``arg_params`` is a ``{name: array/NDArray}`` dict or a param-file
        path (``load_param_dict`` formats — ``Module.save_checkpoint`` /
        ``AsyncCheckpointWriter`` output load directly). Every non-input
        argument of the serving graph must be present with the graph's
        exact shape and the resident array's dtype; extra keys (stripped
        loss heads, optimizer state) are ignored. New arrays are placed
        with the RESIDENT arrays' shardings, so the AOT bucket executables
        (which bind placements at compile time) keep serving — the swap is
        one atomic dict rebind, safe against concurrent ``infer``."""
        import jax
        import jax.numpy as jnp
        if isinstance(arg_params, (str, bytes)) or hasattr(arg_params,
                                                           "read"):
            arg_params, file_aux = load_param_dict(arg_params)
            if aux_params is None:
                aux_params = file_aux
        elif isinstance(arg_params, tuple) and len(arg_params) == 2:
            arg_params, aux_params = arg_params

        from .quantize import is_quantized_leaf, quantize_array

        def validated(new, cur, kind):
            missing = sorted(set(cur) - set(new))
            if missing:
                raise MXNetError(
                    "update_params: checkpoint is missing %s %s — a "
                    "partial swap would serve a chimera; pass every "
                    "parameter of the serving graph"
                    % (kind, ", ".join(missing)))
            out = {}
            for n, resident in cur.items():
                host = np.asarray(getattr(new[n], "data", new[n]))
                if is_quantized_leaf(resident):
                    # quantized engine: re-quantize the incoming f32
                    # checkpoint host-side, land beside the resident
                    # shardings (payload + its per-channel scale)
                    if tuple(host.shape) != tuple(resident["q"].shape):
                        raise MXNetError(
                            "update_params: %s %r shape %s does not match "
                            "the compiled graph's %s — the AOT "
                            "executables bind shapes; rebuild the engine "
                            "for a different architecture"
                            % (kind, n, tuple(host.shape),
                               tuple(resident["q"].shape)))
                    stored = quantize_array(
                        np.asarray(host, np.float32), self.quant_mode)
                    out[n] = {
                        "q": jax.device_put(stored["q"],
                                            resident["q"].sharding),
                        "s": jax.device_put(stored["s"],
                                            resident["s"].sharding)}
                    continue
                arr = jnp.asarray(host)
                if tuple(arr.shape) != tuple(resident.shape):
                    raise MXNetError(
                        "update_params: %s %r shape %s does not match the "
                        "compiled graph's %s — the AOT executables bind "
                        "shapes; rebuild the engine for a different "
                        "architecture" % (kind, n, tuple(arr.shape),
                                          tuple(resident.shape)))
                if arr.dtype != resident.dtype:
                    if not np.issubdtype(arr.dtype, np.floating):
                        raise MXNetError(
                            "update_params: %s %r dtype %s does not match "
                            "the resident %s" % (kind, n, arr.dtype,
                                                 resident.dtype))
                    # f32 checkpoints of a bf16-serving engine (and vice
                    # versa) widen/narrow to the compiled dtype — the
                    # executable's input layout is fixed
                    arr = arr.astype(resident.dtype)
                sh = getattr(resident, "sharding", None)
                out[n] = (jax.device_put(arr, sh) if sh is not None
                          else arr)
            return out

        if self._aux and aux_params is None:
            raise MXNetError(
                "update_params: the graph has aux states %s but no "
                "aux_params were passed" % sorted(self._aux))
        new_params = validated(arg_params, self._params, "parameter")
        new_aux = (validated(aux_params, self._aux, "aux state")
                   if self._aux else dict(self._aux))
        # land the transfers BEFORE the rebind: a request dispatched the
        # instant after the swap must never block on (or race) an H2D
        for v in list(new_params.values()) + list(new_aux.values()):
            if is_quantized_leaf(v):
                v["q"].block_until_ready()
                v["s"].block_until_ready()
            else:
                v.block_until_ready()
        # atomic rebind (CPython assignment): concurrent infer() sees the
        # old set or the new set, never a mix
        self._params, self._aux = new_params, new_aux
        from ..obs import REGISTRY
        REGISTRY.counter(
            "serving.param_reloads",
            "parameter hot-reloads into live serving engines").inc()
        logging.info("%s: hot-reloaded %d parameters (zero recompiles)",
                     self.name, len(new_params))

    # ------------------------------------------------------------------
    def infer(self, inputs):
        """Run the compiled forward over ``{name: (n, ...) array}``; returns
        a list of np arrays with pad rows already sliced off. Requests
        larger than the biggest bucket are chunked."""
        import jax.numpy as jnp
        n = None
        host = {}
        for name in self._input_names:
            if name not in inputs:
                raise MXNetError("infer: missing input %r (need %s)"
                                 % (name, self._input_names))
            v = np.asarray(inputs[name], self._input_dtypes[name])
            if tuple(v.shape[1:]) != self._input_shapes[name]:
                raise MXNetError(
                    "infer: input %r per-example shape %s != %s"
                    % (name, tuple(v.shape[1:]), self._input_shapes[name]))
            if n is None:
                n = v.shape[0]
            elif v.shape[0] != n:
                raise MXNetError("infer: inputs disagree on batch size "
                                 "(%d vs %d)" % (n, v.shape[0]))
            host[name] = v
        if n == 0:
            raise MXNetError("infer: empty request")
        if n > self.max_batch:
            chunks = [self.infer({k: v[i:i + self.max_batch]
                                  for k, v in host.items()})
                      for i in range(0, n, self.max_batch)]
            return [np.concatenate([c[i] for c in chunks])
                    for i in range(len(chunks[0]))]
        b = self.bucket_for(n)
        if b > n:
            host = {k: np.concatenate(
                [v, np.zeros((b - n,) + v.shape[1:], v.dtype)])
                for k, v in host.items()}
        if self._mesh is None:
            batch = {k: jnp.asarray(v) for k, v in host.items()}
        else:
            # activations replicated at the edges: the request lands whole
            # on every model shard (AOT executables require inputs placed
            # exactly as compiled)
            import jax
            repl = jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec())
            batch = {k: jax.device_put(v, repl) for k, v in host.items()}
        outs = self._compiled[b](self._params, self._aux, batch)
        self.health.record_batch(n, b - n)
        res = []
        for o, f in zip(outs, self._out_row_factor):
            a = np.asarray(o)
            res.append(a[:n * f] if f else a)
        return res

    # ------------------------------------------------------------------
    # serialized executables: cold-start-free deploys
    # ------------------------------------------------------------------
    def _meta(self):
        return {"buckets": list(self.buckets),
                "input_shapes": {n: list(s)
                                 for n, s in self._input_shapes.items()},
                "input_dtypes": {n: str(d)
                                 for n, d in self._input_dtypes.items()},
                # a sharded executable only loads against the same mesh
                # width, a quantized one only against the same weight
                # storage; a mismatch falls back to fresh AOT compilation
                "model_devices": self.model_devices,
                "quantize": self.quant_mode}

    def export_compiled(self, path):
        """Serialize every bucket's compiled executable to ``path``
        (atomic write). A later ``ServingEngine(..., executables=path)``
        on the same backend skips compilation entirely. Raises
        :class:`MXNetError` when the backend cannot serialize."""
        from jax.experimental import serialize_executable as _se
        from ..model import atomic_write_bytes
        payload = {"version": 1, "meta": self._meta(), "buckets": {}}
        try:
            for b, comp in self._compiled.items():
                payload["buckets"][b] = _se.serialize(comp)
        except Exception as e:
            raise MXNetError(
                "export_compiled: this backend cannot serialize compiled "
                "executables (%r)" % (e,)) from e
        atomic_write_bytes(path, pickle.dumps(payload))
        return path

    def _try_import(self, path):
        from jax.experimental import serialize_executable as _se
        try:
            with open(path, "rb") as f:
                payload = pickle.loads(f.read())
            if payload.get("meta") != self._meta():
                raise MXNetError(
                    "executable file %s was exported for a different "
                    "bucket/shape configuration" % (path,))
            # load over the devices this engine occupies — the default is
            # every local device, which a one-chip program cannot take
            devs = (self.devices if self._mesh is None
                    else list(self._mesh.devices.flat))
            for b in self.buckets:
                blob, in_tree, out_tree = payload["buckets"][b]
                self._compiled[b] = _se.deserialize_and_load(
                    blob, in_tree, out_tree, execution_devices=devs)
            return True
        except Exception as e:
            logging.warning(
                "ServingEngine: could not import executables from %s (%s) "
                "— falling back to fresh AOT compilation", path, e)
            self._compiled = {}
            return False

    # ------------------------------------------------------------------
    def weight_bytes(self):
        """Resident HBM bytes of the engine's (possibly quantized)
        parameter set — GLOBAL across model shards (a fully sharded
        engine holds 1/N of this per chip). The memcheck-visible number
        the int8 leg's >= 40% HBM-reduction gate is measured against
        (docs/serving.md "Quantized weights")."""
        from .quantize import tree_bytes
        return tree_bytes(self._params) + tree_bytes(self._aux)

    def quality_report(self, reference, probe_inputs):
        """Quantization quality gate, step 1 (docs/serving.md "Quantized
        weights"): run the SAME probe batch through this (quantized)
        engine and an unquantized ``reference`` engine of the same graph,
        and compare first-output argmax agreement + max logit drift. Feed
        the result to :func:`mxnet_tpu.serving.quantize.check_quality`,
        which raises below the ``MXTPU_SERVE_QUANT_MIN_AGREE`` floor —
        ci/serve.sh runs exactly this before trusting a quantized
        deploy."""
        from .quantize import quality_report as _qr
        ref = reference.infer(probe_inputs)[0]
        got = self.infer(probe_inputs)[0]
        return _qr(ref, got)

    # ------------------------------------------------------------------
    def memory_report(self, top=8):
        """Static memory profile of every compiled bucket
        (docs/static_analysis.md "Memory lints"): returns ``{bucket:
        MemoryReport}`` from the ALREADY-compiled executables — no
        recompile, nothing executes. Buckets imported from a serialized
        executable file that cannot report memory are skipped with a
        warning."""
        from .. import memcheck as _mc
        reports = {}
        for b, comp in sorted(self._compiled.items()):
            try:
                reports[b] = _mc.analyze_compiled(
                    comp, "%s/bucket[b=%d]" % (self.name, b),
                    args=self._bucket_structs(b), top=top)
            except Exception as e:
                logging.warning(
                    "ServingEngine: bucket %d executable cannot report "
                    "memory (%s) — skipped from the memory audit", b, e)
        return reports

    def comms_report(self):
        """Static collective-communication inventory of every compiled
        bucket (docs/static_analysis.md "Communication lints"):
        ``{program_name: CommsReport}`` from the ALREADY-compiled
        executables — no recompile, nothing executes. Single-chip engines
        report zero collectives; a model-axis-sharded engine's inventory
        is the partitioning bill the deploy pays per request. Executables
        that cannot surface HLO text are skipped with a warning."""
        from .. import commscheck as _cc
        reports = {}
        for b, comp in sorted(self._compiled.items()):
            name = "%s/bucket[b=%d]" % (self.name, b)
            try:
                reports[name] = _cc.analyze_compiled(comp, name,
                                                     mesh=self._mesh)
            except Exception as e:
                logging.warning(
                    "ServingEngine: bucket %d executable cannot report "
                    "its collectives (%s) — skipped from the comms audit",
                    b, e)
        return reports

    def check(self, const_bytes=None, memory=False, budget=None,
              comms=False, min_eff=0.0):
        """Static-analyze this engine's registered bucket programs
        (docs/static_analysis.md); returns the findings.

        ``memory=True`` additionally runs the memory lints over every
        compiled bucket (``hbm-budget``/``temp-blowup``) plus the
        ``resident-set`` lint over the whole bucket set — the jit/AOT
        cache keeps every bucket's executable reachable, so their
        footprints co-reside.

        ``comms=True`` adds the communication lints over every bucket's
        collective inventory. ``min_eff`` defaults to 0 here (unlike the
        training gate): the comms-bound roofline measures scale-out
        efficiency, and a model-parallel serving program deliberately
        trades it for fitting the model — pass a floor to opt in."""
        from .. import tracecheck as _tc
        findings = _tc.check_registered(const_bytes=const_bytes,
                                        match=self.name + "/")
        if memory:
            from .. import memcheck as _mc
            reports = self.memory_report()
            for rep in reports.values():
                findings += _mc.lint_report(rep, budget=budget)
            findings += _mc.lint_resident_set(
                reports.values(), "%s/resident-set" % self.name,
                budget=budget)
        if comms:
            from .. import commscheck as _cc
            for rep in self.comms_report().values():
                findings += _cc.lint_report(rep, min_eff=min_eff)
        return findings
