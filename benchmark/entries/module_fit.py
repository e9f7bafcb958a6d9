"""Entry ``module_fit``: ``Module.fit(steps_per_dispatch=K)`` for the whole
window, over one chip or the data-parallel mesh of several, on synthetic
batches made on the device from the seed.

ONE ``fit`` call builds the module, its compiled K-step scan and its state,
and runs three epochs over an iterator the benchmark controls: epoch 0 is
one dispatch (the compile), epoch 1 the second warm-up dispatch (the first
to run on donated state, as every dispatch of the window does), epoch 2 the
measured window, which lasts until the clock runs out. ``correct`` compares
the loss and the state each of the two warm-up dispatches leaves with the
plain reference, which follows the same 2K steps from the seed. Float32
products run at the precision the configuration states
(``matmul_precision``), set through JAX's own option for this process. The
window opens when its first batch is asked for (the device is idle: every earlier
dispatch has retired) and closes when its last dispatch has retired, so the
rate is all samples over all of the window's time.
"""
import gc
import time

import numpy as np

from benchmark.harness import runner


def _contexts(mx, run):
    n = run.cell.chips
    if run.peaks is None:            # a CPU rehearsal
        return [mx.cpu(i) for i in range(n)]
    return [mx.tpu(i) for i in range(n)]


def make_iterator(mx, pool, k, warm_dispatches, seconds):
    """The feed: cycles a pool of device-resident batches; epoch 0 yields
    ``k`` batches, epoch 1 ``k * (warm_dispatches - 1)``, epoch 2 whole
    dispatches until ``seconds`` have passed since its first batch."""

    class WindowIter(mx.io.DataIter):
        def __init__(self):
            data, label = pool[0]
            super().__init__(int(data.shape[0]))
            self.provide_data = [mx.io.DataDesc("data", tuple(data.shape))]
            self.provide_label = [mx.io.DataDesc("softmax_label",
                                                 tuple(label.shape))]
            self.epoch, self.i, self.t_open = 0, 0, None

        def reset(self):
            self.epoch += 1
            self.i = 0

        def next(self):
            if self.epoch == 0:
                if self.i >= k:
                    raise StopIteration
            elif self.epoch == 1:
                if self.i >= k * (warm_dispatches - 1):
                    raise StopIteration
            elif self.epoch == 2:
                if self.t_open is None:
                    self.t_open = time.perf_counter()
                if (self.i % k == 0 and
                        time.perf_counter() >= self.t_open + seconds):
                    raise StopIteration
            else:
                raise StopIteration
            data, label = pool[self.i % len(pool)]
            self.i += 1
            return mx.io.DataBatch([mx.nd.NDArray(data)],
                                   [mx.nd.NDArray(label)], pad=0)

    return WindowIter()


def leaf_gaps(prog, ref, skip=()):
    """Per leaf, the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger: ``{leaf: gap}``."""
    names = [n for n in ref if n not in skip]
    rn = {n: float(np.linalg.norm(np.asarray(ref[n], np.float64)))
          for n in names}
    med = float(np.median(list(rn.values())))
    return {n: abs(float(np.linalg.norm(np.asarray(prog[n], np.float64)))
                   - rn[n]) / max(rn[n], med) for n in names}


def worst(gaps):
    """``(gap, leaf name)`` of the worst leaf; a NaN counts as worst."""
    top, where = 0.0, None
    for n, gap in gaps.items():
        if not gap <= top:
            top, where = gap, n
    return top, where


def leaf_gap(prog, ref, skip=()):
    """The worst leaf of ``leaf_gaps``: ``(gap, leaf name)``."""
    return worst(leaf_gaps(prog, ref, skip))


def reference_steps(ref, cfg, params0, batches, mom0=None, done=0,
                    dtype="float32", rows=None, rescale=None, put=None,
                    precision="highest", lr_scale=1.0, weight_lr=None):
    """Follow one dispatch's steps with the plain reference from
    ``(params0, mom0)``, ``done`` updates after the start. Returns ``(mean
    loss, momentum, parameter change, first step's gradient norms, (params,
    momentum) on the device)``, the first three as host copies. Planted
    faults: ``rows`` keeps only the first rows of each batch; ``lr_scale``
    scales the learning rate (0: a state left unchanged); ``weight_lr``
    scales it on the leaves of two or more dimensions only (a wrong update
    of every kernel and matrix, the BatchNorm leaves sound). ``put`` places
    one batch's arrays (sharding)."""
    import jax
    import jax.numpy as jnp
    fit = cfg["fit"]
    mu = float(fit["momentum"])

    def one(p, m, d, l, lr):
        p_new, m_new, loss = ref.train_step(
            p, m, d, l, cfg, fit, dtype=dtype, rescale=rescale,
            precision=precision, lr=lr)
        if weight_lr is not None:
            for n in p:
                if p[n].ndim >= 2:
                    m_new[n] = mu * m[n] + weight_lr * (m_new[n] - mu * m[n])
                    p_new[n] = p[n] + m_new[n]
        return p_new, m_new, loss

    step = jax.jit(one)
    p = params0
    m = ({n: jnp.zeros_like(v) for n, v in params0.items()}
         if mom0 is None else mom0)
    losses, gnorm = [], None
    for i, (data, label) in enumerate(batches):
        if rows is not None:
            data, label = data[:rows], label[:rows]
        if put is not None:
            data, label = put(data), put(label)
        lr = np.float32(ref.learning_rate(fit, done + i + 1) * lr_scale)
        p_new, m_new, loss = step(p, m, data, label, lr)
        if gnorm is None and mom0 is None:
            # momentum after step 1 is -lr * (g + wd * w): the gradient as
            # the optimizer got it, to a constant
            gnorm = {n: float(jnp.linalg.norm(m_new[n])) for n in m_new}
        p, m = p_new, m_new
        losses.append(float(loss))
    mom = {n: np.asarray(v) for n, v in m.items()}
    dparam = {n: np.asarray(p[n] - params0[n]) for n in p}
    return float(np.mean(losses)), mom, dparam, gnorm, (p, m)


def gaps_against(true, other, skip, suffix=""):
    """Every number read between two followings of one dispatch: the mean
    loss, and for the momentum and for the parameters' change the worst
    leaf, the median leaf, and the median over the leaves of two or more
    dimensions (kernels and matrices: a third of the leaves, which the
    BatchNorm leaves outvote in the median over all)."""
    loss_t, mom_t, dp_t = true[:3]
    loss_o, mom_o, dp_o = other[:3]
    out, where = {"loss_gap": abs(loss_o - loss_t) / abs(loss_t)}, {}
    for name, o, t in (("momentum", mom_o, mom_t), ("dparam", dp_o, dp_t)):
        gaps = leaf_gaps(o, t, skip)
        out[name + "_gap"], where[name + "_leaf"] = worst(gaps)
        out[name + "_gap_median"] = float(np.median(list(gaps.values())))
        out[name + "_gap_weights"] = float(np.median(
            [g for n, g in gaps.items() if np.ndim(t[n]) >= 2]))
    return ({k + suffix: v for k, v in out.items()},
            {k + suffix: v for k, v in where.items()})


def drive_fit(run, mx, ref):
    """Set-up and window, through one ``Module.fit`` call. Returns what the
    window measured and what epoch 0 left for the comparison."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import tracecheck
    cell, cfg, mix = run.cell, run.cell.config, run.cell.traffic
    fit = cfg["fit"]
    k = int(fit["steps_per_dispatch"])
    batch = int(fit["batch_per_chip"]) * cell.chips
    builder = cell.builder()
    sym = builder.build_symbol(cfg)
    params0, aux0 = ref.make_params(cfg, run.seed)
    pool = ref.make_batches(cfg, run.seed, int(fit["batch_pool"]), batch)
    jax.block_until_ready(pool)
    t_inputs = time.perf_counter()
    feed = make_iterator(mx, pool, k, int(mix["warm_dispatches"]),
                         run.seconds)
    mod = mx.mod.Module(sym, context=_contexts(mx, run))

    retired = []            # (perf_counter, epoch) per retired dispatch
    left = [{}, {}]         # what epochs 0 and 1 left: loss, params, momentum
    marks = {}

    def on_dispatch(param):
        retired.append((time.perf_counter(), param.epoch))
        if param.epoch < 2:
            ce = param.eval_metric.metrics[1]
            left[param.epoch]["loss"] = ce.sum_metric / ce.num_inst
        if ("trace_until" in marks and run.trace is None
                and time.perf_counter() >= marks["trace_until"]):
            run.stop_trace()

    def on_epoch_end(epoch, symbol, arg_params, aux_params):
        marks["epoch_end_%d" % epoch] = time.perf_counter()
        if epoch < 2:
            # kept on the device until the window has closed; the next
            # dispatch takes the state donated, so both are copied
            left[epoch]["params"] = {n: jnp.copy(v.data)
                                     for n, v in arg_params.items()}
            left[epoch]["momentum"] = {n: jnp.copy(v) for n, v in
                                       mod._fused_state["opt"].items()}
        if epoch == 1:
            marks["retraces"] = tracecheck.retrace_count()
            if run.trace_on:
                run.arm_spans()
                run.start_trace()
                marks["trace_until"] = time.perf_counter() \
                    + runner.TRACE_SECONDS + 1.0

    mod.fit(feed, num_epoch=3, eval_metric=["acc", "ce"],
            arg_params={n: mx.nd.NDArray(v) for n, v in params0.items()},
            aux_params={n: mx.nd.NDArray(v) for n, v in aux0.items()},
            optimizer=fit["optimizer"],
            optimizer_params=dict(builder.optimizer_params(mx, cfg)),
            steps_per_dispatch=k, batch_end_callback=on_dispatch,
            epoch_end_callback=on_epoch_end)
    if run.trace_on:
        if run.trace is None:
            run.stop_trace()
        # the profiler was started before the window's first batch
        t0, t1 = run.trace_window_ns
        run.trace_window_ns = (max(t0, run.perf_to_trace_ns(feed.t_open)), t1)
    window = [t for t, epoch in retired if epoch == 2]
    warm = [t for t, epoch in retired if epoch < 2]
    t_open, t_close = feed.t_open, window[-1]
    scan_keys = [key[:2] for key in mod._fused._jit_scan]
    n_comp, comp_s = run.meter.between(t_open, t_close)
    retraced = tracecheck.retrace_count() - marks["retraces"]
    # a stall inside the window shows as one long gap between retirements
    gaps = np.diff(window) if len(window) > 1 else np.zeros(1)
    run.notes["window"] = {"dispatches": len(window),
                           "seconds": t_close - t_open,
                           "retire_gap_s": {"median": float(np.median(gaps)),
                                            "max": float(gaps.max()),
                                            "at": int(gaps.argmax()) + 1},
                           "scan_keys": [repr(s) for s in scan_keys]}
    run.notes["window_compiles"] = {"programs": n_comp, "seconds": comp_s,
                                    "retraces": retraced}
    run.notes["setup_phases_s"] = {
        "import_and_inputs": t_inputs - run.t_process,
        "bind_compile_first_dispatch": warm[0] - t_inputs,
        "first_dispatch_to_its_epoch_end": marks["epoch_end_0"] - warm[0],
        "to_last_warm_dispatch": warm[-1] - marks["epoch_end_0"],
        "last_warm_dispatch_to_open": t_open - warm[-1]}
    for d in left:
        d["params"], d["momentum"] = jax.device_get(
            (d["params"], d["momentum"]))
    return {"k": k, "batch": batch, "params0": params0, "left": left,
            "t_open": t_open, "t_close": t_close, "dispatches": len(window),
            "samples": len(window) * k * batch,
            # the scan engaged at (batch, k): no silent k=1 fallback
            "engaged": any(key == (batch, k) for key in scan_keys),
            "compiles": n_comp + retraced, "mesh": mod._fused.mesh,
            "memory_peak": run.memory_peak_bytes(),
            "spans": run.spans() if run.trace_on else []}


#: the control (the reference in bfloat16, the next precision below the
#: stated float32), the program's own lower path (``one_pass``: float32
#: products at the chip's default precision, one bfloat16 pass) and the
#: planted faults, each as arguments of ``reference_steps``
VARIANTS = {"bf16": dict(dtype="bfloat16"),
            "one_pass": dict(precision="bfloat16"),
            "weights_lr": dict(weight_lr=0.5),
            "unchanged": dict(lr_scale=0.0)}


def variants_of(batch, chips):
    """``VARIANTS`` and the faults that depend on the cell's sizes: half of
    the batch left out; with several chips, the exchange left out."""
    out = dict(VARIANTS, half_batch=dict(rows=batch // 2))
    if chips > 1:
        out["no_exchange"] = dict(rows=batch // chips, rescale=1.0 / batch)
    return out


def follow(ref, cfg, params0, blocks, **kw):
    """The reference over the warm-up's dispatches, one block of batches
    after another: ``[reference_steps(...) per block]``."""
    out, p, m, done = [], params0, None, 0
    for batches in blocks:
        out.append(reference_steps(ref, cfg, p, batches, mom0=m, done=done,
                                   **kw))
        p, m = out[-1][4]
        done += len(batches)
    return out


def read_gaps(true, other, skip):
    readings, where = {}, {}
    for i, (t, o) in enumerate(zip(true, other)):
        r, w = gaps_against(t, o, skip, suffix="" if i == 0 else "_%d" % (i + 1))
        readings.update(r)
        where.update(w)
    return readings, where


def check_warm_dispatches(run, ref, res):
    """Follow the two warm-up epochs with the plain reference, once the
    program's state is freed, and read the gaps. ``--control <name>``
    puts the reference, computed as that control or with that fault
    planted, in the program's place: ITS readings go through the same
    comparison and the run reports what that says. ``--control all`` notes
    every control's readings beside the program's, for setting limits."""
    import jax
    cell, cfg, mix = run.cell, run.cell.config, run.cell.traffic
    k, batch, params0, left = (res["k"], res["batch"], res["params0"],
                               res["left"])
    put, mesh = None, res["mesh"]
    if mesh is not None and cell.chips > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        axis = mesh.axis_names[0]

        def put(x):
            return jax.device_put(x, NamedSharding(mesh, P(axis)))
        params0 = jax.device_put(params0, NamedSharding(mesh, P()))
    pool = ref.make_batches(cfg, run.seed, int(cfg["fit"]["batch_pool"]),
                            batch)
    blocks = [[pool[i % len(pool)] for i in range(n)]
              for n in (k, k * (int(mix["warm_dispatches"]) - 1))]
    true = follow(ref, cfg, params0, blocks, put=put)
    # leaves whose gradient is nought to rounding move by round-off alone
    gmed = float(np.median(list(true[0][3].values())))
    skip = sorted(n for n, g in true[0][3].items() if g < 1e-3 * gmed)
    host0 = jax.device_get(params0)
    prog, before = [], host0
    for d in left:
        prog.append((d["loss"], d["momentum"],
                     {n: d["params"][n] - before[n] for n in d["params"]}))
        before = d["params"]
    readings, where = read_gaps(true, prog, skip)
    run.notes["compared_at"] = dict(
        where, leaves_skipped=skip, loss_program=[d["loss"] for d in left],
        loss_reference=[t[0] for t in true])
    variants = variants_of(batch, cell.chips)
    # "<name>": that variant's readings take the program's place;
    # "<name>,<other>,...": the others are noted beside; "all": all noted
    asked = [n for n in run.control.split(",") if n]
    unknown = [n for n in asked if n != "all" and n not in variants]
    if unknown:
        raise ValueError("--control %s: this cell has %s"
                         % (",".join(unknown), ", ".join(sorted(variants))))
    for name, kw in variants.items():
        if "all" not in asked and name not in asked:
            continue
        other = follow(ref, cfg, params0, blocks, put=put, **kw)
        got = read_gaps(true, other, skip)[0]
        if name == asked[0]:
            run.notes["program"], readings = readings, got
        else:
            run.notes["control." + name] = got
    return readings


def run(run, out=None, err=None):
    import mxnet_tpu as mx
    cell, cfg = run.cell, run.cell.config
    ref = cell.reference()
    import jax
    stated = cfg.get("matmul_precision")
    before = jax.config.jax_default_matmul_precision
    if stated:
        jax.config.update("jax_default_matmul_precision", stated)
    try:
        res = drive_fit(run, mx, ref)
    finally:
        jax.config.update("jax_default_matmul_precision", before)
    gc.collect()     # drive_fit's module and feed are gone: the state is freed
    readings = check_warm_dispatches(run, ref, res)
    failed = res["compiles"] + (0 if res["engaged"] else res["dispatches"])
    readings["dispatches_failed"] = failed
    correct, compared = runner.compare(readings, cell.limits())

    end_to_end = {"train_samples_per_s":
                  res["samples"] / (res["t_close"] - res["t_open"]),
                  "setup_s": res["t_open"] - run.t_process}
    ctx = {"cfg": cfg, "ref": ref, "spans": res["spans"], "k": res["k"],
           "batch": res["batch"], "chips": cell.chips,
           "setup_compile_s": run.meter.between(0.0, res["t_open"])[1]}
    return runner.finish(
        run, correct and res["engaged"], res["dispatches"] + res["compiles"],
        failed, end_to_end, res["memory_peak"], compared, ctx,
        {"dispatches": res["dispatches"], "samples": res["samples"]},
        out=out, err=err)
