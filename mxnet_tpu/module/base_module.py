"""BaseModule: the high-level training interface
(ref: python/mxnet/module/base_module.py, 952 LoC — fit at :368-519).
"""
from __future__ import annotations

import logging
import os
import time
from collections import deque, namedtuple

import numpy as np

from ..base import (MXNetError, TrainingPreemptedError, env_bool,
                    env_float)
from .. import metric as _metric
from .. import ndarray as nd
from ..ndarray import NDArray
from ..obs import trace as _obs_trace

BatchEndParam = namedtuple("BatchEndParams",
                           ["epoch", "nbatch", "eval_metric", "locals"])


def _as_list(obj):
    if isinstance(obj, list):
        return obj
    return [obj]


class _DispatchPipeline(object):
    """Deferred-readback window for K-step fused dispatches (docs/perf.md
    "Host off the critical path").

    ``run_steps`` returns a device-resident packed metric/sentinel array —
    a future; the ONLY host block in the steady-state train loop is its
    ``np.asarray`` readback. With depth D, ``fit`` enqueues dispatch N+D
    before fetching dispatch N's array, so the device always has the next
    scan queued while the host blocks — Speedometer, batch callbacks and
    the TrainingGuard consume D-dispatch-lagged sums in strict dispatch
    order (FIFO: the metric/guard fold sequence is bitwise identical to
    eager, only later in wall-clock). Depth 0 is eager mode.

    ``host_stall`` accumulates the seconds actually spent blocked in
    readbacks — the Speedometer pipeline suffix reads it.
    """

    # __weakref__: the Speedometer's windowed-suffix store holds its
    # sources weakly (callback.py _window_for) — a slots class without it
    # cannot be weak-referenced
    __slots__ = ("depth", "_pending", "dispatches", "retired",
                 "host_stall", "__weakref__")

    def __init__(self, depth):
        self.depth = max(0, int(depth))
        self._pending = deque()
        self.dispatches = 0
        self.retired = 0
        self.host_stall = 0.0

    def __len__(self):
        return len(self._pending)

    def push(self, sums, nsteps, nbatch, disp=None):
        """Enqueue one dispatch's device-resident sums; returns the list of
        ``(sums, nsteps, nbatch)`` entries that fell out of the window
        (fetched, ready to fold into metric/guard). ``disp`` is the
        dispatch correlation index the readback span reports
        (docs/observability.md); defaults to the push ordinal."""
        if disp is None:
            disp = self.dispatches
        self.dispatches += 1
        self._pending.append((sums, nsteps, nbatch, disp))
        out = []
        while len(self._pending) > self.depth:
            out.append(self._fetch_one())
        return out

    def drain(self):
        """Fetch everything still in flight (checkpoint sealing, epoch
        ends, per-step fallbacks: consumers need ALL sentinels covering the
        current state before acting on it)."""
        out = []
        while self._pending:
            out.append(self._fetch_one())
        return out

    def discard(self):
        """Divergence rollback: pending dispatches cover post-divergence
        state — their sums must never reach the metric or the guard. The
        device work is abandoned, not awaited."""
        self._pending.clear()

    def _fetch_one(self):
        from ..obs import trace as _obs
        sums, nsteps, nbatch, disp = self._pending.popleft()
        t0 = time.perf_counter()
        sums.fetch()
        dt = time.perf_counter() - t0
        _obs.complete("readback_stall", dt, dispatch=disp)
        self.host_stall += dt
        self.retired += 1
        return sums, nsteps, nbatch, disp


class BaseModule(object):
    """Abstract module: computation machine with forward/backward/update
    plus the high-level fit/predict/score drivers."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- high-level drivers --------------------------------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Evaluate over eval_data (ref: base_module.py score)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                       eval_metric=eval_metric, locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(params)
            actual_num_batch += 1
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Run prediction, collecting outputs (ref: base_module.py predict)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, \
                    "Cannot merge batches, as num of outputs is not the same " \
                    "in mini-batches. Maybe bucketing is used?"
            output_list2 = [nd.concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, steps_per_dispatch=None, resume=None,
            checkpoint_prefix=None, checkpoint_every_n_batches=None,
            checkpoint_keep=3, checkpoint_async=None, guard=None,
            dispatch_pipeline=None):
        """The training loop (ref: base_module.py:368-519).

        Data-parallel scaling (docs/perf.md "Data-parallel scaling"): a
        Module built over multiple contexts (or ``MXTPU_DP_DEVICES=N``)
        trains the SAME fused K-step scan over a 'data' mesh — superbatches
        land per-chip sharded straight off the producer thread, params and
        optimizer state are replicated, the gradient all-reduce runs inside
        the donated compiled body, and the packed metric/sentinel array
        comes back globally reduced so the per-K readback stays one small
        host transfer. The guard and checkpoint/resume stack below compose
        unchanged: a chip-count-N run checkpoints and resumes exactly like
        N=1.

        ``steps_per_dispatch=k`` (default: ``engine.bulk_size()``, normally
        1) bulks K train steps into ONE compiled dispatch over a stacked
        superbatch: Python dispatch overhead and the per-step host metric
        readback amortize over K (docs/perf.md "Dispatch bulking"). Metric,
        callback and lr-scheduler plumbing run at K-step granularity —
        ``nbatch`` still counts single batches, but batch_end_callback fires
        once per dispatch. Requires the fused fast path and an acc/ce-style
        metric; configurations that cannot bulk fall back to k=1 with a
        warning.

        Fault tolerance (docs/robustness.md): ``checkpoint_prefix`` turns
        on atomic checksummed checkpoints — every epoch end, plus every
        ``checkpoint_every_n_batches`` completed batches (rounded to a
        dispatch boundary under ``steps_per_dispatch``). ``resume='auto'``
        restores the newest *valid* checkpoint (params, optimizer state,
        lr/update clock, RNG stream, metric partial sums) and fast-forwards
        the train iterator past the already-trained batches, so a killed
        run re-launched with the same script continues bit-for-bit. The
        last ``checkpoint_keep`` checkpoints are retained.
        ``checkpoint_async=True`` (env default ``MXTPU_ASYNC_CKPT``) moves
        the D2H + serialize + hash + fsync work to a background writer
        thread (docs/robustness.md "Asynchronous checkpointing"): the loop
        pays only for an on-device snapshot, blocks on the writer only at
        epoch ends / rollback / teardown, and sheds (counts) a cadence
        save whose predecessor is still in flight. Checkpoint bytes and
        crash-consistency invariants are identical to the sync path.

        Host off the critical path (docs/perf.md): under
        ``steps_per_dispatch=k`` the dispatch loop is PIPELINED —
        ``dispatch_pipeline=d`` (env default ``MXTPU_DISPATCH_PIPELINE``,
        1) defers each dispatch's packed metric/sentinel readback until
        ``d`` further dispatches are enqueued, so the device never idles
        on the host between scans. Metric, Speedometer, batch callbacks
        and the guard consume d-dispatch-lagged sums in strict dispatch
        order (bitwise-identical fold sequence; divergence detection gains
        a bounded staleness of d dispatches); checkpoint sealing always
        drains the pipeline first, so a diverged state can never be sealed
        known-good. ``dispatch_pipeline=0`` — and any per-step
        configuration (k=1, monitors, epoch tails) — is the eager mode.

        Numerical guardrails (docs/robustness.md "Numerical guardrails"):
        ``guard=True`` (or a configured
        :class:`~mxnet_tpu.guard.TrainingGuard`; ``MXTPU_GUARD=1`` turns it
        on by default) makes non-finite steps device-side no-ops counted in
        ``guard.health``, watches a rolling loss window, and on divergence
        rolls back to the newest *known-good* checkpoint with the lr
        reduced by ``guard.lr_factor`` — raising
        :class:`~mxnet_tpu.guard.TrainingDivergedError` once
        ``guard.max_rollbacks`` is exhausted (or immediately when no
        ``checkpoint_prefix``/known-good checkpoint exists to roll back
        to). Requires the fused fast path; ineligible configurations warn
        and train unguarded.
        """
        assert num_epoch is not None, "please specify number of epochs"
        from ..initializer import Uniform
        ckpt_mgr = None
        resume_state = None
        if checkpoint_prefix is not None:
            from ..model import CheckpointManager
            if isinstance(checkpoint_prefix, CheckpointManager):
                # callers (tests) may pass a preconfigured manager to
                # read its counters afterwards
                ckpt_mgr = checkpoint_prefix
            else:
                ckpt_mgr = CheckpointManager(checkpoint_prefix,
                                             keep=checkpoint_keep,
                                             logger=self.logger)
        if resume in ("auto", True):
            if ckpt_mgr is None:
                raise MXNetError("fit(resume=%r) requires checkpoint_prefix"
                                 % (resume,))
            resume_state = ckpt_mgr.load_latest()
            if resume_state is None:
                self.logger.info("resume='auto': no valid checkpoint under "
                                 "%r, starting fresh", checkpoint_prefix)
            else:
                self.logger.info(
                    "resuming from checkpoint %s (epoch %d, %d batches "
                    "done)", resume_state.tag, resume_state.epoch,
                    resume_state.batches_done)
                arg_params = resume_state.arg_params
                aux_params = resume_state.aux_params
                force_init = True
                begin_epoch = resume_state.epoch
        elif resume not in (None, False):
            raise MXNetError("resume must be 'auto' or None, got %r"
                             % (resume,))
        if initializer is None:
            initializer = Uniform(0.01)
        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label, for_training=True,
                  force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        if resume_state is not None:
            self._apply_resume_state(resume_state)
        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)

        # numerical guardrails (docs/robustness.md "Numerical guardrails")
        from ..guard import TrainingGuard, _DivergenceRollback
        if guard is None and env_bool("MXTPU_GUARD"):
            guard = True
        if guard in (None, False):
            guard = None
        else:
            if not isinstance(guard, TrainingGuard):
                guard = TrainingGuard(logger=self.logger)
            can = getattr(self, "_can_guard", None)
            ok, why = (can() if can is not None
                       else (False, "this module has no fused guard "
                             "support"))
            if not ok:
                self.logger.warning(
                    "guard: training-health guardrails unavailable (%s); "
                    "training UNGUARDED", why)
                guard = None
            elif ckpt_mgr is None:
                self.logger.warning(
                    "guard: no checkpoint_prefix — divergence cannot roll "
                    "back and will raise TrainingDivergedError")

        # asynchronous checkpointing (docs/robustness.md): attach a
        # background writer so cadence saves cost the loop only a device
        # snapshot; created here (after guard resolution) so back-pressure
        # skips count into THIS run's health object
        writer_owned = False
        if ckpt_mgr is not None:
            if checkpoint_async is None:
                checkpoint_async = env_bool("MXTPU_ASYNC_CKPT")
            if checkpoint_async and ckpt_mgr.async_writer is None:
                from ..model import AsyncCheckpointWriter
                from .. import guard as _guard_mod
                ckpt_mgr.async_writer = AsyncCheckpointWriter(
                    logger=self.logger,
                    health=(guard.health if guard is not None
                            else _guard_mod.TRAINING_HEALTH))
                writer_owned = True

        fused_step = getattr(self, "_try_fused_fit_step", None)
        fused_dispatch = getattr(self, "_dispatch_fused_steps", None)
        # knob resolution (docs/perf.md "Autotuning"): explicit arg > env
        # > tuning DB > built-in default, per knob — a DB hit is logged
        # once per run via the obs registry, so the training log always
        # says where the configuration came from
        from .. import autotune as _autotune
        k, pl_depth, _knob_src = _autotune.resolve_fit_knobs(
            self, train_data, steps_per_dispatch, dispatch_pipeline,
            logger=self.logger)
        if k > 1:
            reason = None
            if monitor is not None:
                reason = "a monitor needs per-step executor access"
            elif fused_dispatch is None:
                reason = "this module has no fused multi-step path"
            elif not hasattr(train_data, "superbatch"):
                reason = "train_data is not a DataIter (no superbatch mode)"
            else:
                # module-level eligibility (optimizer/grad_req/dist) AND
                # the metric's packed-accumulator layout (docs/perf.md
                # "Packed accumulators") are knowable NOW — checking here
                # instead of per dispatch avoids silently paying
                # superbatch stacking for an epoch the per-step path ends
                # up training anyway, and guarantees every fallback warns
                # with a reason that names WHY (metric, shapes, config)
                can = getattr(self, "_can_bulk_dispatch", None)
                if can is not None:
                    ok, why = can(eval_metric)
                    if not ok:
                        reason = why
            if reason is not None:
                self.logger.warning(
                    "steps_per_dispatch=%d unavailable (%s); training "
                    "with 1", k, reason)
                k = 1

        # pipelined dispatch (docs/perf.md "Host off the critical path"):
        # eager mode is auto-selected for per-step configurations — k=1
        # trains through per-step host metrics, whose output readback is
        # the sync point the pipeline would otherwise defer
        pl_depth = max(0, int(pl_depth))
        if k <= 1 or fused_dispatch is None:
            pl_depth = 0
        if getattr(self, "_is_dist_kvstore", lambda: False)():
            # elastic dist training (docs/robustness.md): every dispatch
            # already blocks on the cross-process reduction so a peer
            # failure surfaces AT its dispatch — a deferred-readback
            # window would only widen the state a WorkerLostError has to
            # discard at re-form time
            pl_depth = 0
        pipeline = _DispatchPipeline(pl_depth)
        if k > 1:
            # device-fed input tier (docs/perf.md "Device-fed input
            # pipeline"): the prefetcher stacks K host batches per dispatch
            # and lands them D+1 deep ahead of the depth-D dispatch
            # pipeline, charging stack/H2D/stall to the pipeline's
            # PipelineStats. A data-parallel mesh hands it the batch-axis
            # sharding so every stacked array LANDS per-chip sharded — the
            # one H2D is the scatter, and the dispatch loop never pays a
            # resharding copy (docs/perf.md "Data-parallel scaling")
            sb_sharding = getattr(self, "_superbatch_sharding", None)
            from .. import data as _data
            train_iter = _data.DevicePrefetcher(
                train_data, k, depth=pl_depth,
                sharding=sb_sharding() if sb_sharding is not None else None)
        else:
            train_iter = train_data
        # deterministic resume through shuffling iterators: pin the data
        # order to the ABSOLUTE epoch — a fresh process resuming at epoch E
        # must re-derive epoch E's shuffle, not epoch 0's (iterators
        # without epoch-addressable order ignore this)
        iter_set_epoch = getattr(train_iter, "set_epoch", None)
        if iter_set_epoch is not None:
            iter_set_epoch(begin_epoch)
        data_stats = (getattr(train_iter, "stats", None)
                      or getattr(train_iter, "data_stats", None))

        note_retired = getattr(self, "_note_dispatch_retired", None)

        def _consume(entries, epoch):
            """Retire dispatches in dispatch order: fold each one's sums
            into the metric and the guard, then fire ITS batch callback
            before folding the next — so every callback observes the
            metric exactly as the eager mode would have at the same
            nbatch (the fold+fire sequence is what the bitwise
            pipelined-vs-eager parity contract pins)."""
            from .. import obs as _obs
            for sums, nsteps, nb, disp in entries:
                _metric.update_from_device_sums(eval_metric, sums)
                if guard is not None:
                    guard.on_dispatch(loss_sum=sums.loss_sum,
                                      nsamp=sums.num_samples,
                                      skipped=sums.skipped,
                                      grad_norm=sums.last_grad_norm,
                                      nsteps=nsteps)
                if note_retired is not None:
                    note_retired(sums, nsteps)
                # flight recorder: the per-dispatch counter delta rides
                # the marks ring so a post-mortem shows what each of the
                # last K dispatches changed (docs/observability.md)
                _obs.flight.note("dispatch_retired", dispatch=disp,
                                 nbatch=nb, nsteps=nsteps)
                if batch_end_callback is not None:
                    cb_params = BatchEndParam(
                        epoch=epoch, nbatch=nb, eval_metric=eval_metric,
                        locals={"guard": guard, "pipeline": pipeline,
                                "eval_metric": eval_metric, "self": self,
                                "data_stats": data_stats})
                    for callback in _as_list(batch_end_callback):
                        callback(cb_params)

        # flight-recorder baseline (docs/observability.md): mark the run
        # start so the FIRST retired dispatch's counter delta covers that
        # dispatch, not "everything since the process began"
        from ..obs import flight as _obs_flight
        from ..kvstore import WorkerLostError as _WorkerLost
        _obs_flight.note("fit_start", epoch=begin_epoch)

        # graceful preemption (docs/robustness.md "Graceful preemption"):
        # SIGTERM is the TPU-preemption shape — the scheduler gives the VM
        # a grace window, then pulls the plug. Install a handler that only
        # SETS A FLAG (checked once per loop iteration, so the signal never
        # interrupts a dispatch mid-flight) and starts a hard wall-clock
        # deadline: a graceful exit that cannot finish in time degrades to
        # an abrupt one, which the SIGKILL resume contract already covers.
        # Installed only when there is a checkpoint manager to seal an
        # emergency save into, and only on the main thread (signal() is
        # main-thread-only; nested/threaded fits keep default delivery).
        import signal as _signal
        import threading as _threading
        preempt = None
        prev_sigterm = None
        sigterm_installed = False
        if (ckpt_mgr is not None
                and not env_bool("MXTPU_SIGTERM_GRACEFUL_OFF")
                and _threading.current_thread() is _threading.main_thread()):
            preempt = {"flag": False, "timer": None}
            _deadline_s = env_float("MXTPU_SIGTERM_DEADLINE", 30.0)

            def _on_sigterm(signum, frame, _p=preempt, _d=_deadline_s):
                if _p["flag"]:
                    return
                _p["flag"] = True
                t = _threading.Timer(_d, os._exit, args=(124,))
                t.daemon = True
                t.start()
                _p["timer"] = t
            prev_sigterm = _signal.getsignal(_signal.SIGTERM)
            _signal.signal(_signal.SIGTERM, _on_sigterm)
            sigterm_installed = True
        try:
            epoch = begin_epoch
            while epoch < num_epoch:
                tic = time.time()
                eval_metric.reset()
                nbatch = -1
                since_ckpt = 0
                resume_skip = 0
                if (resume_state is not None
                        and epoch == resume_state.epoch
                        and resume_state.batches_done > 0):
                    # mid-epoch resume (or divergence rollback): replay the
                    # metric's partial sums and fast-forward past the
                    # already-trained batches (the iterator is consumed but
                    # nothing is computed)
                    resume_skip = resume_state.batches_done
                    self._restore_metric_state(eval_metric,
                                               resume_state.metric_state)
                    self.logger.info("resume: fast-forwarding %d batches "
                                     "of epoch %d", resume_skip, epoch)
                try:
                    for data_batch in train_iter:
                        tail_batches = None
                        stepped_eager = False
                        if resume_skip > 0:
                            n = getattr(data_batch, "num_steps", 1)
                            if n <= resume_skip:
                                resume_skip -= n
                                nbatch += n
                                continue
                            # checkpoint cut through a superbatch (k changed
                            # between runs): train only the un-skipped tail,
                            # per-step
                            tail_batches = data_batch.unstack()[resume_skip:]
                            nbatch += resume_skip
                            resume_skip = 0
                        if monitor is not None:
                            monitor.tic()
                        # fast path: K fused steps in one donated lax.scan
                        # dispatch; the packed metric/sentinel readback is
                        # DEFERRED through the pipeline so dispatch N+1 is
                        # enqueued before dispatch N's np.asarray
                        sums = None
                        disp_id = getattr(data_batch, "sb_seq",
                                          pipeline.dispatches)
                        if (tail_batches is None and k > 1
                                and getattr(data_batch, "num_steps", 0) == k
                                and fused_dispatch is not None):
                            # the "dispatch" span is the ENQUEUE — the
                            # device-side scan runs async; its readback is
                            # the correlated readback_stall span
                            with _obs_trace.span("dispatch",
                                                 dispatch=disp_id,
                                                 k=data_batch.num_steps,
                                                 epoch=epoch):
                                sums = fused_dispatch(data_batch, guard)
                        if sums is not None:
                            nbatch += data_batch.num_steps
                            since_ckpt += data_batch.num_steps
                            _consume(pipeline.push(
                                sums, data_batch.num_steps, nbatch,
                                disp=disp_id), epoch)
                        else:
                            # per-step path: the general executor loop, also
                            # the epoch tail (num_steps < k) without a
                            # K'-recompile. Eager by contract — per-step
                            # host metrics must fold in dispatch order, so
                            # everything still in flight retires first.
                            _consume(pipeline.drain(), epoch)
                            if tail_batches is None:
                                tail_batches = (
                                    data_batch.unstack()
                                    if hasattr(data_batch, "unstack")
                                    else [data_batch])
                            for batch in tail_batches:
                                nbatch += 1
                                since_ckpt += 1
                                if guard is not None:
                                    guard.last_step_skipped = False
                                # fused single step (falls back to the
                                # executor path when the module configuration
                                # needs it — monitor, dist kvstore, grad_req,
                                # unfused optimizer, bucketing/shared
                                # modules)
                                if monitor is not None or fused_step is None \
                                        or not fused_step(batch, guard):
                                    self.forward_backward(batch)
                                    self.update()
                                # a device-side skipped (non-finite) step
                                # contributes nothing to the metric
                                if guard is None \
                                        or not guard.last_step_skipped:
                                    self.update_metric(eval_metric,
                                                       batch.label)
                            stepped_eager = True
                        if monitor is not None:
                            monitor.toc_print()
                        if guard is not None and guard.diverged:
                            # unwind to the rollback handler BEFORE the
                            # checkpoint block: a diverged state must never
                            # be sealed into a checkpoint
                            raise _DivergenceRollback()
                        if (ckpt_mgr is not None
                                and checkpoint_every_n_batches
                                and since_ckpt >= checkpoint_every_n_batches):
                            # checkpoint sealing needs EVERY sentinel
                            # covering the state it is about to seal: drain
                            # the pipeline, re-check divergence, then gate
                            # on the (now fully informed) guard
                            _consume(pipeline.drain(), epoch)
                            if guard is not None and guard.diverged:
                                raise _DivergenceRollback()
                            if guard is None or guard.ok_to_checkpoint():
                                # a mid-spike state is suspect: deferring the
                                # save keeps the newest known-good checkpoint
                                # PRE-spike, so a rollback escapes the
                                # divergence instead of re-entering it
                                with _obs_trace.span("checkpoint",
                                                     dispatch=disp_id,
                                                     epoch=epoch,
                                                     nbatch=nbatch + 1):
                                    ckpt_mgr.save(self, epoch, nbatch + 1,
                                                  metric=eval_metric)
                                since_ckpt = 0
                        self._check_worker_health(
                            ckpt_mgr, eval_metric, epoch, nbatch,
                            drain_pipeline=lambda e=epoch: _consume(
                                pipeline.drain(), e),
                            guard=guard)
                        if preempt is not None and preempt["flag"]:
                            # SIGTERM landed: retire everything in flight
                            # (an emergency checkpoint must never seal a
                            # state its sentinels haven't cleared), then
                            # seal + raise — all inside the deadline timer
                            _consume(pipeline.drain(), epoch)
                            self._graceful_preempt(preempt, ckpt_mgr,
                                                   guard, eval_metric,
                                                   epoch, nbatch)
                        if stepped_eager and batch_end_callback is not None:
                            # eagerly-trained batches (per-step path): one
                            # callback at the current nbatch, exactly as
                            # before
                            batch_end_params = BatchEndParam(
                                epoch=epoch, nbatch=nbatch,
                                eval_metric=eval_metric, locals=locals())
                            for callback in _as_list(batch_end_callback):
                                callback(batch_end_params)
                    # epoch end: everything still in flight retires (folds
                    # + fires its callbacks) before the epoch is sealed
                    # (train metric logged, epoch-end checkpoint written) —
                    # and a divergence surfacing in those last sentinels
                    # still rolls back, never seals
                    _consume(pipeline.drain(), epoch)
                    if guard is not None and guard.diverged:
                        raise _DivergenceRollback()
                except _DivergenceRollback:
                    # divergence: restore the newest known-good checkpoint,
                    # rewind the trainer clock, reduce lr, and re-enter the
                    # epoch loop at the restored cursor (the iterator is
                    # reset and re-fast-forwarded like a resume). Dispatches
                    # still in the pipeline cover post-divergence state:
                    # their sums must never reach the metric or the guard
                    _obs_trace.instant("divergence", epoch=epoch,
                                       nbatch=nbatch,
                                       reason=guard.diverged_reason)
                    pipeline.discard()
                    resume_state = self._guard_rollback(guard, ckpt_mgr)
                    epoch = resume_state.epoch
                    train_iter.reset()
                    if iter_set_epoch is not None:
                        # the rollback rewinds the epoch cursor: re-pin the
                        # data order (reset() alone advances it by one)
                        iter_set_epoch(epoch)
                    continue
                except _WorkerLost as wle:
                    # elastic membership (docs/robustness.md "Elastic
                    # distributed training"): a peer died mid-epoch —
                    # discard in-flight dispatches (their cross-worker
                    # reductions never completed), seal an emergency
                    # checkpoint, re-form the ring at N-1, adopt the
                    # leader's state, and re-enter the epoch loop exactly
                    # like a resume
                    _obs_trace.instant("worker_lost", epoch=epoch,
                                       nbatch=nbatch)
                    pipeline.discard()
                    resume_state = self._elastic_reform(
                        wle, ckpt_mgr, guard, eval_metric, epoch, nbatch,
                        train_data)
                    epoch = resume_state.epoch
                    train_iter.reset()
                    if iter_set_epoch is not None:
                        iter_set_epoch(epoch)
                    continue

                for name, val in eval_metric.get_name_value():
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
                toc = time.time()
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                                 (toc - tic))
                if guard is not None:
                    h = guard.health.report()
                    if h["skipped"] or h["rollbacks"] or h["retraces"]:
                        self.logger.info(
                            "Epoch[%d] TrainingHealth: skipped=%d "
                            "rollbacks=%d divergences=%d retraces=%d "
                            "last_grad_norm=%s",
                            epoch, h["skipped"], h["rollbacks"],
                            h["divergences"], h["retraces"],
                            h["last_grad_norm"])

                arg_params, aux_params = self.get_params()
                self.set_params(arg_params, aux_params)
                if epoch_end_callback is not None:
                    for callback in _as_list(epoch_end_callback):
                        callback(epoch, self.symbol, arg_params, aux_params)

                if eval_data:
                    res = self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback,
                        epoch=epoch)
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f", epoch,
                                         name, val)
                if ckpt_mgr is not None and (guard is None
                                             or guard.ok_to_checkpoint()):
                    # epoch boundary checkpoint: cursor points at the clean
                    # start of the next epoch (deferred while the loss
                    # watcher is mid-spike, same as cadence saves). The
                    # epoch end is a BARRIER for async saves: an in-flight
                    # cadence save lands first (so the epoch-end save is
                    # never shed by back-pressure), then fit blocks until
                    # the epoch's state is durably on disk
                    ckpt_mgr.drain()
                    with _obs_trace.span("checkpoint", epoch=epoch + 1,
                                         nbatch=0):
                        ckpt_mgr.save(self, epoch + 1, 0)
                    ckpt_mgr.drain()
                # epoch boundary is the ONLY admission point for late
                # joiners: a mid-epoch join would change the gradient
                # denominator between checkpoints
                self._admit_dist_joiners(ckpt_mgr, train_data)
                if train_iter is train_data or epoch < num_epoch - 1:
                    train_iter.reset()
                else:
                    # final epoch of a superbatch wrapper: stop its producer
                    # thread (reset() would spawn one that pre-pulls batches
                    # from — and pins device buffers for — an epoch nobody
                    # consumes) and hand the user back a reset base iterator
                    train_iter.close()
                    train_data.reset()
                epoch += 1
        finally:
            if sigterm_installed:
                _signal.signal(_signal.SIGTERM, prev_sigterm)
                # a SIGTERM that arrived too late to be honored (epoch tail,
                # teardown) must not leave a live os._exit timer behind
                if preempt["timer"] is not None:
                    preempt["timer"].cancel()
            if ckpt_mgr is not None and ckpt_mgr.async_writer is not None:
                # teardown barrier: the in-flight save lands (or is reaped)
                # before fit returns; a writer fit created is shut down AND
                # detached so the manager stays usable (a later fit makes a
                # fresh writer, a manual save falls back to sync) — its
                # counters stay readable via last_async_writer. A
                # caller-attached writer is only drained.
                if writer_owned:
                    w = ckpt_mgr.async_writer
                    w.close()
                    ckpt_mgr.async_writer = None
                    ckpt_mgr.last_async_writer = w
                else:
                    ckpt_mgr.async_writer.drain()
            if train_iter is not train_data:
                # exception paths included: never leave a producer thread
                # consuming the user's iterator (close() is idempotent)
                train_iter.close()

    # -- fused-dispatch hooks shared by Module and BucketingModule ------
    def _note_dispatch_retired(self, sums, nsteps):
        """Retirement hook for the dispatch pipeline: advance the
        host-side step-clock mirror for a GUARDED dispatch once its
        sentinels (the device-side skip count) have been fetched —
        skipped steps are full no-ops, the clock must not count them.
        Unguarded dispatches advanced at dispatch time."""
        if getattr(sums, "guarded", False):
            self._fused_host_step += int(nsteps) - sums.skipped

    def _feed_guard_sentinels(self, guard, sent):
        """Host side of one GUARDED single-step dispatch: advance the
        step-clock mirror skip-aware and feed the packed ``[loss,
        correct, nsamp, skipped, grad_norm]`` sentinel array to the
        guard (``last_step_skipped`` tells fit to keep the skipped batch
        out of host-side metrics). ONE definition — the sentinel packet
        layout must never drift between the Module and BucketingModule
        paths."""
        self._fused_host_step += 1 - int(sent[3] > 0)
        guard.on_dispatch(loss_sum=float(sent[0]), nsamp=float(sent[2]),
                          skipped=float(sent[3]),
                          grad_norm=float(sent[4]), nsteps=1)
        guard.last_step_skipped = bool(sent[3] > 0)

    def _adopt_retrace_result(self, e, nsteps, guard):
        """``MXTPU_TRACECHECK=error`` raised mid-dispatch
        (tracecheck.RetraceError): the dispatch already ran and DONATED
        the previous fused state, and the new state rides in
        ``e.result`` — adopt it so ``_fused_state`` never dangles on
        deleted buffers (``get_params`` / emergency checkpoints after
        catching the error keep working). The step-clock mirror advances
        as on the success path; the run is aborting, so the guarded
        paths' sentinel readback costs nothing that matters."""
        if e.result is None:
            return
        self._fused_state = e.result[0]
        self._fused_outputs = None
        self._fused_dirty = True
        self._params_dirty = True
        if guard is None:
            self._fused_host_step += nsteps
            return
        tail = e.result[-1]
        if hasattr(tail, "skipped"):   # StepMetrics (run_steps path)
            skipped = int(tail.skipped)
        else:                          # packed sentinel array (step path)
            skipped = int(np.asarray(tail)[3] > 0)
        self._fused_host_step += nsteps - skipped

    # -- fault tolerance hooks (docs/robustness.md) ---------------------
    def _graceful_preempt(self, preempt, ckpt_mgr, guard, eval_metric,
                          epoch, nbatch):
        """Honor a SIGTERM (docs/robustness.md "Graceful preemption"): the
        dispatch pipeline is already drained by the caller — seal an
        emergency checkpoint with the async writer drained on both sides
        (so the save is never shed by back-pressure and is durably on disk
        before we exit), dump the flight recorder, cancel the hard-deadline
        timer and raise :class:`TrainingPreemptedError`. The checkpoint
        cursor is ``nbatch + 1`` mid-epoch — strictly newer than the last
        cadence save a SIGKILL at the same moment would resume from."""
        tag = None
        if ckpt_mgr is not None and (guard is None
                                     or guard.ok_to_checkpoint()):
            ckpt_mgr.drain()
            with _obs_trace.span("checkpoint", epoch=epoch,
                                 nbatch=nbatch + 1, preempt=True):
                ckpt_mgr.save(self, epoch, nbatch + 1, metric=eval_metric)
            ckpt_mgr.drain()
            tag = "e%04d-b%08d" % (epoch, nbatch + 1)
        self.logger.warning(
            "SIGTERM: graceful preemption — emergency checkpoint %s sealed "
            "at epoch %d batch %d; re-launch with resume='auto' to "
            "continue", tag or "(none: guard mid-spike or no manager)",
            epoch, nbatch + 1)
        from ..obs import flight as _flight
        _flight.dump("TrainingPreemptedError: SIGTERM preemption",
                     extra={"epoch": epoch, "nbatch": nbatch, "tag": tag})
        if preempt["timer"] is not None:
            preempt["timer"].cancel()
        raise TrainingPreemptedError(
            "training preempted by SIGTERM at epoch %d batch %d "
            "(emergency checkpoint: %s) — resume='auto' continues from it"
            % (epoch, nbatch + 1, tag), epoch=epoch,
            batches_done=nbatch + 1, tag=tag)

    def _guard_rollback(self, guard, ckpt_mgr):
        """Divergence recovery (docs/robustness.md "Numerical guardrails"):
        restore the newest known-good checkpoint, rewind the trainer clock
        and RNG stream, reduce the lr by ``guard.lr_factor``, and hand the
        restored cursor back to ``fit``'s epoch loop (which resets and
        re-fast-forwards the iterator). Raises
        :class:`~mxnet_tpu.guard.TrainingDivergedError` when the rollback
        budget is exhausted or there is nothing safe to roll back to."""
        from ..guard import TrainingDivergedError
        from ..obs import flight as _flight

        def _diverged(msg):
            # the post-mortem (docs/observability.md): the last K
            # dispatches' spans + counter deltas land on disk BEFORE the
            # error unwinds — dump() never raises into this failure path
            _flight.dump("TrainingDivergedError: %s" % msg,
                         extra={"health": guard.health.report()})
            return TrainingDivergedError(msg, health=guard.health)

        if guard.health.rollbacks >= guard.max_rollbacks:
            raise _diverged(
                "training diverged again after %d rollback(s) "
                "(max_rollbacks=%d): %s"
                % (guard.health.rollbacks, guard.max_rollbacks,
                   guard.diverged_reason))
        if ckpt_mgr is None:
            raise _diverged(
                "training diverged (%s) and fit() has no checkpoint_prefix "
                "to roll back to — configure checkpoints or lower the lr"
                % (guard.diverged_reason,))
        # async saves: the rollback target search must see the newest save
        # fully on disk (manifest + latest), not race a half-written one
        ckpt_mgr.drain()
        st = ckpt_mgr.load_latest()
        if st is None:
            raise _diverged(
                "training diverged (%s) and no known-good checkpoint "
                "exists under %r" % (guard.diverged_reason,
                                     ckpt_mgr.prefix))
        self.logger.warning(
            "TrainingGuard: rolling back to known-good checkpoint %s "
            "(epoch %d, %d batches done), reducing lr by x%g",
            st.tag, st.epoch, st.batches_done, guard.lr_factor)
        self.init_params(initializer=None, arg_params=st.arg_params,
                         aux_params=st.aux_params, allow_missing=False,
                         force_init=True)
        # the diverged fused state must NOT survive (its optimizer state is
        # poisoned); drop it BEFORE restoring the checkpointed one
        self._drop_fused_state()
        self._apply_resume_state(st)
        self._scale_lr(guard.lr_factor)
        # a SURVIVED divergence still leaves a post-mortem: the timeline
        # that led into the rollback is exactly what the next tuning pass
        # needs, and a rerun would not reproduce it (captured BEFORE
        # note_rollback clears diverged_reason)
        _flight.dump("guard rollback to %s (%s)"
                     % (st.tag, guard.diverged_reason),
                     extra={"health": guard.health.report(),
                            "rollback_tag": st.tag,
                            "rollback_epoch": st.epoch})
        guard.note_rollback(st.tag)
        return st

    def _elastic_reform(self, err, ckpt_mgr, guard, eval_metric, epoch,
                        nbatch, train_data=None):
        """Worker-loss recovery (docs/robustness.md "Elastic distributed
        training"): survivors seal a durable emergency checkpoint, re-form
        the control-plane ring at N-1, adopt ONE authoritative state (the
        leader's newest checkpoint — survivors can legitimately be one
        step apart at the failure point), re-derive the gradient rescale
        and this worker's data shard for the shrunken world, and hand
        ``fit`` a resume cursor. Raises :class:`WorkerLostError` (with a
        flight dump) when the re-form budget (``MXTPU_KV_MAX_REFORMS``)
        is exhausted or the store has no elastic transport."""
        from ..kvstore import WorkerLostError
        from ..obs import flight as _flight
        kv = getattr(self, "_kvstore", None)
        if kv is None or getattr(kv, "reform", None) is None \
                or ckpt_mgr is None:
            why = ("fit() has no checkpoint_prefix to recover through"
                   if kv is not None and ckpt_mgr is None
                   else "kvstore has no elastic re-form support")
            _flight.dump("WorkerLostError: %s" % err, extra={"elastic": why})
            raise err
        max_reforms = int(getattr(kv, "max_reforms", 0))
        if int(getattr(kv, "reforms", 0)) >= max_reforms:
            _flight.dump("WorkerLostError: re-form budget exhausted",
                         extra={"reforms": int(kv.reforms),
                                "max_reforms": max_reforms,
                                "liveness": kv.liveness_table()})
            raise WorkerLostError(
                "worker lost and the re-form budget is exhausted (%d "
                "re-form(s) this run, MXTPU_KV_MAX_REFORMS=%d): %s"
                % (kv.reforms, max_reforms, err)) from err
        self.logger.warning(
            "worker lost (%s): re-forming the ring (re-form %d/%d)",
            err, int(kv.reforms) + 1, max_reforms)
        # 1. seal this survivor's own durable emergency checkpoint BEFORE
        # any further ring traffic: if the re-form itself fails, the run
        # stays resumable from here (drain twice — an in-flight cadence
        # save lands first, then the emergency save must be on disk)
        ckpt_mgr.drain()
        if guard is None or guard.ok_to_checkpoint():
            ckpt_mgr.save(self, epoch, nbatch + 1, metric=eval_metric)
        ckpt_mgr.drain()
        # 2. re-form at N-1 (plus any joiners already waiting)
        kv.reform()
        # 3. one authoritative state for the new ring
        st = self._adopt_leader_checkpoint(kv, ckpt_mgr)
        self.init_params(initializer=None, arg_params=st.arg_params,
                         aux_params=st.aux_params, allow_missing=False,
                         force_init=True)
        self._drop_fused_state()
        # rescale/batch-size re-derivation MUST precede the optimizer
        # state restore: set_optimizer builds a fresh (empty) kvstore
        # updater, which _apply_resume_state then re-fills
        self._refresh_dist_scale()
        self._apply_resume_state(st)
        self._reshard_train_data(kv, train_data)
        _flight.dump(
            "ring re-formed at %d worker(s), resuming from %s"
            % (kv.num_workers, st.tag),
            extra={"liveness": kv.liveness_table(),
                   "reforms": int(kv.reforms), "resume_tag": st.tag,
                   "resume_epoch": st.epoch,
                   "batches_done": st.batches_done})
        self.logger.warning(
            "ring re-formed: %d worker(s) (this rank now index %d), "
            "resuming from %s (epoch %d, %d batches done)",
            kv.num_workers, kv.worker_index, st.tag, st.epoch,
            st.batches_done)
        return st

    def _adopt_leader_checkpoint(self, kv, ckpt_mgr):
        """Broadcast the leader's newest checkpoint BYTES over the ring
        and install + load it on every member. Survivors may be one step
        apart at the failure point; adopting one authoritative state is
        what makes the re-formed replicas bitwise-identical — and a fresh
        resume from the same prefix then reproduces exactly this state
        (the invariant the elastic test pins)."""
        payload = b""
        if kv.worker_index == 0:
            payload = ckpt_mgr.export_latest()
        blob = kv.broadcast_bytes(payload)
        if kv.worker_index != 0 and blob:
            ckpt_mgr.import_blob(blob)
        st = ckpt_mgr.load_latest()
        if st is None:
            raise MXNetError(
                "ring re-form: no loadable checkpoint after the leader "
                "broadcast (prefix %r)" % (ckpt_mgr.prefix,))
        return st

    def _reshard_train_data(self, kv, train_data):
        """Re-derive this worker's data shard from its new (index, size)
        after a membership change. Iterators expose ``reshard_workers``;
        anything else keeps its original shard — correct but overlapping,
        so the run says so."""
        if train_data is None:
            return
        reshard = getattr(train_data, "reshard_workers", None)
        if reshard is not None:
            reshard(kv.worker_index, kv.num_workers)
        else:
            self.logger.warning(
                "train_data has no reshard_workers(index, size): keeping "
                "the pre-reform shard (the dead worker's shard is not "
                "redistributed this run)")

    def _admit_dist_joiners(self, ckpt_mgr, train_data):
        """Epoch-boundary admission (docs/robustness.md "Elastic
        distributed training"): when a late worker has published a join
        request, re-form the ring to include it and broadcast the
        leader's epoch-boundary checkpoint as its warm start; incumbents
        re-derive shards and rescale exactly like a loss re-form. The
        decision itself rides a leader broadcast so every incumbent
        reaches the SAME verdict — per-member polling could split on a
        request that lands mid-poll."""
        kv = getattr(self, "_kvstore", None)
        if kv is None or "dist" not in getattr(kv, "type", ""):
            return
        poll = getattr(kv, "pending_joiners", None)
        bcast = getattr(kv, "broadcast_bytes", None)
        if poll is None or bcast is None or ckpt_mgr is None \
                or kv.num_workers <= 0:
            return
        import pickle
        payload = b""
        if kv.worker_index == 0:
            payload = pickle.dumps(sorted(poll()))
        blob = bcast(payload)
        if not blob:
            return  # no elastic transport: broadcast_bytes is identity
        pending = pickle.loads(blob)
        if not pending:
            return
        self.logger.info("admitting joining worker(s) %s at the epoch "
                         "boundary", list(pending))
        kv.reform()
        self._adopt_leader_checkpoint(kv, ckpt_mgr)
        self._drop_fused_state()
        self._refresh_dist_scale()
        self._reshard_train_data(kv, train_data)

    def _refresh_dist_scale(self):
        """Hook: re-derive the gradient rescale (1 / global batch) after
        a dist membership change. Subclasses with an optimizer
        override."""

    def _drop_fused_state(self):
        """Hook: discard (not flush) any fused device state so the next
        dispatch reseeds from the just-restored params. Subclasses with a
        fused path override."""

    def _scale_lr(self, factor):
        """Hook: reduce the learning rate everywhere the next step reads it
        (rollback policy). Subclasses with an optimizer override."""

    def _apply_resume_state(self, st):
        """Restore optimizer state, update clock and RNG stream from a
        validated checkpoint (params/aux already rode ``init_params``).
        Called by ``fit`` right after ``init_optimizer``."""
        if st.opt_states_file and hasattr(self, "load_optimizer_states"):
            self.load_optimizer_states(st.opt_states_file)
        self._restore_trainer_clock(st.num_update,
                                    getattr(st, "fused_step", None))
        st.restore_rng()

    def _restore_trainer_clock(self, num_update, fused_step=None):
        """Hook: carry the optimizer update count across a resume so lr
        schedules and per-step noise streams continue where the killed run
        stopped. ``fused_step`` is the device step counter, which trails
        ``num_update`` by the number of guard-skipped steps (a skip is a
        full no-op). Subclasses with an optimizer override."""

    @staticmethod
    def _restore_metric_state(eval_metric, state):
        """Replay a checkpointed metric's partial sums into a freshly reset
        metric (scalar or per-output list state; composites skip)."""
        if not state or not hasattr(eval_metric, "sum_metric"):
            return
        try:
            s, n = state
        except (TypeError, ValueError):
            return
        eval_metric.sum_metric = s
        eval_metric.num_inst = n

    def _check_worker_health(self, ckpt_mgr, eval_metric, epoch, nbatch,
                             drain_pipeline=None, guard=None):
        """Dist kvstore degradation policy: feed ``num_dead_node`` into
        warn -> emergency checkpoint -> ``WorkerLostError`` escalation
        (KVStore.check_health throttles the underlying heartbeat scan).
        No-op for local stores."""
        kv = getattr(self, "_kvstore", None)
        if kv is None or "dist" not in getattr(kv, "type", ""):
            return
        on_degraded = None
        if ckpt_mgr is not None:
            def on_degraded():
                # checkpoint sealing needs every in-flight dispatch retired
                # first (metric folds + guard sentinels + step mirror must
                # cover the state being saved) — same invariant as the
                # cadence/epoch-end sites, and a diverged state still must
                # never seal known-good
                if drain_pipeline is not None:
                    drain_pipeline()
                if guard is not None and not guard.ok_to_checkpoint():
                    self.logger.warning(
                        "worker-loss emergency checkpoint skipped: the "
                        "guard reports the current state unsafe to seal")
                    return
                # emergency checkpoint must never be shed by async
                # back-pressure (a cadence save in flight) and must be
                # durable BEFORE check_health escalates to WorkerLostError
                ckpt_mgr.drain()
                ckpt_mgr.save(self, epoch, nbatch + 1, metric=eval_metric)
                ckpt_mgr.drain()
        kv.check_health(on_degraded=on_degraded)

    # -- symbol / params accessors -------------------------------------
    @property
    def symbol(self):
        return self._symbol

    def get_params(self):
        raise NotImplementedError()

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        raise NotImplementedError()

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)

    def save_params(self, fname):
        from ..model import atomic_write_bytes, _param_save_bytes
        arg_params, aux_params = self.get_params()
        atomic_write_bytes(fname, _param_save_bytes(arg_params, aux_params))

    def load_params(self, fname):
        from ..model import _split_param_dict
        save_dict = nd.load(fname)
        arg_params, aux_params = _split_param_dict(save_dict, fname)
        self.set_params(arg_params, aux_params)

    # -- computation API (implemented by subclasses) --------------------
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError()

    def backward(self, out_grads=None):
        raise NotImplementedError()

    def update(self):
        raise NotImplementedError()

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError()

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError()

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError()

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError()

    def install_monitor(self, mon):
        raise NotImplementedError()

    @property
    def data_names(self):
        raise NotImplementedError()

    @property
    def output_names(self):
        raise NotImplementedError()

    @property
    def data_shapes(self):
        raise NotImplementedError()

    @property
    def label_shapes(self):
        raise NotImplementedError()

    @property
    def output_shapes(self):
        raise NotImplementedError()
