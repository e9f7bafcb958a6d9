"""Model-level helpers and the legacy FeedForward estimator
(ref: python/mxnet/model.py, 946 LoC — kvstore helpers :40-117,
checkpointing, FeedForward :387).
"""
from __future__ import annotations

import atexit
import errno
import glob
import hashlib
import json
import logging
import os
import threading
import time
from collections import namedtuple

import numpy as np

from .base import MXNetError
from . import ndarray as nd
from .ndarray import NDArray
from . import symbol as sym
from . import kvstore as kvs
from . import io
from .context import cpu, current_context

BatchEndParam = namedtuple("BatchEndParams",
                           ["epoch", "nbatch", "eval_metric", "locals"])


def _create_kvstore(kvstore, num_device, arg_params):
    """Create kvstore per the reference decision table (ref: model.py:40-77)."""
    update_on_kvstore = True
    if kvstore is None:
        kv = None
    elif isinstance(kvstore, kvs.KVStore):
        kv = kvstore
    elif isinstance(kvstore, str):
        if num_device == 1 and "dist" not in kvstore:
            # a single device: no need for kvstore at all
            kv = None
        else:
            kv = kvs.create(kvstore)
            if kvstore == "local":
                max_size = max(np.prod(param.shape)
                               for param in arg_params.values())
                if max_size < 1024 * 1024 * 16:
                    update_on_kvstore = False
    else:
        raise TypeError("kvstore must be KVStore, str or None")
    if kv is None:
        update_on_kvstore = False
    return (kv, update_on_kvstore)


def _initialize_kvstore(kvstore, param_arrays, arg_params, param_names,
                        update_on_kvstore):
    """ref: model.py:79-87 _initialize_kvstore."""
    for idx, param_on_devs in enumerate(param_arrays):
        kvstore.init(idx, arg_params[param_names[idx]])
        if update_on_kvstore:
            kvstore.pull(idx, param_on_devs, priority=-idx)


def _update_params_on_kvstore(param_arrays, grad_arrays, kvstore):
    """ref: model.py:88-97 — push grad, pull back updated weight; priority
    -index preserved for parity (ordering is XLA's concern here)."""
    for index, pair in enumerate(zip(param_arrays, grad_arrays)):
        arg, grad = pair
        if grad is None:
            continue
        kvstore.push(index, grad, priority=-index)
        kvstore.pull(index, arg, priority=-index)


def _update_params(param_arrays, grad_arrays, updater, num_device,
                   kvstore=None):
    """ref: model.py:99-117 — aggregate on kvstore, update locally."""
    for index, pair in enumerate(zip(param_arrays, grad_arrays)):
        arg, grad = pair
        if grad is None:
            continue
        if kvstore:
            kvstore.push(index, grad, priority=-index)
            kvstore.pull(index, grad, priority=-index)
        updater(index, grad, arg)


# ---------------------------------------------------------------------------
# fault-tolerant checkpointing (docs/robustness.md)
#
# Every checkpoint file lands via write-to-temp + fsync + rename, so a crash
# mid-save can never leave a half-written file under the live name; a
# checksummed JSON manifest binds the file set to a training cursor
# (epoch / batches / optimizer clock / RNG) so load can PROVE a checkpoint
# is whole before trusting it, and fall back to the previous one when not.
# ---------------------------------------------------------------------------

# version 2 adds the manifest's ``known_good`` bit (finite params verified
# at save time); loaders still read version-1 manifests but resume/rollback
# refuses them — a checkpoint that cannot PROVE its params were finite is
# exactly the corpse auto-resume must not revive (docs/robustness.md)
CKPT_VERSION = 2


def _fsync_dir(dirname):
    """Make a rename durable (POSIX: the directory entry needs its own
    fsync). Best-effort on filesystems without directory fds."""
    try:
        fd = os.open(dirname or ".", os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def atomic_write_bytes(path, data):
    """Atomically publish ``data`` at ``path``: temp file + fsync + rename.

    Fault sites: ``checkpoint.write`` (before any byte is written — a raise
    leaves the live file untouched), ``checkpoint.write.mid`` (after half
    the payload — a raise leaves only an orphaned ``.tmp-*``, never a
    truncated live file), ``ckpt.disk_full`` (ENOSPC after half the
    payload — the tmp file is removed and an actionable
    :class:`MXNetError` names the path; a REAL ``ENOSPC`` from the
    filesystem takes the same path). The injected ``truncate`` kind *does*
    publish a torn file, simulating power loss between rename and data
    reaching disk; the manifest checksum is what catches it at load time.
    """
    from . import faults as _faults
    path = os.fspath(path)
    act = _faults.fire("checkpoint.write")
    tmp = "%s.tmp-%d" % (path, os.getpid())
    if act == "truncate":
        data = data[:max(1, len(data) // 2)]
    try:
        with open(tmp, "wb") as f:
            half = len(data) // 2
            f.write(data[:half])
            _faults.fire("checkpoint.write.mid")
            if _faults.fire("ckpt.disk_full") is not None:
                raise OSError(errno.ENOSPC, "No space left on device", tmp)
            f.write(data[half:])
            f.flush()
            os.fsync(f.fileno())
        os.rename(tmp, path)
    except OSError as e:
        if e.errno == errno.ENOSPC:
            # full disk mid-write: the finally below removes the partial
            # tmp file, the live file at ``path`` was never touched
            raise MXNetError(
                "checkpoint write to %r failed: no space left on device "
                "(ENOSPC). The partial temp file was removed and the "
                "previous checkpoint generation is intact — free disk "
                "space (or point checkpoint_prefix at another volume) and "
                "re-run; resume='auto' continues from the newest valid "
                "checkpoint" % (path,)) from e
        raise
    finally:
        if os.path.exists(tmp):
            try:
                os.unlink(tmp)
            except OSError:
                pass
    _fsync_dir(os.path.dirname(os.path.abspath(path)))


def apply_optimizer_states(set_states, fname):
    """Read an optimizer-states file and feed it to ``set_states``, turning
    raw read errors and unpickle failures into actionable MXNetErrors (one
    shared recovery-hint wording for the KVStore and Module paths)."""
    try:
        with open(fname, "rb") as fin:
            data = fin.read()
    except OSError as e:
        raise MXNetError(
            "cannot read optimizer states %r: %s — save them with "
            "save_optimizer_states (or Module.save_checkpoint("
            "save_optimizer_states=True)) before loading" % (fname, e))
    try:
        set_states(data)
    except MXNetError:
        raise
    except Exception as e:
        raise MXNetError(
            "optimizer states file %r is corrupt or truncated (%s: %s); "
            "re-save it or fall back to an earlier checkpoint"
            % (fname, type(e).__name__, e))


def _sha256_file(path):
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _as_host(v):
    """One array to host numpy, sharding-aware: a mesh-sharded jax.Array
    that is not fully addressable (multi-host data parallelism) is reduced
    to this process's replicated/local view first — ``np.asarray`` on such
    an array raises, which would make checkpointing a sharded run
    impossible exactly when it matters (docs/perf.md "Data-parallel
    scaling")."""
    data = v.data if hasattr(v, "data") and hasattr(v, "asnumpy") else v
    if not getattr(data, "is_fully_addressable", True):
        from .parallel.mesh import local_view
        return np.asarray(local_view(data))
    if hasattr(v, "asnumpy"):
        return v.asnumpy()
    return np.asarray(v)


def _param_save_bytes(arg_params, aux_params):
    """Serialize params to the dmlc .params byte layout (what nd.save
    writes), as bytes for the atomic writer."""
    from . import dmlc_serial
    save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
    save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
    names = list(save_dict.keys())
    arrs = [_as_host(save_dict[k]) for k in names]
    return dmlc_serial.dumps(arrs, names)


def _split_param_dict(save_dict, fname):
    """Split a loaded {prefix:name -> NDArray} dict into (arg, aux),
    rejecting malformed keys with an error that names the file and key."""
    arg_params = {}
    aux_params = {}
    for k, v in save_dict.items():
        if ":" not in k:
            raise MXNetError(
                "invalid parameter file %r: key %r is malformed (expected "
                "'arg:<name>' or 'aux:<name>')" % (fname, k))
        tp, name = k.split(":", 1)
        if tp == "arg":
            arg_params[name] = v
        elif tp == "aux":
            aux_params[name] = v
        else:
            raise MXNetError(
                "invalid parameter file %r: key %r has unknown prefix %r "
                "(expected 'arg' or 'aux')" % (fname, k, tp))
    return arg_params, aux_params


def save_checkpoint(prefix, epoch, symbol, arg_params, aux_params):
    """Save symbol JSON + params (ref: model.py save_checkpoint).

    Both files land atomically (temp + fsync + rename): a crash mid-save
    leaves the previous checkpoint intact, never a truncated live file.
    """
    if symbol is not None:
        atomic_write_bytes("%s-symbol.json" % prefix,
                           symbol.tojson().encode())
    param_name = "%s-%04d.params" % (prefix, epoch)
    atomic_write_bytes(param_name, _param_save_bytes(arg_params, aux_params))
    logging.info("Saved checkpoint to \"%s\"", param_name)


def load_checkpoint(prefix, epoch):
    """Load (symbol, arg_params, aux_params) (ref: model.py load_checkpoint).

    Malformed keys (no ``arg:``/``aux:`` prefix) raise :class:`MXNetError`
    naming the offending file and key instead of being silently dropped.
    """
    symbol = sym.load("%s-symbol.json" % prefix)
    fname = "%s-%04d.params" % (prefix, epoch)
    save_dict = nd.load(fname)
    arg_params, aux_params = _split_param_dict(save_dict, fname)
    return (symbol, arg_params, aux_params)


class CheckpointState(object):
    """A validated checkpoint loaded by :class:`CheckpointManager`."""

    __slots__ = ("tag", "epoch", "batches_done", "num_update", "fused_step",
                 "arg_params", "aux_params", "opt_states_file", "rng",
                 "metric_state", "manifest", "known_good")

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw.get(k))

    def restore_rng(self):
        """Restore the global functional RNG stream to its save-time value."""
        if not self.rng:
            return
        import jax
        from . import random as _random
        data = np.asarray(self.rng["data"],
                          dtype=np.dtype(self.rng["dtype"]))
        _random.set_state(jax.random.wrap_key_data(
            data.reshape(self.rng["shape"])))


class AsyncCheckpointWriter(object):
    """Background checkpoint writer: the training loop pays only for a
    cheap on-device snapshot (array copies decoupled from the donated
    fused state); the D2H transfer, serialization, sha256, finite-params
    known-good verification and the atomic rename/manifest/latest sequence
    all run on ONE writer thread (docs/robustness.md "Asynchronous
    checkpointing"; docs/perf.md "Host off the critical path").

    At most one save is in flight. A save submitted while another is still
    writing is SHED and counted (``skipped``; mirrored into the run's
    :class:`~mxnet_tpu.guard.TrainingHealth` via ``record_ckpt_skip``) —
    back-pressure must drop cadence, not queue an unbounded convoy of
    full-model snapshots behind a slow disk.

    Crash-consistency invariants are unchanged from the sync path: the
    writer runs the exact same atomic write sequence (params, states,
    manifest, then ``latest``), so ``latest`` never references a partial
    file and a crash mid-async-save leaves the previous checkpoint
    generation valid. ``fit`` blocks on :meth:`drain` only at epoch ends,
    divergence rollback and teardown; :meth:`close` is also registered
    with ``atexit`` so interpreter exit waits for the in-flight save.

    Fault sites (:mod:`mxnet_tpu.faults`): ``ckpt.async_write`` fires on
    the writer thread before a job's first byte (raise/transient => the
    save is dropped and counted in ``errors``); ``ckpt.async_die`` ==
    ``"die"`` kills the writer thread mid-job — the next submit or drain
    reaps the corpse (counts an error) and a later submit restarts the
    thread.
    """

    def __init__(self, logger=None, health=None):
        self.logger = logger or logging
        #: TrainingHealth-like sink for back-pressure skips (or None)
        self.health = health
        self.submitted = 0
        self.written = 0
        self.skipped = 0
        self.errors = 0
        self.restarts = 0
        self._cond = threading.Condition()
        self._job = None          # pending (not yet started) job closure
        self._busy = False        # a job is being written right now
        self._closed = False
        self._thread = None
        atexit.register(self.close)

    # -- state inspection ----------------------------------------------
    def _reap_dead_locked(self):
        """Detect a writer thread that died mid-job (``ckpt.async_die`` or
        a hard crash): clear the wedged in-flight state so ``drain`` cannot
        hang and ``submit`` can restart the thread. The lost job's temp
        files are orphans; manifest/latest were never touched."""
        if ((self._busy or self._job is not None)
                and self._thread is not None
                and not self._thread.is_alive()):
            # the corpse reference stays: the next submit sees a dead
            # thread and counts the restart
            self._busy = False
            self._job = None
            self.errors += 1
            self.logger.warning(
                "AsyncCheckpointWriter: writer thread died mid-save; the "
                "in-flight checkpoint is lost (previous generation remains "
                "valid)")
            return True
        return False

    def busy(self):
        """True when a save is queued or being written (a submit now would
        be shed)."""
        with self._cond:
            self._reap_dead_locked()
            return self._busy or self._job is not None

    # -- submission ----------------------------------------------------
    def note_skip(self, tag=None):
        """Record a shed save (back-pressure): counted here and in the
        attached health sink."""
        with self._cond:
            self.skipped += 1
        if self.health is not None:
            rec = getattr(self.health, "record_ckpt_skip", None)
            if rec is not None:
                rec()
        self.logger.warning(
            "async checkpoint%s skipped: previous save still in flight "
            "(slow disk? lengthen checkpoint_every_n_batches)",
            (" %s" % tag) if tag else "")

    def submit(self, fn):
        """Queue ``fn`` (the full write job) for the writer thread.
        Returns False — without running anything — when a save is already
        in flight (the caller should :meth:`note_skip`)."""
        with self._cond:
            if self._closed:
                raise MXNetError("AsyncCheckpointWriter is closed")
            self._reap_dead_locked()
            if self._busy or self._job is not None:
                return False
            self.submitted += 1
            self._job = fn
            if self._thread is None or not self._thread.is_alive():
                if self._thread is not None:
                    self.restarts += 1
                self._thread = threading.Thread(
                    target=self._run, name="mxtpu-async-ckpt", daemon=True)
                self._thread.start()
            self._cond.notify_all()
            return True

    # -- writer thread --------------------------------------------------
    def _run(self):
        from . import faults as _faults
        while True:
            with self._cond:
                while self._job is None and not self._closed:
                    self._cond.wait()
                if self._job is None:
                    return  # closed and drained
                fn = self._job
                self._job = None
                self._busy = True
            if _faults.fire("ckpt.async_die") == "die":
                return  # simulated abrupt death: stays wedged until reaped
            try:
                _faults.fire("ckpt.async_write")
                # the host-heavy half of an async save lands as its own
                # span on the WRITER thread's Perfetto track — beside the
                # training thread's cheap "checkpoint" snapshot span
                # (docs/observability.md)
                from .obs import trace as _obs
                with _obs.span("checkpoint_write", async_=True):
                    fn()
                with self._cond:
                    self.written += 1
            except BaseException as exc:
                with self._cond:
                    self.errors += 1
                self.logger.error(
                    "async checkpoint save failed (%s: %s); the previous "
                    "checkpoint generation remains the newest valid one",
                    type(exc).__name__, exc)
            finally:
                with self._cond:
                    self._busy = False
                    self._cond.notify_all()

    # -- barriers --------------------------------------------------------
    def drain(self, timeout=None):
        """Block until no save is in flight. True when the writer emptied
        cleanly; False on timeout or when the writer died mid-save (that
        job is lost; the previous checkpoint generation is intact)."""
        deadline = (time.monotonic() + timeout) if timeout is not None \
            else None
        with self._cond:
            while self._busy or self._job is not None:
                if self._reap_dead_locked():
                    return False
                wait = 0.05  # poll: a dying thread never notifies
                if deadline is not None:
                    wait = min(wait, deadline - time.monotonic())
                    if wait <= 0:
                        return False
                self._cond.wait(timeout=wait)
            return True

    def close(self):
        """Drain and stop the writer thread (idempotent; also the atexit
        hook, so interpreter exit blocks until the in-flight save lands)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self.drain()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=5.0)
        try:
            atexit.unregister(self.close)
        except Exception:
            pass


class CheckpointManager(object):
    """Atomic, checksummed, self-validating training checkpoints.

    One checkpoint = a tag ``e<epoch>-b<batches>`` owning
    ``<prefix>-<tag>.params`` (+ ``.states`` when an optimizer is live) and
    ``<prefix>-<tag>.manifest.json`` holding sha256/size for each file plus
    the training cursor (epoch, batches_done, optimizer update count, RNG
    key, metric partial sums). ``<prefix>-latest`` points at the newest tag;
    the last ``keep`` checkpoints are retained, older ones pruned.

    ``load_latest`` validates checksums and falls back to the previous
    valid checkpoint (with a warning) when the newest is truncated or
    corrupt — the recovery contract the fault-injection suite pins down.
    """

    def __init__(self, prefix, keep=3, logger=None, save_rng=True,
                 async_writer=None):
        self.prefix = os.fspath(prefix)
        self.keep = max(1, int(keep))
        self.logger = logger or logging
        self.save_rng = save_rng
        #: attach a :class:`AsyncCheckpointWriter` to move the D2H +
        #: serialize + hash + fsync work off the caller's thread; ``save``
        #: then only snapshots (device copies) and submits
        self.async_writer = async_writer
        #: the writer a finished ``fit`` closed and detached — counters
        #: (written/skipped/errors) stay readable here after the run
        self.last_async_writer = None
        #: cumulative seconds ``save`` spent on the CALLER's thread (full
        #: write when sync; snapshot+submit when async)
        self.save_time = 0.0
        d = os.path.dirname(os.path.abspath(self.prefix))
        if d and not os.path.isdir(d):
            os.makedirs(d, exist_ok=True)

    # -- naming --------------------------------------------------------
    @staticmethod
    def _tag(epoch, batches_done):
        return "e%04d-b%08d" % (epoch, batches_done)

    def _file(self, tag, suffix):
        return "%s-%s.%s" % (self.prefix, tag, suffix)

    @property
    def latest_path(self):
        return "%s-latest" % self.prefix

    # -- save ----------------------------------------------------------
    def save(self, module, epoch, batches_done, metric=None):
        """Checkpoint a module's full training state at a batch boundary.

        ``batches_done`` is the number of completed batches within
        ``epoch`` (0 = clean epoch start). Returns the tag written.

        With an :class:`AsyncCheckpointWriter` attached, this thread only
        takes the cheap on-device snapshot and submits; the write job runs
        in the background and ``save`` returns the tag it WILL write —
        call :meth:`drain` before trusting it on disk. Returns None when
        the save was shed under back-pressure (a previous save still in
        flight).
        """
        t0 = time.perf_counter()
        try:
            if self.async_writer is not None:
                tag = self._tag(epoch, batches_done)
                if self.async_writer.busy():
                    # shed BEFORE snapshotting: the check is the cheap part
                    self.async_writer.note_skip(tag)
                    return None
                job = self._snapshot(module, epoch, batches_done,
                                     metric=metric, decouple=True)
                if job["needs_module"] is not None:
                    # no decoupled optimizer snapshot for this module kind:
                    # write synchronously (correctness over latency)
                    return self._write_job(job)
                if self.async_writer.submit(lambda: self._write_job(job)):
                    return tag
                self.async_writer.note_skip(tag)
                return None
            return self._write_job(
                self._snapshot(module, epoch, batches_done, metric=metric))
        finally:
            self.save_time += time.perf_counter() - t0

    def drain(self):
        """Block until any in-flight async save has landed (no-op without
        an async writer). Returns False when the in-flight save was lost
        (writer died) — the previous checkpoint generation is intact."""
        if self.async_writer is not None:
            return self.async_writer.drain()
        return True

    def _snapshot(self, module, epoch, batches_done, metric=None,
                  decouple=False):
        """Capture everything a checkpoint needs WITHOUT host-side heavy
        lifting: device-side array copies, the host training cursor, RNG
        key and metric sums. ``decouple=True`` (async mode) additionally
        copies every param/aux array so later in-place training updates
        (the imperative executor path mutates arrays) cannot race the
        writer thread; copies are device-to-device and asynchronous."""
        tag = self._tag(epoch, batches_done)
        arg_params, aux_params = module.get_params()
        arg_params = dict(arg_params or {})
        aux_params = dict(aux_params or {})
        if decouple:
            def cp(v):
                return v.copy() if hasattr(v, "copy") else v
            arg_params = {n: cp(v) for n, v in arg_params.items()}
            aux_params = {n: cp(v) for n, v in aux_params.items()}
        job = {"tag": tag, "epoch": int(epoch),
               "batches_done": int(batches_done),
               "arg_params": arg_params, "aux_params": aux_params,
               "states_fn": None, "needs_module": None, "symbol_json": None}
        if getattr(module, "optimizer_initialized", False):
            states_fn = None
            if decouple:
                # the device-side state replica exists only to decouple the
                # writer thread from concurrent in-place updates; a sync
                # save writes inline before training resumes, so it keeps
                # the copy-free module.save_optimizer_states path
                snap = getattr(module, "_snapshot_opt_states", None)
                states_fn = snap() if snap is not None else None
            if states_fn is not None:
                job["states_fn"] = states_fn
            else:
                job["needs_module"] = module
        if getattr(module, "symbol", None) is not None:
            sym_f = "%s-symbol.json" % self.prefix
            if not os.path.exists(sym_f):
                job["symbol_json"] = module.symbol.tojson().encode()
        opt = getattr(module, "_optimizer", None)
        job["num_update"] = int(getattr(opt, "num_update", 0) or 0)
        # the device step counter can TRAIL num_update when the guard
        # skipped non-finite steps (a skip is a full no-op, the host lr
        # clock still advances); record it so resume/rollback restores the
        # exact noise/Adam-t clock instead of re-deriving it from
        # num_update (read from the module's host-side step clock cache —
        # never a device sync)
        fused_step = getattr(module, "_fused_step_count", None)
        job["fused_step"] = fused_step() if callable(fused_step) else None
        job["rng"] = None
        if self.save_rng:
            import jax
            from . import random as _random
            kd = np.asarray(jax.random.key_data(_random.get_state()))
            job["rng"] = {"dtype": str(kd.dtype), "shape": list(kd.shape),
                          "data": kd.reshape(-1).tolist()}
        job["metric"] = self._metric_state(metric)
        return job

    def _write_job(self, job):
        """The host-heavy half of a save: D2H, serialization, sha256,
        finite-params verification and the atomic write sequence (params,
        states, symbol-on-first-save, manifest, latest — the order the
        fault-injection suite pins). Runs inline for sync saves and on the
        writer thread for async ones; byte-identical output either way."""
        tag = job["tag"]
        files = {}
        params_f = self._file(tag, "params")
        params_bytes = _param_save_bytes(job["arg_params"],
                                         job["aux_params"])
        atomic_write_bytes(params_f, params_bytes)
        # hash the INTENDED payload, not a re-read of the file: a write
        # torn between publish and durability then shows up as a
        # size/checksum mismatch at load time instead of validating
        files["params"] = {
            "name": os.path.basename(params_f),
            "size": len(params_bytes),
            "sha256": hashlib.sha256(params_bytes).hexdigest(),
        }

        states_bytes = None
        if job["states_fn"] is not None:
            states_f = self._file(tag, "states")
            states_bytes = job["states_fn"]()
            atomic_write_bytes(states_f, states_bytes)
        elif job["needs_module"] is not None:
            states_f = self._file(tag, "states")
            states_bytes = job["needs_module"].save_optimizer_states(states_f)
            if not isinstance(states_bytes, (bytes, bytearray)):
                # module whose save doesn't return the payload: re-read
                # (loses torn-write detection for this file only)
                with open(states_f, "rb") as f:
                    states_bytes = f.read()
        if states_bytes is not None:
            files["states"] = {
                "name": os.path.basename(states_f),
                "size": len(states_bytes),
                "sha256": hashlib.sha256(bytes(states_bytes)).hexdigest(),
            }

        if job["symbol_json"] is not None:
            sym_f = "%s-symbol.json" % self.prefix
            if not os.path.exists(sym_f):
                atomic_write_bytes(sym_f, job["symbol_json"])

        known_good = self._params_finite(job["arg_params"],
                                         job["aux_params"])
        from . import faults as _faults
        if _faults.fire_flag("guard.param_nan"):
            known_good = False
        if not known_good:
            self.logger.warning(
                "checkpoint %s: params are NOT all finite — saving anyway "
                "(post-mortem value) but not marking it known-good; "
                "resume/rollback will skip it", tag)
        manifest = {
            "version": CKPT_VERSION,
            "tag": tag,
            "epoch": job["epoch"],
            "batches_done": job["batches_done"],
            "num_update": job["num_update"],
            "known_good": bool(known_good),
            "files": files,
        }
        if job["fused_step"] is not None:
            manifest["fused_step"] = int(job["fused_step"])
        if job["rng"] is not None:
            manifest["rng"] = job["rng"]
        if job["metric"] is not None:
            manifest["metric"] = job["metric"]
        atomic_write_bytes(self._file(tag, "manifest.json"),
                           json.dumps(manifest, indent=1).encode())
        atomic_write_bytes(self.latest_path, tag.encode())
        self._prune()
        self.logger.info("Saved checkpoint %s (epoch %d, %d batches done)",
                         tag, job["epoch"], job["batches_done"])
        return tag

    @staticmethod
    def _params_finite(arg_params, aux_params):
        """Known-good verification: every float param/aux array is fully
        finite. Int/bool arrays are trivially finite and skipped; the scan
        costs one host pass over data the save already hashed."""
        for tree in (arg_params, aux_params):
            for v in (tree or {}).values():
                a = _as_host(v)
                if (np.issubdtype(a.dtype, np.floating)
                        and not np.isfinite(a).all()):
                    return False
        return True

    @staticmethod
    def _metric_state(metric):
        """Snapshot an EvalMetric's partial sums when its state is the
        plain (sum_metric, num_inst) pair; composite metrics skip."""
        if metric is None or not hasattr(metric, "sum_metric"):
            return None
        s, n = metric.sum_metric, metric.num_inst
        try:
            json.dumps([s, n])
        except (TypeError, ValueError):
            return None
        return [s, n]

    # -- load ----------------------------------------------------------
    def list_tags(self):
        """All tags with a manifest on disk, oldest -> newest."""
        # glob.escape: a prefix containing [ ? * must not read as a glob
        # pattern (it would silently disable resume and retention)
        pat = "%s-*.manifest.json" % glob.escape(self.prefix)
        plen = len(self.prefix) + 1
        tags = [p[plen:-len(".manifest.json")] for p in glob.glob(pat)]
        return sorted(tags)

    def load(self, tag):
        """Load and VALIDATE one checkpoint; raises MXNetError naming the
        file and failure (missing / size mismatch / checksum mismatch /
        unparseable manifest) when it is not whole."""
        man_f = self._file(tag, "manifest.json")
        try:
            with open(man_f, "rb") as f:
                manifest = json.loads(f.read().decode())
        except OSError as e:
            raise MXNetError("checkpoint %s: cannot read manifest %r: %s"
                             % (tag, man_f, e))
        except ValueError as e:
            raise MXNetError("checkpoint %s: manifest %r is corrupt: %s"
                             % (tag, man_f, e))
        if manifest.get("version", 0) > CKPT_VERSION:
            raise MXNetError(
                "checkpoint %s: manifest version %s is newer than this "
                "build supports (%d)" % (tag, manifest.get("version"),
                                         CKPT_VERSION))
        base_dir = os.path.dirname(os.path.abspath(self.prefix))
        paths = {}
        for role, info in manifest.get("files", {}).items():
            path = os.path.join(base_dir, info["name"])
            if not os.path.exists(path):
                raise MXNetError("checkpoint %s: file %r is missing"
                                 % (tag, path))
            size = os.path.getsize(path)
            if size != info["size"]:
                raise MXNetError(
                    "checkpoint %s: file %r is truncated (%d bytes, "
                    "manifest says %d)" % (tag, path, size, info["size"]))
            digest = _sha256_file(path)
            if digest != info["sha256"]:
                raise MXNetError(
                    "checkpoint %s: checksum mismatch for %r (sha256 %s, "
                    "manifest says %s)" % (tag, path, digest,
                                           info["sha256"]))
            paths[role] = path
        if "params" not in paths:
            raise MXNetError("checkpoint %s: manifest lists no params file"
                             % tag)
        save_dict = nd.load(paths["params"])
        arg_params, aux_params = _split_param_dict(save_dict,
                                                   paths["params"])
        return CheckpointState(
            tag=tag, epoch=int(manifest["epoch"]),
            batches_done=int(manifest["batches_done"]),
            num_update=int(manifest.get("num_update", 0)),
            fused_step=manifest.get("fused_step"),
            arg_params=arg_params, aux_params=aux_params,
            opt_states_file=paths.get("states"),
            rng=manifest.get("rng"), metric_state=manifest.get("metric"),
            manifest=manifest, known_good=manifest.get("known_good"))

    def load_latest(self, require_known_good=True):
        """Newest VALID checkpoint, or None. A corrupt/truncated newest
        checkpoint is skipped with a warning and the previous valid one is
        returned — the auto-resume (and divergence-rollback) entry point.

        ``require_known_good`` (default): checkpoints whose manifest lacks
        ``known_good: true`` — params were non-finite at save time, or the
        manifest predates the known-good bit — are skipped with a warning.
        Resuming one would faithfully revive a numerically dead run; pass
        ``require_known_good=False`` only for forensics.

        Tags are tried newest-first by cursor order; the ``latest`` pointer
        is only a fallback (a crash between the manifest write and the
        pointer write leaves the pointer one save behind — the newer
        on-disk checkpoint must still win)."""
        candidates = list(reversed(self.list_tags()))
        try:
            with open(self.latest_path) as f:
                pointed = f.read().strip()
            if pointed and pointed not in candidates:
                candidates.append(pointed)
        except OSError:
            pass
        for tag in candidates:
            try:
                st = self.load(tag)
            except MXNetError as e:
                self.logger.warning(
                    "checkpoint %s failed validation (%s); falling back to "
                    "the previous checkpoint", tag, e)
                continue
            if require_known_good and st.known_good is not True:
                self.logger.warning(
                    "checkpoint %s is not marked known-good (non-finite "
                    "params at save time, or a pre-guard manifest); "
                    "skipping it for resume/rollback", tag)
                continue
            return st
        return None

    # -- elastic checkpoint adoption (docs/robustness.md "Elastic
    # distributed training") -------------------------------------------
    def export_latest(self):
        """Serialize the newest known-good checkpoint — manifest plus
        every file it lists, plus the symbol file when present — into one
        bytes blob for a ring broadcast (the re-form leader's state
        adoption). Returns ``b""`` when nothing loadable exists."""
        import pickle
        st = self.load_latest()
        if st is None:
            return b""
        base_dir = os.path.dirname(os.path.abspath(self.prefix))
        payload = {"tag": st.tag, "manifest": st.manifest, "files": {}}
        for info in st.manifest.get("files", {}).values():
            path = os.path.join(base_dir, info["name"])
            with open(path, "rb") as f:
                payload["files"][info["name"]] = f.read()
        sym_f = "%s-symbol.json" % self.prefix
        if os.path.exists(sym_f):
            with open(sym_f, "rb") as f:
                payload["files"][os.path.basename(sym_f)] = f.read()
        return pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)

    def import_blob(self, blob):
        """Install a checkpoint exported by :meth:`export_latest` under
        THIS manager's directory: every file atomically, the manifest
        second-to-last, the ``latest`` pointer last — the same durability
        order as a native save, so a crash mid-import never publishes a
        partial checkpoint. Returns the installed tag."""
        import pickle
        payload = pickle.loads(blob)
        base_dir = os.path.dirname(os.path.abspath(self.prefix))
        manifest = payload["manifest"]
        listed = {i["name"] for i in manifest.get("files", {}).values()}
        for name, data in payload["files"].items():
            if name in listed:
                atomic_write_bytes(os.path.join(base_dir, name), data)
            else:  # symbol file: shared across tags, first-write-wins
                path = os.path.join(base_dir, name)
                if not os.path.exists(path):
                    atomic_write_bytes(path, data)
        atomic_write_bytes(self._file(payload["tag"], "manifest.json"),
                           json.dumps(manifest, indent=1).encode())
        atomic_write_bytes(self.latest_path, payload["tag"].encode())
        self.logger.info("Adopted broadcast checkpoint %s", payload["tag"])
        return payload["tag"]

    # -- retention -----------------------------------------------------
    def _read_manifest(self, tag):
        try:
            with open(self._file(tag, "manifest.json"), "rb") as f:
                return json.loads(f.read().decode())
        except (OSError, ValueError):
            return None

    def _prune(self):
        tags = self.list_tags()
        old = tags[:-self.keep]
        if not old:
            return
        # age-only retention would be fatal after a numerical death: a run
        # whose params went non-finite keeps writing post-mortem
        # (not-known-good) checkpoints, pushing the last RESUMABLE state
        # out of the window — so the newest known-good tag is never pruned
        newest_good = None
        for tag in reversed(tags):
            man = self._read_manifest(tag)
            if man is not None and man.get("known_good") is True:
                newest_good = tag
                break
        base_dir = os.path.dirname(os.path.abspath(self.prefix))
        for tag in old:
            if tag == newest_good:
                continue
            manifest = self._read_manifest(tag)
            if manifest is not None:
                victims = [os.path.join(base_dir, i["name"])
                           for i in manifest.get("files", {}).values()]
            else:
                victims = [self._file(tag, "params"),
                           self._file(tag, "states")]
            for path in victims + [self._file(tag, "manifest.json")]:
                try:
                    os.unlink(path)
                except OSError:
                    pass


def _init_iter(X, y, batch_size, is_train=True):
    if isinstance(X, io.DataIter):
        return X
    if isinstance(X, NDArray):
        X = X.asnumpy()
    X = np.asarray(X)
    if y is not None:
        if isinstance(y, NDArray):
            y = y.asnumpy()
        y = np.asarray(y)
    if is_train:
        return io.NDArrayIter(X, y, min(X.shape[0], batch_size),
                              shuffle=is_train, last_batch_handle="roll_over")
    return io.NDArrayIter(X, y, min(X.shape[0], batch_size), shuffle=False)


class FeedForward(object):
    """Legacy estimator API (ref: model.py:387 FeedForward). Thin shell over
    Module — deprecated in the reference too, kept for script parity."""

    def __init__(self, symbol, ctx=None, num_epoch=None, epoch_size=None,
                 optimizer="sgd", initializer=None, numpy_batch_size=128,
                 arg_params=None, aux_params=None, allow_extra_params=False,
                 begin_epoch=0, **kwargs):
        from .initializer import Uniform
        self.symbol = symbol
        self.ctx = ctx if ctx is not None else [current_context()]
        if not isinstance(self.ctx, list):
            self.ctx = [self.ctx]
        self.num_epoch = num_epoch
        self.epoch_size = epoch_size
        self.optimizer = optimizer
        self.initializer = initializer if initializer is not None else Uniform(0.01)
        self.numpy_batch_size = numpy_batch_size
        self.arg_params = arg_params
        self.aux_params = aux_params
        self.allow_extra_params = allow_extra_params
        self.begin_epoch = begin_epoch
        self.kwargs = kwargs.copy()
        self._module = None

    def _label_names(self):
        args = set(self.symbol.list_arguments())
        for cand in ("softmax_label", "label", "lro_label"):
            if cand in args:
                return [cand]
        labels = [a for a in self.symbol.list_arguments()
                  if a.endswith("_label") or a == "label"]
        return labels or ["softmax_label"]

    def fit(self, X, y=None, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            logger=None, work_load_list=None, monitor=None,
            eval_end_callback=None, eval_batch_end_callback=None):
        from .module.module import Module
        data = _init_iter(X, y, self.numpy_batch_size, is_train=True)
        if eval_data is not None and not isinstance(eval_data, io.DataIter):
            ex, ey = eval_data
            eval_data = _init_iter(ex, ey, self.numpy_batch_size, is_train=False)
        if self.epoch_size is not None:
            data = io.ResizeIter(data, self.epoch_size)
        label_names = [d.name for d in (data.provide_label or [])] \
            or self._label_names()
        self._module = Module(self.symbol,
                              data_names=[d.name for d in data.provide_data],
                              label_names=label_names,
                              context=self.ctx, logger=logger or logging)
        opt_params = dict(self.kwargs)
        self._module.fit(data, eval_data=eval_data, eval_metric=eval_metric,
                         epoch_end_callback=epoch_end_callback,
                         batch_end_callback=batch_end_callback,
                         kvstore=kvstore, optimizer=self.optimizer,
                         optimizer_params=opt_params,
                         eval_end_callback=eval_end_callback,
                         eval_batch_end_callback=eval_batch_end_callback,
                         initializer=self.initializer,
                         arg_params=self.arg_params,
                         aux_params=self.aux_params,
                         begin_epoch=self.begin_epoch,
                         num_epoch=self.num_epoch, monitor=monitor)
        self.arg_params, self.aux_params = self._module.get_params()
        return self

    def predict(self, X, num_batch=None, return_data=False, reset=True):
        from .module.module import Module
        data = _init_iter(X, None, self.numpy_batch_size, is_train=False)
        if self._module is None or not self._module.binded:
            self._module = Module(self.symbol,
                                  data_names=[d.name for d in data.provide_data],
                                  label_names=None, context=self.ctx)
            self._module.bind(data_shapes=data.provide_data,
                              label_shapes=None, for_training=False)
            # with label_names=None the symbol's label variable counts as a
            # parameter the checkpoint never stores; inference ignores it,
            # so ONLY label variables may be absent — a genuinely missing
            # weight must still fail loudly, not predict garbage
            data_names = set(d.name for d in data.provide_data)
            missing = [n for n in self.symbol.list_arguments()
                       if n not in data_names
                       and n not in self._label_names()
                       and n not in (self.arg_params or {})]
            missing += [n for n in self.symbol.list_auxiliary_states()
                        if n not in (self.aux_params or {})]
            if missing:
                raise MXNetError(
                    "predict: loaded params are missing weight/aux "
                    "state(s) %s — wrong or incomplete checkpoint?"
                    % (missing,))
            self._module.set_params(self.arg_params, self.aux_params or {},
                                    allow_missing=True)
        out = self._module.predict(data, num_batch=num_batch, reset=reset)
        if isinstance(out, list):
            return [o.asnumpy() for o in out]
        return out.asnumpy()

    def score(self, X, y=None, eval_metric="acc", num_batch=None,
              batch_end_callback=None, reset=True):
        data = _init_iter(X, y, self.numpy_batch_size, is_train=False)
        assert self._module is not None
        res = self._module.score(data, eval_metric, num_batch=num_batch,
                                 batch_end_callback=batch_end_callback,
                                 reset=reset)
        return res[0][1]

    def save(self, prefix, epoch=None):
        if epoch is None:
            epoch = self.num_epoch
        assert epoch is not None
        save_checkpoint(prefix, epoch, self.symbol, self.arg_params or {},
                        self.aux_params or {})

    @staticmethod
    def load(prefix, epoch, ctx=None, **kwargs):
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return FeedForward(symbol, ctx=ctx, arg_params=arg_params,
                           aux_params=aux_params, begin_epoch=epoch, **kwargs)

    @staticmethod
    def create(symbol, X, y=None, ctx=None, num_epoch=None, epoch_size=None,
               optimizer="sgd", initializer=None, eval_data=None,
               eval_metric="acc", epoch_end_callback=None,
               batch_end_callback=None, kvstore="local", logger=None,
               work_load_list=None, eval_end_callback=None,
               eval_batch_end_callback=None, **kwargs):
        model = FeedForward(symbol, ctx=ctx, num_epoch=num_epoch,
                            epoch_size=epoch_size, optimizer=optimizer,
                            initializer=initializer
                            if initializer is not None else None, **kwargs)
        model.fit(X, y, eval_data=eval_data, eval_metric=eval_metric,
                  epoch_end_callback=epoch_end_callback,
                  batch_end_callback=batch_end_callback, kvstore=kvstore,
                  logger=logger, work_load_list=work_load_list,
                  eval_end_callback=eval_end_callback,
                  eval_batch_end_callback=eval_batch_end_callback)
        return model
