"""Engine facade.

The reference's threaded dependency engine (ref: src/engine/threaded_engine.h,
threaded_engine_perdevice.cc) schedules every op asynchronously against
read/write variable dependencies. On the JAX substrate that role collapses
into XLA's async dispatch: every dispatched computation already runs
asynchronously with data-flow ordering enforced by jax.Array futures. What
remains useful — and is kept here — is the *control* surface:

- ``wait_all()``              (ref: Engine::WaitForAll / MXNDArrayWaitAll)
- ``wait_for_var(arr)``       (ref: Engine::WaitForVar) -> block_until_ready
- naive/synchronous debug mode (ref: MXNET_ENGINE_TYPE=NaiveEngine) which
  forces a blocking wait after every imperative op, for bisecting async bugs.
- ``push(fn)`` for host callbacks ordered after all pending device work.
- ``bulk(k)`` dispatch bulking (ref: Engine bulk execution /
  MXEngineSetBulkSize): on this substrate the bulked unit is K whole train
  steps compiled into one ``lax.scan`` dispatch — ``Module.fit`` reads the
  current bulk size as its default ``steps_per_dispatch``.
"""
from __future__ import annotations

import contextlib
import os

import jax

_naive = os.environ.get("MXNET_ENGINE_TYPE", "") == "NaiveEngine"
_bulk_steps = int(os.environ.get("MXTPU_BULK_STEPS", "1") or 1)
# whether set_bulk_size was ever called: an EXPLICIT bulk(1) must read as
# "the operator asked for 1", not "unset — consult the tuning DB"
_bulk_explicit = False


def set_engine_type(name):
    """'NaiveEngine' => synchronous execution after every imperative op;
    'ThreadedEngine'/'ThreadedEnginePerDevice' => default async dispatch."""
    global _naive
    _naive = (name == "NaiveEngine")


def is_naive():
    return _naive


def set_bulk_size(size):
    """Set the default steps-per-dispatch for training loops; returns the
    previous value (ref: Engine::set_bulk_size)."""
    global _bulk_steps, _bulk_explicit
    prev = _bulk_steps
    _bulk_steps = max(1, int(size))
    _bulk_explicit = True
    return prev


def bulk_size():
    """Current default steps-per-dispatch consumed by Module.fit."""
    return _bulk_steps


def bulk_configured():
    """Whether the bulk size was explicitly configured (``MXTPU_BULK_STEPS``
    env or a ``bulk()``/``set_bulk_size`` call — INCLUDING an explicit
    ``bulk(1)``, which means "the operator asked for 1", not "unset") —
    the precedence probe that lets ``fit``'s knob resolution distinguish
    an operator choice from "nobody said anything, consult the tuning DB"
    (docs/perf.md "Autotuning")."""
    if _bulk_explicit or _bulk_steps != 1:
        return True
    return bool(os.environ.get("MXTPU_BULK_STEPS", "").strip())


@contextlib.contextmanager
def bulk(size):
    """Scoped dispatch bulking: ``with mx.engine.bulk(8): mod.fit(...)``
    trains 8 steps per compiled dispatch (the reference's engine bulk
    scope, applied at train-loop granularity). Exit restores BOTH the
    previous size and the was-explicitly-set flag, so a transient scope
    never leaves the process looking operator-configured (which would
    disarm tuning-DB resolution for every later fit)."""
    global _bulk_steps, _bulk_explicit
    prev, prev_flag = _bulk_steps, _bulk_explicit
    set_bulk_size(size)
    try:
        yield
    finally:
        _bulk_steps, _bulk_explicit = prev, prev_flag


_pipeline_override = None


def dispatch_pipeline():
    """Default deferred-readback depth for K-step dispatch (docs/perf.md
    "Host off the critical path"): ``Module.fit`` enqueues dispatch
    N+depth before fetching dispatch N's packed metric/sentinel array, so
    the device never idles waiting on the host between dispatches. 0 =
    eager (fetch immediately after each dispatch). Env default:
    ``MXTPU_DISPATCH_PIPELINE`` (1)."""
    if _pipeline_override is not None:
        return _pipeline_override
    v = os.environ.get("MXTPU_DISPATCH_PIPELINE")
    if v is None or v.strip() == "":
        return 1
    try:
        return max(0, int(v))
    except ValueError:
        from .base import MXNetError
        raise MXNetError(
            "MXTPU_DISPATCH_PIPELINE must be an integer, got %r" % v)


def dispatch_pipeline_configured():
    """Whether the pipeline depth was explicitly configured (env or
    ``set_dispatch_pipeline``) rather than defaulted — see
    :func:`bulk_configured` for why resolution needs to know
    (docs/perf.md "Autotuning")."""
    if _pipeline_override is not None:
        return True
    return bool(os.environ.get("MXTPU_DISPATCH_PIPELINE", "").strip())


def set_dispatch_pipeline(depth):
    """Override the default dispatch-pipeline depth (None = back to the
    env/default); returns the previous effective value."""
    global _pipeline_override
    prev = dispatch_pipeline()
    _pipeline_override = None if depth is None else max(0, int(depth))
    return prev


def dp_devices():
    """Default data-parallel device count for ``Module`` (docs/perf.md
    "Data-parallel scaling"): ``MXTPU_DP_DEVICES=N`` makes a Module built
    without an explicit ``context=`` spread over the first N local devices
    — the env-knob spelling of ``context=[mx.cpu(i) for i in range(N)]``.
    0/unset keeps the single-device default."""
    v = os.environ.get("MXTPU_DP_DEVICES")
    if v is None or v.strip() == "":
        return 0
    try:
        return max(0, int(v))
    except ValueError:
        from .base import MXNetError
        raise MXNetError("MXTPU_DP_DEVICES must be an integer, got %r" % v)


def _mode_from_env(env_name, default):
    """Shared warn|error|off tri-state parser for the analyzers'
    runtime-policy env knobs; ``default`` is the meaning of unset/empty."""
    v = os.environ.get(env_name, "").strip().lower()
    if v == "":
        return default
    if v in ("1", "on", "true", "warn", "warning"):
        return "warn"
    if v in ("0", "off", "false", "no"):
        return "off"
    if v in ("error", "raise"):
        return "error"
    from .base import MXNetError
    raise MXNetError("%s must be warn|error|off, got %r" % (env_name, v))


def _validate_mode(mode, who):
    if mode is not None and mode not in ("warn", "error", "off"):
        from .base import MXNetError
        raise MXNetError("%s: mode must be warn|error|off or None, got %r"
                         % (who, mode))


_tracecheck_override = None


def tracecheck_mode():
    """Retrace-policy mode for the static analyzer's runtime hooks
    (docs/static_analysis.md): ``"warn"`` (default) logs the cache-key
    diff when a watched jit entry unexpectedly retraces, ``"error"``
    raises :class:`~mxnet_tpu.base.MXNetError`, ``"off"`` disables
    signature capture. Env default: ``MXTPU_TRACECHECK``."""
    if _tracecheck_override is not None:
        return _tracecheck_override
    return _mode_from_env("MXTPU_TRACECHECK", "warn")


def set_tracecheck(mode):
    """Override the tracecheck mode (None = back to the env/default);
    returns the previous effective value."""
    global _tracecheck_override
    prev = tracecheck_mode()
    _validate_mode(mode, "set_tracecheck")
    _tracecheck_override = mode
    return prev


_memcheck_override = None


def memcheck_mode():
    """Memory-audit policy for load-time-compiled program sets
    (docs/static_analysis.md "Memory lints"): ``"off"`` (default) skips
    the audit, ``"warn"`` logs unsuppressed memory findings when a
    serving tier compiles its program set (``ServingEngine`` buckets,
    ``DecodeLoop`` body), ``"error"`` raises
    :class:`~mxnet_tpu.base.MXNetError` — a deploy that cannot fit its
    budget fails at LOAD, not at the first full-batch request. Env
    default: ``MXTPU_MEMCHECK``."""
    if _memcheck_override is not None:
        return _memcheck_override
    return _mode_from_env("MXTPU_MEMCHECK", "off")


def set_memcheck(mode):
    """Override the memcheck mode (None = back to the env/default);
    returns the previous effective value."""
    global _memcheck_override
    prev = memcheck_mode()
    _validate_mode(mode, "set_memcheck")
    _memcheck_override = mode
    return prev


_commscheck_override = None


def commscheck_mode():
    """Collective-communication audit policy for SHARDED dispatch
    programs (docs/static_analysis.md "Communication lints"): ``"off"``
    (default) skips the audit — the CLI/CI drift gate covers the
    committed program sets; ``"warn"`` makes a mesh-bearing
    ``TrainStep`` run the comms lints ONCE per compiled program at its
    first dispatch (one extra compile, arguments carry the real
    shardings) and log unsuppressed findings; ``"error"`` raises
    :class:`~mxnet_tpu.base.MXNetError` — a sharding mistake that
    gathers inside the scan body fails at the first dispatch, not after
    a slow multichip run. Env default: ``MXTPU_COMMSCHECK``."""
    if _commscheck_override is not None:
        return _commscheck_override
    return _mode_from_env("MXTPU_COMMSCHECK", "off")


def set_commscheck(mode):
    """Override the commscheck mode (None = back to the env/default);
    returns the previous effective value."""
    global _commscheck_override
    prev = commscheck_mode()
    _validate_mode(mode, "set_commscheck")
    _commscheck_override = mode
    return prev


_flopcheck_override = None


def flopcheck_mode():
    """Compute/memory roofline audit policy for dispatch programs
    (docs/static_analysis.md "Roofline lints"): ``"off"`` (default)
    skips the audit — the CLI/CI drift gate covers the committed program
    sets; ``"warn"`` makes ``TrainStep`` run the roofline lints ONCE per
    compiled program at its first dispatch (one extra compile, arguments
    reduced to structs) and log unsuppressed findings; ``"error"``
    raises :class:`~mxnet_tpu.base.MXNetError` — a fusion regression
    that shatters the step into tiny dispatches fails at the first
    dispatch, not after a slow profiling session. Env default:
    ``MXTPU_FLOPCHECK``."""
    if _flopcheck_override is not None:
        return _flopcheck_override
    return _mode_from_env("MXTPU_FLOPCHECK", "off")


def set_flopcheck(mode):
    """Override the flopcheck mode (None = back to the env/default);
    returns the previous effective value."""
    global _flopcheck_override
    prev = flopcheck_mode()
    _validate_mode(mode, "set_flopcheck")
    _flopcheck_override = mode
    return prev


#: where compiled programs persist when the environment names no place:
#: a fixed path in the checkout (the path is part of the cache key's
#: lookup, so a directory that moves between runs never hits)
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def setup_compile_cache():
    """Place JAX's persistent compilation cache; call before the first
    compile. ``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX, which
    reads it itself; unset, the cache is :data:`COMPILE_CACHE_DIR`. Every
    ``jit`` and every AOT ``lower().compile()`` (the serving engines) goes
    through it. Returns the directory in use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def maybe_sync(arr):
    """Called after each imperative op; blocks in naive mode."""
    if _naive and arr is not None:
        try:
            arr.block_until_ready()
        except AttributeError:
            pass
    return arr


def wait_all():
    """Block until all pending device computation completes."""
    jax.effects_barrier()
    # also sync all live arrays' devices
    try:
        jax.block_until_ready(jax.device_put(0))
    except Exception:
        pass


def wait_for_var(arr):
    jax.block_until_ready(arr)


def push(fn):
    """Run a host callback after all currently pending work (debug/profiling)."""
    wait_all()
    fn()
