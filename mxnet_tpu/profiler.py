"""Profiler (ref: python/mxnet/profiler.py; C++ engine profiler at
src/engine/profiler.{h,cc} emitting Chrome trace-event JSON).

TPU-native substrate: jax.profiler captures XLA device traces (XPlane /
TensorBoard format, which also opens in chrome://tracing-compatible viewers
via Perfetto). The reference API shape — set_config, set_state, dump — is
preserved; op names flow into the trace through jit scopes automatically.

``MXNET_PROFILER_AUTOSTART=1`` is honored (ref: src/initialize.cc) but
DEFERRED to the first dispatch: starting the device trace at import time
would race ``profiler_set_config`` — the trace would land in the default
directory before the program ever had a chance to point it elsewhere.
:func:`maybe_autostart` is called from the executor/fused-dispatch hot
paths (one boolean check once armed-or-done).

The HOST half of the timeline lives in :mod:`mxnet_tpu.obs`:
:class:`Scope` enters a ``jax.profiler.TraceAnnotation`` (device trace)
AND an ``obs.span`` (host trace) together, so one ``with`` covers both
sides of the Perfetto view (docs/observability.md).
"""
from __future__ import annotations

import os
import time

import jax

from .base import MXNetError
from .obs import trace as _obs_trace

#: the name of the event that aligns the device trace with the host trace
CLOCK_SYNC = "mxtpu_clock_sync"

_state = {"running": False, "dir": "profile_output", "mode": "symbolic"}

#: MXNET_PROFILER_AUTOSTART seen at import: the trace starts at the FIRST
#: DISPATCH, after any profiler_set_config has run — never at import
_autostart_pending = (
    os.environ.get("MXNET_PROFILER_AUTOSTART", "0") == "1")


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Configure output location (ref: MXSetProfilerConfig). ``filename``'s
    directory becomes the trace dir (XPlane traces are directories)."""
    _state["mode"] = mode
    d = os.path.dirname(filename) or "."
    base = os.path.basename(filename)
    _state["dir"] = os.path.join(d, base.replace(".json", "_trace"))


def profiler_set_state(state="stop"):
    """'run' starts the jax trace; 'stop' ends and writes it
    (ref: MXSetProfilerState)."""
    global _autostart_pending
    if state == "run" and not _state["running"]:
        _autostart_pending = False  # an explicit start supersedes it
        jax.profiler.start_trace(_state["dir"])
        _state["running"] = True
        _clock_sync()
    elif state == "stop" and _state["running"]:
        jax.profiler.stop_trace()
        _state["running"] = False
    elif state not in ("run", "stop"):
        raise MXNetError("profiler state must be 'run' or 'stop'")


def _clock_sync():
    """One instant on both clocks: a ``mxtpu_clock_sync`` annotation in
    the device trace's host plane and, opened inside it, an instant of
    the same name in the host trace that carries its own
    ``perf_counter_ns``. The device trace's clock and the host trace's
    (microseconds since ``obs.trace``'s epoch) then differ by the
    difference of the two events' starts (docs/observability.md)."""
    with jax.profiler.TraceAnnotation(CLOCK_SYNC):
        _obs_trace.instant(CLOCK_SYNC,
                           perf_counter_ns=time.perf_counter_ns())


def maybe_autostart():
    """First-dispatch hook: start the deferred MXNET_PROFILER_AUTOSTART
    trace, AFTER any profiler_set_config has had its say. Near-zero cost
    once resolved (one module-global boolean check)."""
    global _autostart_pending
    if _autostart_pending:
        _autostart_pending = False
        profiler_set_state("run")


def dump_profile():
    """Finish the trace (ref: MXDumpProfile). XPlane output is written on
    stop; this stops a running trace."""
    if _state["running"]:
        profiler_set_state("stop")


class Scope(object):
    """Named trace annotation for user code regions — on BOTH timelines:
    the device trace (``jax.profiler.TraceAnnotation`` threads the name
    into the XPlane track) and the host trace (an ``obs.span`` complete
    event), so one ``with profiler.Scope("epoch3")`` brackets the same
    region in Perfetto's device and host views side by side."""

    def __init__(self, name, **args):
        self._t = jax.profiler.TraceAnnotation(name)
        self._name = name
        self._args = args
        self._span = None

    def __enter__(self):
        self._span = _obs_trace.span(self._name, **self._args)
        self._span.__enter__()
        self._t.__enter__()
        return self

    def __exit__(self, *a):
        try:
            self._t.__exit__(*a)
        finally:
            self._span.__exit__(*a)
            self._span = None
