"""Host-span tracer: one Chrome-trace-event timeline across train/data/serve.

The reference MXNet ships a Chrome-trace engine profiler spanning
compute/copy/IO (src/engine/profiler.{h,cc}); ``jax.profiler`` covers the
DEVICE side of that story (XPlane traces of XLA programs) but says nothing
about the host threads that feed it — the data producer's stack/H2D, the
dispatch pipeline's deferred readbacks, the checkpoint writer, the serving
batcher's queue/coalesce/split. This module is the host half: a
low-overhead thread-safe span API emitting Chrome trace-event JSON
(``{"traceEvents": [...]}``) that opens in Perfetto BESIDE the device
trace, with correlation IDs (``dispatch=`` / ``req=``) threaded through
span args so one dispatch or one serving request reads as one timeline
(docs/observability.md).

Cost contract, as it is: :func:`span` is one module-global flag check
returning a shared no-op context manager (no allocation, no clock read)
ONLY with tracing AND the flight recorder both off. The recorder is on by
default (``MXTPU_FLIGHT_RECORDER``, :mod:`.flight`) and attaches itself as
this module's sink at import, so in a default process every span is LIVE:
a ``_Span``, two clock reads, one event dict and the ring's append (with
tracing on, also a lock and a list append here). That is 1-3 us a span on
a quiet core; CHANGES.md (PR 27) has the decode step's cost measured on
the chip in all three states. A site whose ARGUMENTS are costly to build
asks :func:`active` first; ``span()`` cannot spare what its caller already
evaluated. ``MXTPU_TRACE=1`` arms the trace file; ``MXTPU_TRACE_PATH``
names it (default ``mxtpu_trace.json``, written at interpreter exit and by
:func:`save`).

Event model (Chrome trace-event format, the subset Perfetto renders):

- ``ph="X"`` complete events — one record per span, ``ts``+``dur`` in
  microseconds since the module epoch, ``pid``/``tid`` real process/thread
  ids with ``M`` thread-name metadata records so Perfetto labels tracks.
- ``ph="i"`` instant events (:func:`instant`) for point occurrences
  (divergence, rollback, replica death, request submit).
- ``ph="b"``/``"e"`` async pairs keyed by ``id`` (:func:`async_complete`)
  for a life that crosses threads and outlasts the spans around it: a
  batcher request's ``serve_queue``, a decode request's ``decode_request``
  (the begin event carries the whole record). Each id is its own track;
  they are no complete events, so :func:`nest_check` and readers of
  ``ph="X"`` never meet them.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time

from ..base import env_bool, env_int, env_str

__all__ = [
    "span", "instant", "complete", "async_complete", "enabled", "active",
    "start", "stop", "save", "events", "clear", "trace_path", "set_sink",
    "expand_laps", "NOOP",
]

#: hard bound on buffered events — a runaway span site degrades to a
#: dropped-events counter, never unbounded memory. Parsed LAZILY (first
#: record with tracing armed) through base.env_int, so a malformed
#: MXTPU_TRACE_MAX_EVENTS raises a named MXNetError at first use — never
#: a bare ValueError that bricks `import mxnet_tpu`
_MAX_EVENTS = None


def _max_events():
    global _MAX_EVENTS
    if _MAX_EVENTS is None:
        _MAX_EVENTS = max(16, env_int("MXTPU_TRACE_MAX_EVENTS", 200000))
    return _MAX_EVENTS

_lock = threading.Lock()
_events = []            # event dicts, append-only under _lock
_dropped = 0
_named_tids = set()     # tids that already emitted thread_name metadata
#: perf_counter_ns at module import — all ts are relative to this, so
#: spans from every thread share one monotonic clock
_EPOCH_NS = time.perf_counter_ns()

#: this process's id, read once: ``os.getpid()`` is a system call on every
#: event otherwise (several microseconds each on a sandboxed host)
_PID = os.getpid()


def _refresh_pid():
    global _PID
    _PID = os.getpid()


os.register_at_fork(after_in_child=_refresh_pid)

#: module-level fast-path flag: True when the tracer OR the flight
#: recorder needs span records. span()/instant() check ONLY this.
_ACTIVE = False
#: tracing specifically (the JSON file); flight recording may be on alone
_TRACING = False

#: optional extra consumer (the flight recorder's ring): called with the
#: finished event dict under no lock
_SINK = None


def _recompute_active():
    global _ACTIVE
    _ACTIVE = _TRACING or (_SINK is not None)


def set_sink(sink):
    """Attach/detach the secondary event consumer (the flight recorder).
    ``sink`` is ``fn(event_dict)`` or None."""
    global _SINK
    _SINK = sink
    _recompute_active()


def enabled():
    """True when spans are being recorded for the TRACE FILE (the flight
    recorder may keep span() live even when this is False)."""
    return _TRACING


def active():
    """True when :func:`span` returns a live span: tracing on, or the
    flight recorder attached (its default). A site asks this before it
    builds span arguments that cost something (a list per call)."""
    return _ACTIVE


def trace_path():
    return env_str("MXTPU_TRACE_PATH", "mxtpu_trace.json")


def start():
    """Arm the tracer (idempotent). ``MXTPU_TRACE=1`` does this at import."""
    global _TRACING
    _TRACING = True
    _recompute_active()


def stop():
    """Disarm the tracer; buffered events stay until :func:`clear`/
    :func:`save`."""
    global _TRACING
    _TRACING = False
    _recompute_active()


def clear():
    global _dropped
    with _lock:
        del _events[:]
        _named_tids.clear()
        _dropped = 0


def events():
    """Snapshot of the buffered trace events (tests / the CI gate)."""
    with _lock:
        return list(_events)


def _now_us():
    return (time.perf_counter_ns() - _EPOCH_NS) // 1000


def _record(ev):
    """Append one finished event: trace buffer (when tracing) + sink."""
    global _dropped
    if _TRACING:
        with _lock:
            tid = ev["tid"]
            if tid not in _named_tids:
                _named_tids.add(tid)
                _events.append({
                    "ph": "M", "name": "thread_name", "pid": ev["pid"],
                    "tid": tid,
                    "args": {"name": threading.current_thread().name}})
            if len(_events) < _max_events():
                _events.append(ev)
            else:
                _dropped += 1
    sink = _SINK
    if sink is not None:
        try:
            sink(ev)
        except Exception:
            pass  # the recorder must never break the traced path


class _NoopSpan(object):
    """Shared do-nothing context manager: the tracing-off fast path
    allocates nothing (one module-level instance, returned by value)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def set(self, **args):
        pass

    def lap(self, name, **args):
        pass


_NOOP = _NoopSpan()
#: the shared no-op span, for a site that hands "the live span, if any"
#: down to the code it wraps
NOOP = _NOOP


class _Span(object):
    __slots__ = ("name", "args", "_t0", "_laps")

    def __init__(self, name, args):
        self.name = name
        self.args = args
        self._t0 = None
        self._laps = None

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **args):
        """Add arguments known only at the span's end (what the region
        turned out to process); they land in the event beside the ones
        the span was opened with."""
        self.args.update(args)

    def lap(self, name, **args):
        """End a phase of this span: ``name`` lasted from the end of the
        lap before (or the span's start) until now. Laps are a span's
        leaf phases at one clock read and one tuple each: contiguous by
        construction, kept INSIDE the span's event (``args["laps"]``:
        ``[name, offset_us, dur_us]``, plus the lap's own args if any),
        so that a hot loop's phases do not multiply the events every
        reader walks; :func:`expand_laps` (and so the written trace file)
        turns them into child spans."""
        if self._laps is None:
            self._laps = []
        self._laps.append((name, time.perf_counter_ns(), args))

    def __exit__(self, exc_type, exc, tb):
        t0 = self._t0
        t1 = time.perf_counter_ns()
        args = self.args
        if exc_type is not None:
            args = dict(args)
            args["error"] = exc_type.__name__
        if self._laps is not None:
            # on the grid of ts and dur, so that laps nest in their span
            laps, start = [], (t0 - _EPOCH_NS) // 1000
            at = start
            for name, t, largs in self._laps:
                end = (t - _EPOCH_NS) // 1000
                lap = [name, at - start, end - at]
                laps.append(lap + [largs] if largs else lap)
                at = end
            args["laps"] = laps
        # both ends rounded on the epoch's grid, so that a span that ends
        # inside another in nanoseconds also does in whole microseconds
        ts = (t0 - _EPOCH_NS) // 1000
        _record({"ph": "X", "name": self.name, "cat": "host",
                 "ts": ts, "dur": (t1 - _EPOCH_NS) // 1000 - ts,
                 "pid": _PID, "tid": threading.get_ident(),
                 "args": args})
        return False


def span(name, **args):
    """Context manager timing one host region as a Chrome complete event.

    ``args`` are the correlation payload (``dispatch=i``, ``req=rid``, …)
    and land in the event's ``args`` dict; ``with span(...) as sp`` and
    ``sp.set(**more)`` adds what is known only at the end, ``sp.lap(name)``
    ends a phase inside the span. When neither
    tracing nor the flight recorder is armed this returns a shared no-op
    instance (whose ``set`` and ``lap`` do nothing); in a default process the
    recorder is armed and the span is live (see the module docstring)."""
    if not _ACTIVE:
        return _NOOP
    return _Span(name, args)


def complete(name, dur_s, **args):
    """Record an ALREADY-measured region (duration in seconds) ending now.

    For sites that time themselves (SuperBatchIter's ``_note_stage``
    already wraps stack/H2D in perf_counter pairs) — the span is emitted
    after the fact with ``ts = now - dur``, which renders identically."""
    if not _ACTIVE:
        return
    now = _now_us()
    dur = max(0, int(dur_s * 1e6))
    _record({"ph": "X", "name": name, "cat": "host", "ts": now - dur,
             "dur": dur, "pid": _PID,
             "tid": threading.get_ident(), "args": args})


def async_complete(name, dur_s, id, **args):
    """Record an ALREADY-measured ASYNC region (``ph="b"``/``"e"`` pair
    keyed by ``id``) ending now. For lifecycles that span threads — a
    serving request's queue wait begins on the caller thread and ends on
    the batcher thread — where a same-track complete event would overlap
    (not nest) the batcher's own spans. Perfetto renders each id as its
    own async track."""
    if not _ACTIVE:
        return
    now = _now_us()
    dur = max(0, int(dur_s * 1e6))
    pid = _PID
    tid = threading.get_ident()
    _record({"ph": "b", "name": name, "cat": "async", "id": id,
             "ts": now - dur, "pid": pid, "tid": tid, "args": args})
    _record({"ph": "e", "name": name, "cat": "async", "id": id,
             "ts": now, "pid": pid, "tid": tid, "args": {}})


def instant(name, **args):
    """Record a point event (``ph="i"``, thread scope)."""
    if not _ACTIVE:
        return
    _record({"ph": "i", "name": name, "cat": "host", "s": "t",
             "ts": _now_us(), "pid": _PID,
             "tid": threading.get_ident(), "args": args})


def expand_laps(evs):
    """``evs`` with every span's laps (:meth:`_Span.lap`) written out as
    child complete events, each after its parent: same thread, the lap's
    own args, and the parent's scalar args (its correlation ids: a
    ``step``, a ``dispatch``). What :func:`save` writes, so that Perfetto
    shows the phases nested in their span."""
    out = []
    for ev in evs:
        out.append(ev)
        laps = ev.get("args", {}).get("laps") if ev.get("ph") == "X" else None
        if not laps:
            continue
        inherit = {k: v for k, v in ev["args"].items()
                   if isinstance(v, (int, float, str))}
        for lap in laps:
            out.append({"ph": "X", "name": lap[0], "cat": ev["cat"],
                        "ts": ev["ts"] + lap[1], "dur": lap[2],
                        "pid": ev["pid"], "tid": ev["tid"],
                        "args": dict(inherit, **(lap[3] if len(lap) > 3
                                                 else {}))})
    return out


def save(path=None):
    """Write the buffered events as one Chrome-trace JSON (atomic: temp +
    rename via model.atomic_write_bytes). Returns the path written."""
    from ..model import atomic_write_bytes
    path = path or trace_path()
    with _lock:
        evs = list(_events)
        dropped = _dropped
    doc = {"traceEvents": expand_laps(evs), "displayTimeUnit": "ms",
           "otherData": {"producer": "mxnet_tpu.obs",
                         "dropped_events": dropped}}
    atomic_write_bytes(path, json.dumps(doc).encode("utf-8"))
    return path


def _atexit_save():
    if _TRACING:
        try:
            with _lock:
                empty = not _events
            if not empty:
                save()
        except Exception:
            pass


atexit.register(_atexit_save)


def _parse_env():
    """Honor MXTPU_TRACE at import (mirrors MXTPU_GUARD's spelling rules
    via env_bool). A malformed MXTPU_TRACE_MAX_EVENTS raises at first use
    of the buffer bound, not here."""
    if env_bool("MXTPU_TRACE"):
        start()


_parse_env()


def nest_check(evs):
    """Validate span nesting per (pid, tid): complete events on one thread
    must nest like a call stack (Perfetto renders overlap-but-not-nested
    spans as a corrupt track). Returns a list of violation strings — the
    CI schema gate asserts it empty. Exposed here so tests and
    tools/obs_gate.py share one checker."""
    bad = []
    by_thread = {}
    for ev in evs:
        if ev.get("ph") != "X":
            continue
        by_thread.setdefault((ev["pid"], ev["tid"]), []).append(ev)
    for key, track in by_thread.items():
        track.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for ev in track:
            end = ev["ts"] + ev["dur"]
            while stack and ev["ts"] >= stack[-1][1]:
                stack.pop()
            if stack and end > stack[-1][1]:
                bad.append(
                    "span %r [%d,%d) overlaps %r [%d,%d) on tid %s"
                    % (ev["name"], ev["ts"], end, stack[-1][0],
                       stack[-1][2], stack[-1][1], key[1]))
                continue
            stack.append((ev["name"], end, ev["ts"]))
    return bad
