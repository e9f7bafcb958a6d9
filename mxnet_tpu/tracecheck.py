"""tracecheck: a static analyzer for compiled step programs.

The whole performance story of this stack — the bulked ``lax.scan`` train
dispatch (docs/perf.md "Dispatch bulking") and the pipelined readback
(docs/perf.md "Host off the critical path") — rests on invariants that
nothing else checks:

* no hidden host transfer or callback inside the compiled region (a single
  ``jax.debug.print`` in the scan body serializes every dispatch on the
  host);
* no silent retrace when a Python scalar, a weak type or a perturbed shape
  leaks into a trace (a retrace storm turns the "compile once per (batch,
  k)" contract into a recompile per epoch);
* donated state actually donated (an un-aliasable donation silently doubles
  the parameter working set);
* no float64/weak-type promotion inside the step jaxpr (on TPU an f64
  literal means an unintended cast chain, or an error).

The reference's dependency engine made dataflow properties explicit per-op
(PAPER.md §1); on the XLA substrate they live implicitly in the
jaxpr/StableHLO, where only a *static* pass can see them — the same
motivation as whole-program inspection in the Julia-to-TPU compiler
(arXiv:1810.09868) and graph-level placement analysis in TensorFlow
(arXiv:1605.08695). ``tracecheck`` lowers a step program WITHOUT executing
it, walks the ClosedJaxpr + StableHLO, and emits structured
:class:`Finding` objects with an op path (nesting inside scan/cond bodies is
visible) and source provenance.

Lint catalog (docs/static_analysis.md):

========================  ==================================================
lint id                   fires when
========================  ==================================================
``host-sync``             a callback / infeed / outfeed op is reachable in
                          the program (op path shows if inside a scan body)
``retrace``               a watched jit cache entry re-traced; the differ
                          names the argument and property that changed
``donation``              a donated argument is copied by the lowering
                          (no input-output alias)
``const-capture``         a closure-captured constant larger than
                          ``MXTPU_TRACECHECK_CONST_BYTES`` is baked into
                          the program
``dtype-f64``             any op/const/input in the jaxpr carries a 64-bit
                          float/complex dtype
``dtype-weak``            a weak-typed program input (a bare Python scalar
                          reached the trace)
``collective-in-scan``    a gather-type collective (all-gather /
                          all-to-all / reduce-scatter) sits inside a scan
                          body — the expected data-parallel program syncs
                          only by psum (the grad/metric all-reduce), so a
                          gather there means a sharding mistake replaying
                          K times per dispatch. Jaxpr pass catches explicit
                          (shard_map) collectives;
                          :func:`check_collectives` additionally compiles
                          the partitioned program and audits the collectives
                          GSPMD inserted
========================  ==================================================

The memory-side lints (``hbm-budget``, ``donation-waste``,
``temp-blowup``, ``resident-set``) live in :mod:`mxnet_tpu.memcheck` —
the HBM analyzer that COMPILES programs and audits their buffer
assignment — and the communication-side lints (``resharding-copy``,
``replicated-large``, ``gather-in-loop``, ``comms-bound``) in
:mod:`mxnet_tpu.commscheck`, the collective-inventory analyzer whose
parser also backs :func:`check_collectives`; both share this module's
:class:`Finding` framework and suppression registry
(docs/static_analysis.md "Memory lints" / "Communication lints").

Suppression: put ``# tracecheck: ignore[lint-id]`` (or a bare
``# tracecheck: ignore`` for all lints) on — or on the line above — the
source line a finding's provenance points at; or register a programmatic
suppression with :func:`add_suppression`. Suppressed findings are still
reported but do not fail the CLI gate.

Runtime hooks: ``TrainStep`` registers every jit cache entry here (the
guard-on / guard-off / pipelined program set is auditable as a unit via
:func:`check_registered`) and routes each dispatch through a
:class:`TraceWatcher`, so an unexpected jit-cache miss logs the cache-key
diff — and raises under ``MXTPU_TRACECHECK=error`` (see
``engine.tracecheck_mode``).

CLI::

    python -m mxnet_tpu.tracecheck --zoo          # audit the model zoo
    python -m mxnet_tpu.tracecheck --models mlp,lenet --json

Exit status is non-zero iff any unsuppressed finding remains.
"""
from __future__ import annotations

import linecache
import logging
import re
import warnings
import weakref
from collections import namedtuple

import numpy as np

from .base import MXNetError

LINTS = ("host-sync", "retrace", "donation", "const-capture", "dtype-f64",
         "dtype-weak", "collective-in-scan")

#: memory lints (implemented in :mod:`mxnet_tpu.memcheck` — the HBM-side
#: complement of this analyzer; docs/static_analysis.md "Memory lints").
#: Declared here so one suppression registry covers both analyzers.
MEM_LINTS = ("hbm-budget", "donation-waste", "temp-blowup", "resident-set")

#: communication lints (implemented in :mod:`mxnet_tpu.commscheck` — the
#: collective-traffic side of the analyzer trilogy; docs/static_analysis.md
#: "Communication lints"). Declared here so ONE suppression registry
#: covers all three analyzers.
COMM_LINTS = ("resharding-copy", "replicated-large", "gather-in-loop",
              "comms-bound")

#: roofline lints (implemented in :mod:`mxnet_tpu.flopcheck` — the
#: compute/memory-bandwidth side, the fourth and final leg of the
#: static-analysis suite; docs/static_analysis.md "Roofline lints").
#: Declared here so ONE suppression registry covers all four analyzers.
ROOFLINE_LINTS = ("memory-bound-hot", "layout-copy", "tiny-dispatch",
                  "predicted-mfu")

#: gather-type collective primitives that must NOT appear inside a scan
#: body (jaxpr level — explicit shard_map collectives). ``psum`` is the
#: expected grad/metric sync and ``ppermute`` the ring/pipeline schedule
#: (value-preserving, constant payload per step) — both allowed.
_SCAN_COLLECTIVE_PRIMS = frozenset({
    "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
    "pgather",
})

#: callback-ish primitives whose presence inside a compiled step program
#: means a host round-trip on every execution (the scan body runs them K
#: times per dispatch)
_HOST_SYNC_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
    "infeed", "outfeed", "outside_call", "host_callback_call",
})

#: StableHLO backstop patterns (caught even if a future jax renames the
#: jaxpr primitive): custom-call python callbacks and host transfer ops
_HLO_HOST_SYNC = ("python_cpu_callback", "python_gpu_callback",
                  "xla_ffi_python", "stablehlo.infeed", "stablehlo.outfeed",
                  "SendToHost", "RecvFromHost")

_64BIT = ("float64", "complex128")


def _const_bytes_default():
    from .base import env_float
    return int(env_float("MXTPU_TRACECHECK_CONST_BYTES", float(1 << 20)))


# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------

class Finding(object):
    """One structured lint hit: ``lint`` id, the ``program`` it was found
    in, a human message, the ``op_path`` (nesting through scan/cond bodies,
    e.g. ``scan/log``) and source ``provenance`` (``file:line (fn)``)."""

    __slots__ = ("lint", "program", "message", "op_path", "provenance",
                 "suppressed")

    def __init__(self, lint, program, message, op_path=None, provenance=None,
                 suppressed=False):
        self.lint = lint
        self.program = program
        self.message = message
        self.op_path = op_path
        self.provenance = provenance
        self.suppressed = suppressed

    def format(self):
        where = []
        if self.op_path:
            where.append("at %s" % self.op_path)
        if self.provenance:
            where.append(self.provenance)
        s = "[%s] %s: %s" % (self.lint, self.program, self.message)
        if where:
            s += " (%s)" % "; ".join(where)
        if self.suppressed:
            s += " [suppressed]"
        return s

    def as_dict(self):
        return {"lint": self.lint, "program": self.program,
                "message": self.message, "op_path": self.op_path,
                "provenance": self.provenance, "suppressed": self.suppressed}

    def __repr__(self):
        return "Finding(%s)" % self.format()


def unsuppressed(findings):
    return [f for f in findings if not f.suppressed]


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

# inline marker, checked on the finding's provenance line and the line
# above it: `# tracecheck: ignore[host-sync]`, `# tracecheck: ignore`
_SUPPRESS_RE = re.compile(
    r"tracecheck:\s*(?:ignore|ok)(?:\[(?P<lints>[a-z0-9_,\-\s]+)\])?")
_PROV_RE = re.compile(r"^(?P<file>.+?):(?P<line>\d+)")

#: programmatic suppressions: set of (lint, program_substring_or_None)
_SUPPRESSIONS = set()


def add_suppression(lint, program=None):
    """Suppress ``lint`` findings globally, or only for programs whose name
    contains ``program``. Returns a token usable with
    :func:`remove_suppression`."""
    if (lint not in LINTS + MEM_LINTS + COMM_LINTS + ROOFLINE_LINTS
            and lint != "*"):
        raise MXNetError("tracecheck: unknown lint %r (have %s)"
                         % (lint, ", ".join(LINTS + MEM_LINTS
                                            + COMM_LINTS
                                            + ROOFLINE_LINTS)))
    tok = (lint, program)
    _SUPPRESSIONS.add(tok)
    return tok


def remove_suppression(token):
    _SUPPRESSIONS.discard(token)


def clear_suppressions():
    _SUPPRESSIONS.clear()


def _inline_suppressed(finding):
    if not finding.provenance:
        return False
    m = _PROV_RE.match(finding.provenance)
    if not m:
        return False
    fname, line = m.group("file"), int(m.group("line"))
    for ln in (line, line - 1):
        if ln < 1:
            continue
        sm = _SUPPRESS_RE.search(linecache.getline(fname, ln))
        if sm:
            lints = sm.group("lints")
            if lints is None:
                return True
            if finding.lint in [s.strip() for s in lints.split(",")]:
                return True
    return False


def _is_suppressed(finding):
    for lint, prog in _SUPPRESSIONS:
        if lint in ("*", finding.lint) and (
                prog is None or prog in (finding.program or "")):
            return True
    return _inline_suppressed(finding)


# ---------------------------------------------------------------------------
# mode (engine owns the env knob, like dispatch_pipeline)
# ---------------------------------------------------------------------------

def mode():
    """Current retrace-policy mode: ``"warn"`` (default — log the diff),
    ``"error"`` (raise MXNetError on an unexpected retrace) or ``"off"``
    (skip signature capture entirely). Env: ``MXTPU_TRACECHECK``."""
    from . import engine
    return engine.tracecheck_mode()


def enabled():
    return mode() != "off"


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    from jax.extend import core
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for item in vals:
            if isinstance(item, core.ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, core.Jaxpr):
                yield item


def walk_jaxpr(jaxpr, path=""):
    """Yield ``(eqn, op_path)`` for every equation in ``jaxpr`` and every
    nested sub-jaxpr (scan/while/cond bodies, pjit calls, custom_vjp rules
    — anything carrying a Jaxpr in its params). ``op_path`` spells the
    nesting, e.g. ``scan/pjit/log``: a finding whose path starts with
    ``scan/`` is *inside the scan body* and runs K times per dispatch."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        p = "%s/%s" % (path, name) if path else name
        yield eqn, p
        for sub in _sub_jaxprs(eqn):
            for item in walk_jaxpr(sub, p):
                yield item


def _provenance(eqn):
    try:
        from jax._src import source_info_util
        s = source_info_util.summarize(eqn.source_info)
        return s or None
    except Exception:
        return None


# ---------------------------------------------------------------------------
# argument signatures + the retrace differ
# ---------------------------------------------------------------------------

class Signature(namedtuple("Signature", ["treedef", "metas"])):
    """A call signature in flat form: the pytree structure plus one
    metadata tuple per leaf. Built on the C-level ``tree_flatten`` so the
    per-dispatch capture in the hot loop costs microseconds — argument
    *path names* (``keystr``) are derived lazily, only when a diff must
    actually be reported."""

    __slots__ = ()

    def paths(self):
        """Per-leaf argument path strings, in leaf order (lazy: walks the
        treedef once with dummy leaves — flatten_with_path and flatten
        traverse in the same order)."""
        import jax
        dummy = jax.tree_util.tree_unflatten(self.treedef,
                                             list(range(len(self.metas))))
        flat = jax.tree_util.tree_flatten_with_path(dummy)[0]
        return [jax.tree_util.keystr(p) for p, _ in flat]

    def as_dict(self):
        return dict(zip(self.paths(), self.metas))


def _leaf_meta(leaf):
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return ("array", tuple(leaf.shape), str(leaf.dtype),
                bool(getattr(leaf, "weak_type", False)),
                bool(getattr(leaf, "_committed", False)))
    if isinstance(leaf, (bool, int, float, complex)):
        return ("pyscalar", type(leaf).__name__)
    return ("static", type(leaf).__name__, repr(leaf))


def signature(args, kwargs=None):
    """Capture the trace-cache-relevant signature of a call: for every
    argument leaf its shape / dtype / weak-type / committed-ness (array
    leaves) or its type and value (static leaves — Python scalars are
    recorded by type, since jit traces them as weak scalars whose *value*
    does not key the cache). Pure metadata: donated buffers can be signed
    after the call."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(
        (tuple(args), dict(kwargs or {})))
    return Signature(treedef, tuple(_leaf_meta(leaf) for leaf in leaves))


#: substantive array properties: a change here re-keys the TRACING cache
#: (a real retrace — recompile, new program). ``committed`` is deliberately
#: excluded: donated outputs come back device-committed, so the FIRST
#: dispatch after seeding flips every state leaf uncommitted -> committed —
#: that only re-keys jit's C++ fast-path dispatch signature (one python
#: round-trip, executable reused), not the trace.
_PROPS = ("shape", "dtype", "weak_type")


def _leaf_diff_line(path, a, b):
    if a[0] != b[0]:
        return ("argument %s: kind changed %s -> %s (%r -> %r)"
                % (path, a[0], b[0], a[1:], b[1:]))
    if a[0] == "array":
        for i, prop in enumerate(_PROPS, start=1):
            if a[i] != b[i]:
                return ("argument %s: %s %s -> %s" % (path, prop, a[i], b[i]))
        return None  # only committedness differs: benign
    if a[0] == "pyscalar":
        return ("argument %s: Python scalar type %s -> %s"
                % (path, a[1], b[1]))
    return "argument %s: static value %s -> %s" % (path, a[2], b[2])


def explain_diff(old, new):
    """The cache-key differ: given two call signatures for the same
    function, name exactly which argument's shape / dtype / weak-type /
    static value changed. Returns a list of human-readable lines — EMPTY
    when nothing substantive changed (benign committedness churn from
    donation is ignored; :func:`benign_diff` names it)."""
    if isinstance(old, Signature) and isinstance(new, Signature):
        if old.treedef == new.treedef:
            # per-dispatch fast path: elementwise meta compare, path names
            # derived only for the (rare) leaves that actually changed
            idxs = [i for i, (a, b) in enumerate(zip(old.metas, new.metas))
                    if a != b]
            if not idxs:
                return []
            paths = new.paths()
            lines = [_leaf_diff_line(paths[i], old.metas[i], new.metas[i])
                     for i in idxs]
            return [ln for ln in lines if ln is not None]
        old, new = old.as_dict(), new.as_dict()
    elif isinstance(old, Signature):
        old = old.as_dict()
    elif isinstance(new, Signature):
        new = new.as_dict()
    lines = []
    for path in sorted(set(old) | set(new)):
        a, b = old.get(path), new.get(path)
        if a == b:
            continue
        if a is None:
            lines.append("argument %s: newly present %r" % (path, (b,)))
        elif b is None:
            lines.append("argument %s: no longer present (was %r)"
                         % (path, (a,)))
        else:
            ln = _leaf_diff_line(path, a, b)
            if ln is not None:
                lines.append(ln)
    return lines


def benign_diff(old, new):
    """Differences that re-key only jit's C++ dispatch fast path, not the
    trace: today, array committed-ness (donated outputs come back
    committed). Returns human-readable lines, empty when none."""
    if isinstance(old, Signature):
        old = old.as_dict()
    if isinstance(new, Signature):
        new = new.as_dict()
    lines = []
    for path in sorted(set(old) & set(new)):
        a, b = old[path], new[path]
        if (a != b and a[0] == b[0] == "array" and len(a) > 4
                and len(b) > 4 and a[4] != b[4] and a[1:4] == b[1:4]):
            lines.append("argument %s: committed %s -> %s"
                         % (path, a[4], b[4]))
    return lines


class RetraceError(MXNetError):
    """Raised by :class:`TraceWatcher` under ``MXTPU_TRACECHECK=error``.

    The watcher runs AFTER the dispatch, which has already DONATED the old
    state buffers — so when this is raised from inside
    ``TrainStep.step``/``run_steps``, ``result`` carries the call's return
    value (new state + outputs/metrics) and the caller must adopt it
    (``Module`` does) rather than keep a reference to deleted buffers."""

    def __init__(self, msg):
        super(RetraceError, self).__init__(msg)
        self.result = None


RetraceEvent = namedtuple("RetraceEvent", ["site", "diff"])

#: process-global log of every detected retrace (test_utils.assert_no_retrace
#: snapshots its length; Speedometer counts per-TrainStep events instead)
RETRACE_EVENTS = []


def retrace_count():
    return len(RETRACE_EVENTS)


#: per-base-name sequence numbers for :func:`unique_name` — registry names
#: must stay process-unique even when symbols share a name (the default
#: "softmax" head is common), or a second instance's programs would shadow
#: the first's in ``PROGRAMS`` and audits would silently check the wrong set
_NAME_SEQ = {}


def unique_name(base):
    """Process-unique program/watcher base name: first caller gets ``base``
    verbatim, later callers get ``base#2``, ``base#3``, ... Shared by
    ``TrainStep`` and the serving tier so their registry entries never
    collide."""
    n = _NAME_SEQ.get(base, 0) + 1
    _NAME_SEQ[base] = n
    return base if n == 1 else "%s#%d" % (base, n)


def make_watcher(base):
    """A :class:`TraceWatcher` under a process-unique name (see
    :func:`unique_name`)."""
    return TraceWatcher(unique_name(base))


class TraceWatcher(object):
    """Per-call-site retrace detector: records the argument signature and
    the jit entry's ``_cache_size()`` after every watched call; when the
    cache grows for an already-seen key, the signature differ names the
    offending argument and property, the event is counted (process-global
    ``RETRACE_EVENTS`` + ``guard.TRAINING_HEALTH.retraces`` + the per-run
    health when one is attached), and per ``MXTPU_TRACECHECK`` the diff is
    logged (``warn``) or raised (``error``)."""

    __slots__ = ("name", "events", "_seen")

    def __init__(self, name):
        self.name = name
        self.events = []
        self._seen = {}

    def after_call(self, key, jitfn, sig, health=None):
        try:
            size = jitfn._cache_size()
        except Exception:
            return None
        prev = self._seen.get(key)
        self._seen[key] = (sig, size)
        if prev is None or size <= prev[1]:
            return None
        diff = explain_diff(prev[0], sig)
        if not diff:
            # the cache entry count grew without any substantive argument
            # change: committedness churn from donation (benign, the
            # executable is reused) — or, with no benign diff either, a
            # closure/jit-option change worth surfacing
            if benign_diff(prev[0], sig):
                return None
            diff = ["no argument signature difference visible (a "
                    "closure/global or jit option changed?)"]
        return self._emit(key, diff, health)

    def _emit(self, key, diff, health):
        site = "%s/%s" % (self.name, key)
        ev = RetraceEvent(site=site, diff=tuple(diff))
        self.events.append(ev)
        RETRACE_EVENTS.append(ev)
        from . import guard as _guard
        if health is not None:
            health.record_retrace(site)
        else:
            _guard.TRAINING_HEALTH.record_retrace(site)
        msg = ("tracecheck: unexpected retrace at %s — the jit cache missed "
               "for an already-compiled program. Changed: %s"
               % (site, "; ".join(diff)))
        if mode() == "error":
            raise RetraceError(msg)
        logging.warning(msg)
        return ev


# ---------------------------------------------------------------------------
# program registry (TrainStep registers every jit cache entry here)
# ---------------------------------------------------------------------------

ProgramRecord = namedtuple("ProgramRecord",
                           ["name", "fn_ref", "arg_structs", "donate_argnums"])

#: name -> ProgramRecord; fn_ref is a weakref so the registry never keeps a
#: dead TrainStep's compiled programs alive
PROGRAMS = {}


def _to_struct(x):
    import jax
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
    return x


def register_program(name, jitfn, args, donate_argnums=()):
    """Register a live jitted program (with abstract example arguments) for
    later auditing via :func:`check_registered`. The args are converted to
    ``ShapeDtypeStruct``s — no device memory is pinned."""
    import jax
    structs = jax.tree_util.tree_map(_to_struct, tuple(args))
    PROGRAMS[name] = ProgramRecord(name, weakref.ref(jitfn), structs,
                                   tuple(donate_argnums))
    return PROGRAMS[name]


def registered_programs():
    """Live registered programs (dead weakrefs are dropped lazily)."""
    dead = [n for n, r in PROGRAMS.items() if r.fn_ref() is None]
    for n in dead:
        del PROGRAMS[n]
    return list(PROGRAMS.values())


def check_registered(const_bytes=None, match=None):
    """Audit every live registered program — the guard-on / guard-off /
    pipelined jit caches as a unit — and return all findings."""
    findings = []
    for rec in registered_programs():
        if match is not None and match not in rec.name:
            continue
        fn = rec.fn_ref()
        if fn is None:
            continue
        findings += check_program(fn, rec.arg_structs,
                                  donate_argnums=rec.donate_argnums,
                                  name=rec.name, const_bytes=const_bytes)
    return findings


# ---------------------------------------------------------------------------
# the static pass
# ---------------------------------------------------------------------------

def _flat_arg_paths(args, kwargs):
    import jax
    leaves = jax.tree_util.tree_flatten_with_path((tuple(args),
                                                   dict(kwargs or {})))[0]
    return [(jax.tree_util.keystr(p), leaf) for p, leaf in leaves]


def _lint_host_sync(closed, hlo_text, name):
    findings = []
    for eqn, path in walk_jaxpr(closed.jaxpr):
        if eqn.primitive.name in _HOST_SYNC_PRIMS:
            inside = ("scan" in path.split("/")[:-1]
                      or "while" in path.split("/")[:-1])
            msg = ("host round-trip op %r compiled into the program%s — "
                   "every dispatch will synchronize with the host"
                   % (eqn.primitive.name,
                      " INSIDE the scan body (runs K times per dispatch)"
                      if inside else ""))
            findings.append(Finding("host-sync", name, msg, op_path=path,
                                    provenance=_provenance(eqn)))
    if not findings and hlo_text:
        # one finding per program: a callback's custom-call target can
        # match several patterns (``xla_ffi_python_cpu_callback``)
        pat = next((p for p in _HLO_HOST_SYNC if p in hlo_text), None)
        if pat is not None:
            findings.append(Finding(
                "host-sync", name,
                "lowered StableHLO contains host-transfer construct %r"
                % pat, op_path="stablehlo"))
    return findings


def _lint_dtype(closed, args, kwargs, name):
    findings = []
    paths = _flat_arg_paths(args, kwargs)
    invars = closed.jaxpr.invars
    for i, v in enumerate(invars):
        aval = v.aval
        pstr = paths[i][0] if i < len(paths) else "#%d" % i
        dt = str(getattr(aval, "dtype", ""))
        if dt in _64BIT:
            findings.append(Finding(
                "dtype-f64", name,
                "program input %s is %s — pin a 32-bit dtype (TPU has no "
                "native f64)" % (pstr, dt)))
        if getattr(aval, "weak_type", False):
            findings.append(Finding(
                "dtype-weak", name,
                "program input %s is weak-typed (a bare Python scalar "
                "reached the trace): pin it, e.g. "
                "jnp.asarray(np.asarray(x, np.float32)) — weak/strong "
                "toggling retraces the program" % pstr))
    for i, c in enumerate(closed.consts):
        dt = str(getattr(c, "dtype", ""))
        if dt in _64BIT:
            findings.append(Finding(
                "dtype-f64", name,
                "closure-captured constant consts[%d] is %s%s" %
                (i, dt, list(getattr(c, "shape", ()))),
                op_path="consts[%d]" % i))
    for eqn, path in walk_jaxpr(closed.jaxpr):
        for ov in eqn.outvars:
            dt = str(getattr(ov.aval, "dtype", ""))
            if dt in _64BIT:
                findings.append(Finding(
                    "dtype-f64", name,
                    "op %r produces %s%s — a 64-bit value inside the step "
                    "program" % (eqn.primitive.name, dt,
                                 list(getattr(ov.aval, "shape", ()))),
                    op_path=path, provenance=_provenance(eqn)))
                break  # one finding per eqn is enough
    return findings


def _const_sources(fn):
    """Python-level ``{name: value}`` candidates for a program's captured
    constants: the (unwrapped) traced function's closure cells plus the
    globals its code references."""
    import inspect
    try:
        f = inspect.unwrap(getattr(fn, "__wrapped__", fn))
    except Exception:
        f = fn
    code = getattr(f, "__code__", None)
    if code is None:
        return {}
    out = {}
    for nm, cell in zip(code.co_freevars, getattr(f, "__closure__", ())
                        or ()):
        try:
            out[nm] = cell.cell_contents
        except ValueError:
            pass
    g = getattr(f, "__globals__", None) or {}
    for nm in code.co_names:
        if nm in g:
            out.setdefault(nm, g[nm])
    return out


def _const_var_name(c, sources):
    """Best-effort name of the closure variable a captured constant came
    from: object identity first, else a UNIQUE shape+dtype match (an
    ambiguous match names nothing rather than the wrong variable)."""
    ids = [nm for nm, v in sources.items() if v is c]
    if len(ids) == 1:
        return ids[0]
    shape = tuple(getattr(c, "shape", ()) or ())
    dt = str(getattr(c, "dtype", ""))
    matches = [nm for nm, v in sources.items()
               if hasattr(v, "shape") and hasattr(v, "dtype")
               and tuple(getattr(v, "shape", ()) or ()) == shape
               and str(getattr(v, "dtype", "")) == dt]
    if len(matches) == 1:
        return matches[0]
    return None


def _const_first_uses(closed):
    """``const index -> (op_path, provenance)`` of the first equation
    consuming each captured constant."""
    uses = {}
    cids = {id(v): i for i, v in enumerate(closed.jaxpr.constvars)}
    if not cids:
        return uses
    for eqn, path in walk_jaxpr(closed.jaxpr):
        for v in eqn.invars:
            i = cids.get(id(v))
            if i is not None and i not in uses:
                uses[i] = (path, _provenance(eqn))
        if len(uses) == len(cids):
            break
    return uses


def _lint_consts(closed, const_bytes, name, fn=None):
    threshold = (_const_bytes_default() if const_bytes is None
                 else int(const_bytes))
    findings = []
    sources = _const_sources(fn) if fn is not None else {}
    first_uses = None
    for i, c in enumerate(closed.consts):
        nbytes = getattr(c, "nbytes", 0) or 0
        if nbytes > threshold:
            if first_uses is None:
                first_uses = _const_first_uses(closed)
            varname = _const_var_name(c, sources)
            _, prov = first_uses.get(i, (None, None))
            findings.append(Finding(
                "const-capture", name,
                "closure-captured constant %s (consts[%d], %s%s) is %d "
                "bytes (> %d, MXTPU_TRACECHECK_CONST_BYTES) baked into "
                "the program — pass it as an argument instead"
                % ("variable %r" % varname if varname else "consts[%d]" % i,
                   i, getattr(c, "dtype", "?"),
                   list(getattr(c, "shape", ())), nbytes, threshold),
                op_path="consts[%d]" % i, provenance=prov))
    return findings


def _lint_collectives(closed, name):
    """Jaxpr half of ``collective-in-scan``: explicit (shard_map-style)
    gather-type collectives inside a scan/while body. GSPMD-inserted
    collectives don't exist at jaxpr level — :func:`check_collectives`
    compiles the partitioned program and audits those."""
    findings = []
    for eqn, path in walk_jaxpr(closed.jaxpr):
        pname = eqn.primitive.name
        if pname not in _SCAN_COLLECTIVE_PRIMS:
            continue
        parents = path.split("/")[:-1]
        if "scan" not in parents and "while" not in parents:
            continue
        findings.append(Finding(
            "collective-in-scan", name,
            "gather-type collective %r inside the scan body (runs K times "
            "per dispatch) — a data-parallel step syncs only by psum (the "
            "grad/metric all-reduce); a gather here usually means a "
            "sharding that forces the full batch onto every chip" % pname,
            op_path=path, provenance=_provenance(eqn)))
    return findings


def check_collectives(fn, args=(), kwargs=None, name=None,
                      allow=("all-reduce", "collective-permute")):
    """Compiled-HLO half of ``collective-in-scan``: COMPILE the program
    (partitioning happens at compile time, so GSPMD-inserted collectives
    are invisible to the jaxpr/StableHLO passes) and flag every collective
    opcode inside a while body that is not in ``allow``. The expected
    data-parallel K-step scan lowers to all-reduces only — one combined
    gradient sync plus the packed metric/sentinel reduction; any
    all-gather / reduce-scatter / all-to-all in the loop body is a
    sharding mistake paying its bandwidth K times per dispatch. The
    default ``allow`` matches the jaxpr pass: all-reduce (psum, the
    expected sync) and collective-permute (ppermute — the value-preserving
    ring/pipeline schedule, constant payload per step).

    ``fn`` may be a jitted function or a plain callable; ``args`` must
    carry the REAL shardings (device arrays or ShapeDtypeStructs with
    ``sharding=``) — unsharded arguments compile an unpartitioned program
    with no collectives at all. Compiling is the cost of this check: use
    it on gates and tests, not in per-dispatch paths. Returns findings
    with suppressions applied, like :func:`check_program`.

    This is a thin alias over :mod:`mxnet_tpu.commscheck`'s collective
    inventory pass (ONE collective parser for both analyzers) — the
    findings keep this module's historical ``collective-in-scan`` lint
    id, so existing suppressions and tests are unaffected; commscheck's
    own generalization is the ``gather-in-loop`` lint."""
    from . import commscheck as _cc
    report = _cc.analyze(fn, args, kwargs=kwargs, name=name)
    if report.hlo_unavailable:
        # the pre-dedupe implementation read compiled.as_text() unguarded
        # and raised; an empty-for-lack-of-evidence inventory must not
        # become a silent [] under the same contract
        raise MXNetError(
            "check_collectives: compiled HLO text unavailable for %s — "
            "cannot audit the partitioned program's collectives"
            % report.program)
    findings = _cc.loop_findings(report, report.program,
                                 lint="collective-in-scan", allow=allow)
    for f in findings:
        f.suppressed = _is_suppressed(f)
    return findings


_MAIN_SIG_RE = re.compile(r"func\.func\s+public\s+@main\((?P<params>.*?)\)"
                          r"\s*->", re.S)
_PARAM_SPLIT_RE = re.compile(r"%arg\d+:")


def _main_param_attrs(hlo_text):
    """Per-parameter attribute strings of the StableHLO @main signature
    (jax marks a successfully donated parameter with
    ``tf.aliasing_output``). None when the signature cannot be parsed."""
    m = _MAIN_SIG_RE.search(hlo_text or "")
    if not m:
        return None
    parts = _PARAM_SPLIT_RE.split(m.group("params"))
    return [p for p in parts[1:]]  # parts[0] is the text before %arg0


def _lint_donation(closed, hlo_text, lowering_warnings, donate_argnums,
                   args, kwargs, name):
    findings = []
    donate_argnums = tuple(donate_argnums or ())
    if not donate_argnums:
        return findings
    import jax
    # flat leaf index ranges of the donated positional args
    donated = set()
    labels = {}
    offset = 0
    for i, a in enumerate(args):
        leaves = jax.tree_util.tree_flatten_with_path(a)[0]
        for j, (path, _) in enumerate(leaves):
            if i in donate_argnums:
                donated.add(offset + j)
                labels[offset + j] = "args[%d]%s" % (
                    i, jax.tree_util.keystr(path))
        offset += len(leaves)
    attrs = _main_param_attrs(hlo_text)
    if attrs is not None and len(attrs) == offset + len(
            jax.tree_util.tree_leaves(dict(kwargs or {}))):
        for idx in sorted(donated):
            if "jax.buffer_donor" in attrs[idx]:
                # SPMD lowering (sharded arguments) defers aliasing to the
                # compiler: the parameter is marked a buffer donor and XLA
                # resolves the input_output_alias at compile time — the
                # missing tf.aliasing_output is NOT evidence of a copy
                # here. The compiled-side check (memcheck donation-waste,
                # which reads the executable's real alias accounting) is
                # the evidence-bearing lint for these programs.
                continue
            if "tf.aliasing_output" not in attrs[idx]:
                findings.append(Finding(
                    "donation", name,
                    "donated argument %s is NOT aliased to any output — "
                    "the lowering copies it anyway (shape/dtype mismatch "
                    "with every output, or it is returned transformed)"
                    % labels[idx]))
    # the lowering's own complaint is authoritative when emitted
    for w in lowering_warnings or ():
        msg = str(getattr(w, "message", w))
        if "donated" in msg.lower():
            if not findings:
                findings.append(Finding(
                    "donation", name,
                    "lowering reports unusable donations: %s"
                    % msg.splitlines()[0]))
    return findings


def check_program(fn, args=(), kwargs=None, donate_argnums=(), name=None,
                  const_bytes=None):
    """Run every static lint over ONE program.

    ``fn`` may be a jitted function (its own donate/static settings are
    kept) or a plain callable (wrapped in ``jax.jit(fn,
    donate_argnums=...)``). The program is traced and lowered but NEVER
    executed — arguments can be real arrays or ``ShapeDtypeStruct``s.
    Returns a list of :class:`Finding` with inline/programmatic
    suppressions already applied (``.suppressed``)."""
    import jax
    kwargs = dict(kwargs or {})
    if name is None:
        name = getattr(fn, "__name__", None) or repr(fn)
    jitted = fn if hasattr(fn, "trace") and hasattr(fn, "lower") \
        else jax.jit(fn, donate_argnums=donate_argnums or ())
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        traced = jitted.trace(*args, **kwargs)
        lowered = traced.lower()
    closed = traced.jaxpr
    try:
        hlo_text = lowered.as_text()
    except Exception:
        hlo_text = ""
    findings = []
    findings += _lint_host_sync(closed, hlo_text, name)
    findings += _lint_dtype(closed, args, kwargs, name)
    findings += _lint_consts(closed, const_bytes, name, fn=jitted)
    findings += _lint_collectives(closed, name)
    findings += _lint_donation(closed, hlo_text, wlog, donate_argnums,
                               args, kwargs, name)
    for f in findings:
        f.suppressed = _is_suppressed(f)
    return findings


# ---------------------------------------------------------------------------
# TrainStep auditing + the model-zoo CLI
# ---------------------------------------------------------------------------

def train_step_programs(ts, data_shapes, label_shapes, k=2, guard=True,
                        name=None):
    """The ``(name, jitfn, example_args)`` program set of one
    :class:`~mxnet_tpu.train_step.TrainStep` — unguarded step, K-step
    scan, and (with ``guard``) their guarded variants — over the given
    ``{name: shape}`` dicts. This is THE recipe for what training
    dispatches (argument order, donated state at argnum 0, the traced
    lr/poison extras), shared by :func:`check_train_step` and
    ``memcheck.check_train_step`` so the two analyzers can never drift
    apart on program shape. No step program ever executes; the state
    skeleton is built with a no-op initializer (zero-filled buffers,
    never trained — param-drawing RNG and its host cost are skipped)
    purely to capture the state pytree's shapes/dtypes."""
    import jax
    name = name or "TrainStep(%s)" % ts.symbol.name
    state = ts.init(data_shapes, label_shapes,
                    initializer=lambda desc, arr: None, seed=0)
    bs = next(iter(data_shapes.values()))[0]
    f32 = np.float32

    def sds(shape, dtype=f32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    batch = {n: sds(s) for n, s in data_shapes.items()}
    batch.update({n: sds(s) for n, s in (label_shapes or {}).items()})
    sb = {n: sds((k,) + tuple(s.shape), s.dtype) for n, s in batch.items()}
    key = ts._dispatch_key()
    lr = sds(())
    lrs = sds((k,))
    poison = sds(())
    poisons = sds((k,))
    state_s = jax.tree_util.tree_map(_to_struct, state)

    programs = [
        ("%s/step" % name, ts._build(bs), (state_s, batch, key, lr)),
        ("%s/scan[k=%d]" % (name, k), ts._build_scan(bs, k),
         (state_s, sb, key, lrs)),
    ]
    if guard:
        programs += [
            ("%s/guarded-step" % name, ts._build_guard_step(bs),
             (state_s, batch, key, lr, poison)),
            ("%s/guarded-scan[k=%d]" % (name, k),
             ts._build_scan(bs, k, guard=True),
             (state_s, sb, key, lrs, poisons)),
        ]
    return programs


def check_train_step(ts, data_shapes, label_shapes, k=2, guard=True,
                     const_bytes=None, name=None):
    """Audit a :class:`~mxnet_tpu.train_step.TrainStep`'s full program set
    — unguarded step, guarded step, K-step scan, guarded K-step scan —
    over the given ``{name: shape}`` dicts (see
    :func:`train_step_programs` for how the set is built)."""
    findings = []
    for pname, jitfn, pargs in train_step_programs(
            ts, data_shapes, label_shapes, k=k, guard=guard, name=name):
        findings += check_program(jitfn, pargs, donate_argnums=(0,),
                                  name=pname, const_bytes=const_bytes)
    return findings


#: model-zoo audit configs: tiny shapes — no step program executes (state
#: buffers are zero-filled, initializer skipped), so even 224px nets stay
#: cheap
ZOO = {
    "mlp": dict(kwargs=dict(num_classes=4, hidden=(32,)),
                data=(8, 64), label=(8,)),
    "lenet": dict(kwargs=dict(num_classes=10),
                  data=(4, 1, 28, 28), label=(4,)),
    "resnet": dict(kwargs=dict(num_classes=4, num_layers=18,
                               image_shape="3,16,16"),
                   data=(2, 3, 16, 16), label=(2,)),
    "alexnet": dict(kwargs=dict(num_classes=10),
                    data=(2, 3, 224, 224), label=(2,)),
    "vgg": dict(kwargs=dict(num_classes=10, num_layers=11),
                data=(2, 3, 224, 224), label=(2,)),
    "inception-bn": dict(kwargs=dict(num_classes=10),
                         data=(2, 3, 224, 224), label=(2,)),
    "transformer": dict(kwargs=dict(vocab_size=32, embed=16, num_heads=2,
                                    num_layers=1, seq_len=16),
                        data=(2, 16), label=(2, 16)),
    # multi-head detection (rank-3 cls + loc heads + in-graph
    # MultiBoxTarget matching): the packed-accumulator protocol's proof
    # model — its label rides the net's OWN outputs, name "label"
    "ssd": dict(kwargs=dict(num_classes=3, width=8),
                data=(2, 3, 32, 32), label=(2, 2, 5),
                label_name="label"),
}


def zoo_train_step(mname, optimizer="sgd", learning_rate=0.1):
    """Build one zoo model's ``(TrainStep, data_shapes, label_shapes)`` —
    ONE recipe shared by the tracecheck/memcheck/commscheck zoo gates
    (per-model data/label names live in the ZOO config; SSD's label
    variable is ``label``, not ``softmax_label``)."""
    from . import models
    from .train_step import TrainStep
    if mname not in ZOO:
        raise MXNetError("unknown zoo model %r (have %s)"
                         % (mname, ", ".join(sorted(ZOO))))
    cfg = ZOO[mname]
    sym = models.get_symbol(mname, **cfg["kwargs"])
    dname = cfg.get("data_name", "data")
    lname = cfg.get("label_name", "softmax_label")
    ts = TrainStep(sym, data_names=(dname,), label_names=(lname,),
                   optimizer=optimizer, learning_rate=learning_rate)
    return ts, {dname: cfg["data"]}, {lname: cfg["label"]}


def check_zoo(names=None, k=2, guard=True, const_bytes=None, log=None):
    """Audit the model zoo's step programs; returns (findings, n_programs).
    ``names=None`` audits every shipped model."""
    names = list(names) if names else sorted(ZOO)
    findings = []
    nprog = 0
    for mname in names:
        if mname not in ZOO:
            raise MXNetError("tracecheck: unknown zoo model %r (have %s)"
                             % (mname, ", ".join(sorted(ZOO))))
        if log:
            log("auditing %s ..." % mname)
        ts, data_shapes, label_shapes = zoo_train_step(mname)
        findings += check_train_step(
            ts, data_shapes, label_shapes,
            k=k, guard=guard, const_bytes=const_bytes, name=mname)
        nprog += 4 if guard else 2
    return findings, nprog


def report(findings, out=None):
    """Write one formatted line per finding (the CLIs' human-readable
    mode; their ``--json`` paths serialize a structured object
    themselves)."""
    import sys
    out = out or sys.stdout
    for f in findings:
        out.write(f.format() + "\n")


def main(argv=None):
    import argparse
    import sys
    p = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.tracecheck",
        description="Static analyzer for compiled step programs: host-sync,"
                    " donation, const-capture and dtype lints over the"
                    " jaxpr/StableHLO of the model zoo's train steps"
                    " (docs/static_analysis.md).")
    p.add_argument("--zoo", action="store_true",
                   help="audit every shipped model's step/scan programs")
    p.add_argument("--models", default=None,
                   help="comma-separated zoo subset (implies --zoo)")
    p.add_argument("--k", type=int, default=2,
                   help="scan depth for the K-step programs (default 2)")
    p.add_argument("--no-guard", action="store_true",
                   help="skip the guarded program variants")
    p.add_argument("--const-bytes", type=int, default=None,
                   help="const-capture threshold (default "
                        "MXTPU_TRACECHECK_CONST_BYTES or 1 MiB)")
    p.add_argument("--json", action="store_true", help="JSON findings")
    p.add_argument("--list", action="store_true",
                   help="list zoo models and exit")
    p.add_argument("--quiet", action="store_true",
                   help="suppress progress lines")
    args = p.parse_args(argv)
    if args.list:
        for n in sorted(ZOO):
            print(n)
        return 0
    if not (args.zoo or args.models):
        p.error("nothing to check: pass --zoo or --models")
    names = ([s.strip() for s in args.models.split(",") if s.strip()]
             if args.models else None)
    log = (lambda m: None) if (args.quiet or args.json) \
        else (lambda m: print(m, file=sys.stderr))
    findings, nprog = check_zoo(names=names, k=args.k,
                                guard=not args.no_guard,
                                const_bytes=args.const_bytes, log=log)
    bad = unsuppressed(findings)
    if args.json:
        import json as _json
        print(_json.dumps({
            "findings": [f.as_dict() for f in findings],
            "total": len(findings),
            "suppressed": len(findings) - len(bad),
            "programs": nprog,
        }, indent=2))
    else:
        report(findings)
        print("tracecheck: %d finding(s) (%d suppressed) over %d program(s)"
              % (len(findings), len(findings) - len(bad), nprog))
    return 1 if bad else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
