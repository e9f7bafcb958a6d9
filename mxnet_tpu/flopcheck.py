"""flopcheck: a static per-kernel compute/memory roofline analyzer for
compiled programs.

The analyzer trilogy audits retraces (tracecheck), HBM footprint
(memcheck) and collective traffic (commscheck); this module completes
the suite with the resource none of them price: the compute itself.
ROADMAP item 3 wants a Pallas kernel tier "searched by the autotuner",
but a search loop needs a per-kernel cost signal before it measures
anything — TVM's whole premise (arXiv:1802.04799) — and MXNet's
original design treats the graph cost model as the substrate every
optimization pass stands on (arXiv:1512.01274). ``flopcheck`` is that
signal: it names WHICH fusions are worth a hand kernel, before any
profiler runs.

Like its siblings it compiles a program WITHOUT executing it (arguments
may be ``ShapeDtypeStruct``s) and walks the scheduled HLO — here
fusion-by-fusion into a per-program **kernel inventory**
(:class:`KernelEntry`): per-fusion FLOPs (structural estimates
normalized against ``compiled.cost_analysis()`` — "cost-analysis
apportioned", so the sum matches XLA's own count), HBM bytes moved
(operand + result shapes x memcheck's dtype-width table, alias-aware),
arithmetic intensity against the device ridge point
(``peak_flops / hbm_bandwidth`` from :mod:`mxnet_tpu.devspec`),
compute-bound/memory-bound classification, in-loop multipliers for
scan/while bodies, op path and source provenance. From the inventory:

* **predicted step time** — per kernel ``max(flops/peak, bytes/bw)``,
  summed with the in-loop multipliers and composed with commscheck's
  collective wire-time model (collective opcodes are EXCLUDED from the
  kernel inventory so their time is never double-counted);
* **predicted MFU** — dispatch FLOPs over ``predicted_time x peak``;
* a ranked **hotspot table** (``--hotspots``) — the Pallas tier's
  shopping list: the biggest memory-bound fusions are exactly the
  flash-attention/fused-optimizer candidates.

Four lints ride tracecheck's :class:`~mxnet_tpu.tracecheck.Finding`
framework and shared suppression registry
(``tracecheck.ROOFLINE_LINTS``):

====================  ====================================================
lint id               fires when
====================  ====================================================
``memory-bound-hot``  one fusion holds >= ``MXTPU_FLOPCHECK_HOT_FRAC``
                      of the predicted step time with arithmetic
                      intensity below the device ridge point (and moves
                      >= ``MXTPU_FLOPCHECK_HOT_BYTES``) — the
                      flash-attention / fused-optimizer signature: the
                      step is waiting on HBM, a hand kernel that keeps
                      the working set in VMEM wins
``layout-copy``       a transpose/copy/bitcast kernel (or a fusion of
                      nothing else) moves more than
                      ``MXTPU_FLOPCHECK_LAYOUT_BYTES`` per dispatch —
                      pure data motion, zero FLOPs: fix the layout that
                      forced it
``tiny-dispatch``     more than ``MXTPU_FLOPCHECK_TINY_COUNT`` kernel
                      executions per dispatch each predicted under
                      ``MXTPU_FLOPCHECK_TINY_US`` — the fusion-
                      regression signature: dispatch overhead dominates
                      compute
``predicted-mfu``     the program's predicted MFU is below
                      ``MXTPU_FLOPCHECK_MIN_MFU`` (default 0 =
                      disabled; arm it per-deploy for the flagship LM)
====================  ====================================================

The roofline is a MODEL, not a measurement: structural FLOP counts,
spec-sheet peak/bandwidth rows (:mod:`mxnet_tpu.devspec` — the SAME
table commscheck's wire model reads), zero overlap assumed. What holds
it against the chip: ``chip_smoke.py``'s decode legs check this
inventory of the chip's own step executable against the cache.

CLI::

    python -m mxnet_tpu.flopcheck --zoo                   # 32 programs
    python -m mxnet_tpu.flopcheck --zoo --sharded         # all 36
    python -m mxnet_tpu.flopcheck --models transformer --hotspots 10
    python -m mxnet_tpu.flopcheck --zoo --sharded \\
        --write-baseline FLOPCHECK_baseline.json

``--baseline`` is the CI drift gate (``ci/flopcheck.sh``): per-program
kernel count, predicted step time, predicted MFU and top-hotspot
identity against the committed ``FLOPCHECK_baseline.json`` with a
tolerance band (``MXTPU_FLOPCHECK_TOL``, default 10%) — a refactor that
shatters a fusion or bloats the predicted step time fails CI with the
kernel breakdown and source provenance, before any profiler runs. The
same absence-of-evidence discipline as commscheck: an unreadable HLO
fails the gate (and ``--write-baseline`` refuses it), never reads as an
improvement.

``--memcheck-baseline`` / ``--commscheck-baseline`` turn the run into
the COMBINED compile-once gate: one compile per program feeds all three
static analyzers (memcheck + commscheck + flopcheck), cutting CI
wall-clock by ~3x over three separate sweeps (the gate logs the compile
phase it shared). ``MXTPU_FLOPCHECK=warn|error`` arms a one-time
first-dispatch audit through the TrainStep registration hook.
"""
from __future__ import annotations

import json
import re

import numpy as np

from .base import MXNetError, env_float, env_int, env_str
from .tracecheck import (Finding, ROOFLINE_LINTS, _is_suppressed,
                         unsuppressed, ZOO)
# ONE HLO-metadata parser set across the analyzer suite: byte/shape
# helpers, the computation-header regex and the op_name/source
# provenance regexes all live in memcheck
from .memcheck import (_parse_bytes, _shape_bytes, _fmt_bytes, _unescape,
                       _COMP_RE, _OPNAME_RE, _VIEW_OPCODES,
                       instr_provenance, parse_stack_frames)
# the collective inventory + wire-time model live in commscheck; the
# tuple-capable type pattern is shared so fusion results parse
from .commscheck import (COLLECTIVE_KINDS, CommsReport, _TYPE_PAT,
                         _infer_mesh, parse_collectives, struct_args)
from . import devspec

__all__ = [
    "KernelEntry", "RooflineReport", "parse_kernels", "analyze",
    "analyze_compiled", "lint_report", "check_program", "check_train_step",
    "check_zoo", "check_sharded", "compiled_zoo_programs",
    "compiled_sharded_programs", "hotspot_report", "write_baseline",
    "compare_baseline", "hot_frac", "hot_bytes", "layout_bytes",
    "layout_frac", "tiny_us",
    "tiny_count", "min_mfu", "tolerance", "maybe_audit_dispatch", "main",
    "ROOFLINE_LINTS",
]


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def hot_frac():
    """``memory-bound-hot`` step-time share threshold
    (``MXTPU_FLOPCHECK_HOT_FRAC``, default 0.6)."""
    return env_float("MXTPU_FLOPCHECK_HOT_FRAC", 0.6)


def hot_bytes():
    """``memory-bound-hot`` absolute traffic floor — a kernel must move
    this much per dispatch before its step-time share matters
    (``MXTPU_FLOPCHECK_HOT_BYTES``, K/M/G/T binary suffixes; default
    4 MiB — the zoo's deliberately tiny programs all have SOME dominant
    kernel, and flagging a 50 KiB matvec as a Pallas candidate would be
    noise)."""
    env = _parse_bytes(env_str("MXTPU_FLOPCHECK_HOT_BYTES"),
                       "MXTPU_FLOPCHECK_HOT_BYTES")
    return env if env is not None else (4 << 20)


def layout_bytes():
    """``layout-copy`` absolute per-dispatch traffic floor
    (``MXTPU_FLOPCHECK_LAYOUT_BYTES``, default 4 MiB) — a copy must move
    at least this much before its traffic SHARE (:func:`layout_frac`)
    matters; keeps KiB-scale relayouts in toy programs quiet."""
    env = _parse_bytes(env_str("MXTPU_FLOPCHECK_LAYOUT_BYTES"),
                       "MXTPU_FLOPCHECK_LAYOUT_BYTES")
    return env if env is not None else (4 << 20)


def layout_frac():
    """``layout-copy`` share-of-total-traffic threshold
    (``MXTPU_FLOPCHECK_LAYOUT_FRAC``, default 0.25): a pure-data-motion
    kernel only fires when it carries at least this fraction of the
    program's HBM bytes per dispatch. An absolute threshold alone cannot
    work — vgg legitimately re-lays-out ~1.5 GiB of stacked conv
    activations, a rounding error next to its conv traffic, while a
    transpose chain moving 10 MiB of a 12 MiB program IS the problem."""
    return env_float("MXTPU_FLOPCHECK_LAYOUT_FRAC", 0.25)


def tiny_us():
    """``tiny-dispatch`` per-kernel predicted-time floor in microseconds
    (``MXTPU_FLOPCHECK_TINY_US``, default 1.0)."""
    return env_float("MXTPU_FLOPCHECK_TINY_US", 1.0)


def tiny_count():
    """``tiny-dispatch`` kernel-execution count threshold per dispatch
    (``MXTPU_FLOPCHECK_TINY_COUNT``, default 4096 — above every zoo
    program including inception-bn's guarded K-step scan (~3.2k genuine
    small executions) and the nested ring-attention scans; a fusion
    regression that shatters the step blows past it)."""
    return env_int("MXTPU_FLOPCHECK_TINY_COUNT", 4096)


def min_mfu():
    """``predicted-mfu`` floor (``MXTPU_FLOPCHECK_MIN_MFU``, default 0.0
    = disabled — the zoo's tiny programs are memory-bound by
    construction; arm per-deploy for the flagship LM)."""
    return env_float("MXTPU_FLOPCHECK_MIN_MFU", 0.0)


def tolerance():
    """Baseline drift band (``MXTPU_FLOPCHECK_TOL``, default 0.1)."""
    return env_float("MXTPU_FLOPCHECK_TOL", 0.1)


# ---------------------------------------------------------------------------
# the scheduled-HLO kernel parser
# ---------------------------------------------------------------------------

# one instruction, tuple-typed results included (fusions returning
# several buffers, while carries) — commscheck's _TYPE_PAT
_KINSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<instr>[\w.\-]+)\s*=\s*"
    r"(?P<type>" + _TYPE_PAT + r")\s+"
    r"(?P<opcode>[\w\-]+)\((?P<rest>.*)$")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
# an operand's inline type: `f32[8,64]{1,0} %name` — anchored on the
# following %ref so shape-shaped noise elsewhere on the line never counts
_OPERAND_TYPE_RE = re.compile(
    r"([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{[^}]*\})?\s+%")
_CALLS_RE = re.compile(r"calls=%(?P<callee>[\w.\-]+)")
_TO_APPLY_RE = re.compile(r"to_apply=%(?P<callee>[\w.\-]+)")
_BODY_RE = re.compile(r"body=%(?P<body>[\w.\-]+)")
_BRANCHES_RE = re.compile(
    r"(?:true_computation=%([\w.\-]+)|false_computation=%([\w.\-]+)"
    r"|branch_computations=\{([^}]*)\})")
_BRANCH_NAME_RE = re.compile(r"%([\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,\s]*)\}")
_DIM_LABELS_RE = re.compile(r"dim_labels=([a-z0-9?]+)_([a-z0-9?]+)->")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')

#: opcodes that never become kernels: control flow (their bodies are
#: inventoried as their own execution contexts), data views, and the
#: collectives (priced by commscheck's wire model — counting them here
#: would double-bill the step time)
_NONKERNEL_OPCODES = frozenset(
    {"parameter", "constant", "while", "conditional", "call",
     "after-all", "add-dependency", "copy-start", "copy-done"}
    | set(_VIEW_OPCODES)
    | set(COLLECTIVE_KINDS)
    | {k + "-start" for k in COLLECTIVE_KINDS}
    | {k + "-done" for k in COLLECTIVE_KINDS})

#: pure data-motion opcodes: a kernel (or a fusion of nothing else) made
#: of these computes nothing — the ``layout-copy`` signature
_LAYOUT_OPCODES = frozenset({"copy", "transpose", "bitcast", "reshape"})

#: a while loop with more trips than this is an EXPANSION loop (the CPU
#: backend lowers select-and-scatter / pool backprop as scalar loops
#: with one trip per output element) — not a dispatch-per-trip scan
#: body. It is collapsed into ONE merged kernel (body totals x trips)
#: instead of multiplying the inventory into millions of "executions";
#: real K-step scans and ring schedules sit far below this
_EXPANSION_TRIPS = 64


def _dims(dims_str):
    return [int(d) for d in dims_str.split(",") if d.strip()]


def _type_elems(type_str):
    """Total element count of a (possibly tuple) HLO type string."""
    total = 0
    for _dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in _dims(dims):
            n *= d
        total += n
    return total


def _type_bytes(type_str):
    return sum(_shape_bytes(dt, dims)
               for dt, dims in _SHAPE_RE.findall(type_str))


def _operand_head(rest):
    """The operand segment of an instruction's tail — everything before
    the metadata block, so source paths / op names can never be read as
    shapes."""
    idx = rest.find("metadata=")
    return rest if idx < 0 else rest[:idx]


def _parse_computations(hlo_text):
    """name -> [instr dict] for every computation, plus the entry name.
    An instr dict carries instruction/type/opcode/rest plus op path and
    source provenance pulled from its metadata."""
    comps, entry_name, cur = {}, None, None
    frames = parse_stack_frames(hlo_text)
    for line in hlo_text.splitlines():
        cm = _COMP_RE.match(line)
        if cm:
            cur = cm.group("name")
            comps[cur] = []
            if cm.group("entry"):
                entry_name = cur
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        im = _KINSTR_RE.match(line)
        if not im:
            continue
        op = _OPNAME_RE.search(line)
        comps[cur].append({
            "instr": im.group("instr"),
            "type": im.group("type"),
            "opcode": im.group("opcode"),
            "rest": im.group("rest"),
            "op_path": _unescape(op.group(1)) if op else None,
            "provenance": instr_provenance(line, frames),
        })
    return comps, entry_name


def _estimate_flops(ins, comps, _depth=0):
    """Structural FLOP estimate for one instruction: dots and convs by
    their contraction algebra, fusions by their callee's sum, everything
    else one op per output element. These are RELATIVE weights — the
    report normalizes their sum against ``cost_analysis()['flops']``, so
    only the apportioning between kernels rides on this model."""
    opcode = ins["opcode"]
    if (opcode in ("parameter", "constant") or opcode in _VIEW_OPCODES
            or _depth > 8):
        return 0.0
    head = _operand_head(ins["rest"])
    if opcode in ("fusion", "call"):
        m = _CALLS_RE.search(ins["rest"]) or _TO_APPLY_RE.search(ins["rest"])
        if m:
            return sum(_estimate_flops(i, comps, _depth + 1)
                       for i in comps.get(m.group("callee"), ()))
        return float(_type_elems(ins["type"]))
    if opcode == "dot":
        out = _type_elems(ins["type"])
        ops = _OPERAND_TYPE_RE.findall(head)
        cm = _CONTRACT_RE.search(ins["rest"])
        contracted = 1
        if ops and cm:
            lhs_dims = _dims(ops[0][1])
            for idx in _dims(cm.group(1)):
                if idx < len(lhs_dims):
                    contracted *= lhs_dims[idx]
        return 2.0 * out * contracted
    if opcode == "convolution":
        out = _type_elems(ins["type"])
        ops = _OPERAND_TYPE_RE.findall(head)
        if len(ops) >= 2:
            rhs_dims = _dims(ops[1][1])
            rhs_elems = 1
            for d in rhs_dims:
                rhs_elems *= d
            out_ch = 1
            dl = _DIM_LABELS_RE.search(ins["rest"])
            if dl:
                o_idx = dl.group(2).find("o")
                if 0 <= o_idx < len(rhs_dims):
                    out_ch = rhs_dims[o_idx] or 1
            # 2 x output x (kernel-volume x in-channels-per-group): the
            # rhs carries exactly that product out_ch times, so /out_ch
            # absorbs feature groups too
            return 2.0 * out * rhs_elems / max(out_ch, 1)
        return 2.0 * out
    if opcode in ("reduce", "reduce-window", "sort", "scatter",
                  "select-and-scatter"):
        ops = _OPERAND_TYPE_RE.findall(head)
        if ops:
            n = 1
            for d in _dims(ops[0][1]):
                n *= d
            return float(max(n, _type_elems(ins["type"])))
    return float(_type_elems(ins["type"]))


def _estimate_bytes(ins):
    """HBM traffic estimate: operand bytes read + result bytes written
    (inline operand types x memcheck's dtype widths). Alias-aware: a
    dynamic-slice reads only the slice (not its operand), a
    dynamic-update-slice touches only the update window (the rest of its
    full-shaped "result" aliases the operand in place), and an explicit
    ``output_to_operand_aliasing`` counts the shared buffer once."""
    opcode = ins["opcode"]
    head = _operand_head(ins["rest"])
    result = _type_bytes(ins["type"])
    if opcode in ("dynamic-slice", "gather"):
        return 2 * result
    if opcode == "dynamic-update-slice":
        ops = _OPERAND_TYPE_RE.findall(head)
        upd = _shape_bytes(*ops[1]) if len(ops) >= 2 else result
        return 2 * upd
    operand = sum(_shape_bytes(dt, dims)
                  for dt, dims in _OPERAND_TYPE_RE.findall(head))
    if "output_to_operand_aliasing=" in ins["rest"]:
        return max(operand, result)
    return operand + result


def _comp_totals(cname, comps, _depth=0):
    """(flops, bytes) of ONE sequential execution of a computation,
    nested control flow included (inner whiles multiply by their known
    trips) — the merged-kernel cost of a collapsed expansion loop."""
    flops = nbytes = 0.0
    if _depth > 8:
        return flops, nbytes
    for ins in comps.get(cname, ()):
        opcode = ins["opcode"]
        if opcode == "while":
            bm = _BODY_RE.search(ins["rest"])
            tm = _TRIP_RE.search(ins["rest"])
            trips = int(tm.group(1)) if tm else 1
            if bm:
                f, b = _comp_totals(bm.group("body"), comps, _depth + 1)
                flops += f * trips
                nbytes += b * trips
            continue
        if opcode in ("conditional", "call"):
            for m in (_CALLS_RE.search(ins["rest"]),
                      _TO_APPLY_RE.search(ins["rest"])):
                if m:
                    f, b = _comp_totals(m.group("callee"), comps,
                                        _depth + 1)
                    flops += f
                    nbytes += b
            for groups in _BRANCHES_RE.findall(ins["rest"]):
                for g in groups:
                    if not g:
                        continue
                    for bname in (_BRANCH_NAME_RE.findall(g) or [g]):
                        f, b = _comp_totals(bname, comps, _depth + 1)
                        flops += f
                        nbytes += b
            continue
        if opcode in _NONKERNEL_OPCODES:
            continue
        flops += _estimate_flops(ins, comps)
        nbytes += _estimate_bytes(ins)
    return flops, nbytes


def _is_layout(ins, comps):
    """Pure data motion? True for copy/transpose kernels and for fusions
    whose callee computes nothing but layout ops."""
    opcode = ins["opcode"]
    if opcode in ("copy", "transpose"):
        return True
    if opcode == "fusion":
        m = _CALLS_RE.search(ins["rest"])
        body = comps.get(m.group("callee"), ()) if m else ()
        real = [i for i in body
                if i["opcode"] not in ("parameter", "constant")
                and i["opcode"] not in _VIEW_OPCODES]
        return bool(real) and all(i["opcode"] in _LAYOUT_OPCODES
                                  for i in real)
    return False


class KernelEntry(object):
    """One kernel launch in the compiled program's schedule: a fusion,
    dot, convolution, reduce, copy ... with its apportioned FLOPs, HBM
    traffic, roofline classification and provenance. ``multiplier`` is
    the per-dispatch execution count (a while-body kernel runs K times);
    ``seconds`` is the roofline time for ONE execution —
    ``max(flops/peak, bytes/bw)``."""

    __slots__ = ("instruction", "opcode", "flops", "bytes", "in_loop",
                 "multiplier", "is_layout", "op_path", "provenance",
                 "seconds", "intensity", "bound", "norm_flops")

    def __init__(self, instruction, opcode, flops, bytes_, in_loop=False,
                 multiplier=1, is_layout=False, op_path=None,
                 provenance=None, norm_flops=None):
        self.instruction = instruction
        self.opcode = opcode
        self.flops = float(flops)
        self.bytes = int(bytes_)
        #: the weight this kernel contributes to the cost-analysis
        #: normalization basis. Defaults to ``flops``; a collapsed
        #: expansion loop passes its ONE-trip body estimate instead —
        #: the XLA cost model counts a while body once, so normalizing
        #: on the trip-multiplied figure would let one scalar loop steal
        #: the whole program's FLOP budget
        self.norm_flops = (self.flops if norm_flops is None
                           else float(norm_flops))
        self.in_loop = bool(in_loop)
        self.multiplier = max(1, int(multiplier))
        self.is_layout = bool(is_layout)
        self.op_path = op_path
        self.provenance = provenance
        # roofline fields, priced by the report against its device spec
        self.seconds = 0.0
        self.intensity = 0.0
        self.bound = "memory"

    def price(self, peak_flops_per_s, hbm_bytes_per_s):
        self.intensity = (self.flops / self.bytes) if self.bytes else 0.0
        ridge = peak_flops_per_s / hbm_bytes_per_s
        self.bound = "compute" if self.intensity >= ridge else "memory"
        self.seconds = max(self.flops / peak_flops_per_s,
                           self.bytes / hbm_bytes_per_s)

    @property
    def total_seconds(self):
        return self.seconds * self.multiplier

    def as_dict(self):
        return {
            "instruction": self.instruction,
            "opcode": self.opcode,
            "flops": self.flops,
            "bytes": self.bytes,
            "intensity": self.intensity,
            "bound": self.bound,
            "in_loop": self.in_loop,
            "multiplier": self.multiplier,
            "is_layout": self.is_layout,
            "predicted_us": self.seconds * 1e6,
            "op_path": self.op_path,
            "provenance": self.provenance,
        }

    def format(self):
        where = self.op_path or self.instruction
        if self.provenance:
            where += " @ " + self.provenance
        mult = " x%d" % self.multiplier if self.multiplier > 1 else ""
        return ("%-7s %8.2fus %10s %8.1f FLOP/B %-14s%s %s"
                % (self.bound, self.seconds * 1e6, _fmt_bytes(self.bytes),
                   self.intensity, self.opcode, mult, where))

    def __repr__(self):
        return "KernelEntry(%s)" % self.format()


def parse_kernels(hlo_text, loop_trips=1):
    """Walk the scheduled HLO into the kernel inventory: the entry
    computation's top-level instructions plus every while body (in-loop,
    multiplied by its known trip count or ``loop_trips``) and every
    conditional branch. Parameters, constants, views, control flow and
    collectives are not kernels. FLOPs here are the RAW structural
    estimates — :func:`analyze_compiled` apportions them against the XLA
    cost model."""
    comps, entry = _parse_computations(hlo_text)
    if entry is None:
        return []
    kernels = []
    seen = set()
    # (computation, in_loop, multiplier) execution contexts, discovered
    # by walking control flow from the entry
    work = [(entry, False, 1)]
    while work:
        cname, in_loop, mult = work.pop(0)
        if cname in seen:
            continue
        seen.add(cname)
        for ins in comps.get(cname, ()):
            opcode = ins["opcode"]
            if opcode == "while":
                bm = _BODY_RE.search(ins["rest"])
                if bm:
                    trips = loop_trips
                    tm = _TRIP_RE.search(ins["rest"])
                    if tm:
                        trips = int(tm.group(1))
                    trips = max(1, trips)
                    if trips > _EXPANSION_TRIPS:
                        # a scalar expansion loop (CPU pool backprop),
                        # not a per-trip dispatch schedule: ONE merged
                        # kernel. FLOPs are the body total x trips, but
                        # bytes are ONE streaming pass over the
                        # loop-carried state (read + write the tuple):
                        # each scalar iteration's body references the
                        # full arrays it slices from, so body-bytes x
                        # trips would bill the whole array once per
                        # element — petabytes of fiction
                        f, _ = _comp_totals(bm.group("body"), comps)
                        b = 2 * _type_bytes(ins["type"])
                        kernels.append(KernelEntry(
                            ins["instr"], "while", f * trips, b,
                            in_loop=in_loop, multiplier=mult,
                            op_path=ins["op_path"],
                            provenance=ins["provenance"],
                            norm_flops=f))
                    else:
                        work.append((bm.group("body"), True,
                                     mult * trips))
                continue
            if opcode == "conditional":
                for groups in _BRANCHES_RE.findall(ins["rest"]):
                    for g in groups:
                        if not g:
                            continue
                        # group 3 is a brace list of %names; 1/2 are bare
                        for bname in (_BRANCH_NAME_RE.findall(g) or [g]):
                            work.append((bname, in_loop, mult))
                continue
            if opcode == "call":
                tm = _TO_APPLY_RE.search(ins["rest"])
                if tm:
                    work.append((tm.group("callee"), in_loop, mult))
                continue
            if opcode in _NONKERNEL_OPCODES:
                continue
            kernels.append(KernelEntry(
                ins["instr"], opcode,
                _estimate_flops(ins, comps),
                _estimate_bytes(ins),
                in_loop=in_loop, multiplier=mult,
                is_layout=_is_layout(ins, comps),
                op_path=ins["op_path"], provenance=ins["provenance"]))
    return kernels


# ---------------------------------------------------------------------------
# the report + roofline
# ---------------------------------------------------------------------------

class RooflineReport(object):
    """Static compute/memory profile of ONE compiled program.

    ``kernel_count`` is the PER-DISPATCH kernel execution count (in-loop
    kernels multiplied by their trips — the same semantics as
    commscheck's ``collective_count``); ``predicted_step_seconds`` is
    the zero-overlap roofline bound for one dispatch: every kernel's
    ``max(flops/peak, bytes/bw)`` plus the collective wire time from the
    embedded :class:`~mxnet_tpu.commscheck.CommsReport`. The baseline
    gate pins kernel count / predicted step ms / predicted MFU /
    top-hotspot identity."""

    __slots__ = ("program", "platform", "kernels", "loop_trips", "flops",
                 "comms", "peak_flops_per_s", "hbm_bytes_per_s",
                 "peak_source", "hlo_unavailable")

    def __init__(self, program, platform, kernels, loop_trips=1,
                 flops=None, comms=None, peak_flops_per_s=None,
                 hbm_bytes_per_s=None, peak_source=None,
                 hlo_unavailable=False):
        self.program = program
        self.platform = platform
        self.kernels = list(kernels)
        self.loop_trips = max(1, int(loop_trips))
        self.flops = None if flops is None else float(flops)
        self.comms = comms
        if peak_flops_per_s is None or hbm_bytes_per_s is None:
            spec, source = devspec.lookup()
            peak_flops_per_s = (spec.peak_flops_per_s
                                if peak_flops_per_s is None
                                else peak_flops_per_s)
            hbm_bytes_per_s = (spec.hbm_bytes_per_s
                               if hbm_bytes_per_s is None
                               else hbm_bytes_per_s)
            peak_source = source if peak_source is None else peak_source
        self.peak_flops_per_s = float(peak_flops_per_s)
        self.hbm_bytes_per_s = float(hbm_bytes_per_s)
        self.peak_source = peak_source or "spec"
        #: the executable's HLO text could not be read: the (empty)
        #: inventory is ABSENCE OF EVIDENCE, not a cheap program — the
        #: drift gate fails such programs and the roofline claims nothing
        self.hlo_unavailable = bool(hlo_unavailable)
        # apportion the structural estimates against the XLA cost model
        # (which counts a while body ONCE — so normalize on the
        # once-each sum, then let the multipliers scale per-dispatch)
        raw = sum(k.norm_flops for k in self.kernels)
        if self.flops and raw > 0:
            scale = self.flops / raw
            for k in self.kernels:
                k.flops *= scale
        for k in self.kernels:
            k.price(self.peak_flops_per_s, self.hbm_bytes_per_s)
        self.kernels.sort(key=lambda k: k.total_seconds, reverse=True)

    @property
    def ridge_intensity(self):
        return self.peak_flops_per_s / self.hbm_bytes_per_s

    @property
    def kernel_count(self):
        return sum(k.multiplier for k in self.kernels)

    @property
    def flops_per_dispatch(self):
        return sum(k.flops * k.multiplier for k in self.kernels)

    @property
    def bytes_per_dispatch(self):
        return sum(k.bytes * k.multiplier for k in self.kernels)

    @property
    def kernel_seconds(self):
        return sum(k.total_seconds for k in self.kernels)

    @property
    def comm_seconds(self):
        """Per-dispatch collective wire time (commscheck's per-iteration
        model x the trip count); 0 for an unsharded program."""
        if self.comms is None:
            return 0.0
        return self.comms.comm_seconds * self.loop_trips

    @property
    def predicted_step_seconds(self):
        return self.kernel_seconds + self.comm_seconds

    @property
    def predicted_step_ms(self):
        return self.predicted_step_seconds * 1e3

    @property
    def predicted_mfu(self):
        """Dispatch FLOPs over predicted time x peak — what the roofline
        says utilization CAN be; None without evidence."""
        if self.hlo_unavailable or not self.kernels:
            return None
        t = self.predicted_step_seconds
        if t <= 0:
            return None
        return self.flops_per_dispatch / (t * self.peak_flops_per_s)

    @property
    def top_hotspot(self):
        """op path (or instruction name) of the kernel holding the most
        predicted step time — the identity the baseline pins."""
        if not self.kernels:
            return None
        k = self.kernels[0]
        return k.op_path or k.instruction

    def hotspots(self, top=10, memory_only=False):
        """The Pallas shopping list: kernels ranked by held step time
        (``memory_only`` keeps just the below-ridge ones — the hand-
        kernel candidates)."""
        ks = [k for k in self.kernels
              if not memory_only or k.bound == "memory"]
        return ks[:top]

    def breakdown(self, top=6):
        return [k.format() for k in self.kernels[:top]]

    def as_dict(self):
        mfu = self.predicted_mfu
        return {
            "program": self.program,
            "platform": self.platform,
            "hlo_unavailable": self.hlo_unavailable,
            "kernel_count": self.kernel_count,
            "flops_per_dispatch": self.flops_per_dispatch,
            "bytes_per_dispatch": self.bytes_per_dispatch,
            "ridge_intensity": self.ridge_intensity,
            "peak_source": self.peak_source,
            "loop_trips": self.loop_trips,
            "kernel_seconds": self.kernel_seconds,
            "comm_seconds": self.comm_seconds,
            "predicted_step_ms": self.predicted_step_ms,
            "predicted_mfu": None if mfu is None else round(mfu, 6),
            "top_hotspot": self.top_hotspot,
            "kernels": [k.as_dict() for k in self.kernels],
        }

    def format(self):
        mfu = self.predicted_mfu
        return ("%s: %d kernel(s)/dispatch, predicted %.3f ms, MFU %s"
                % (self.program, self.kernel_count, self.predicted_step_ms,
                   "?" if mfu is None else "%.4f" % mfu))

    def __repr__(self):
        return "RooflineReport(%s)" % self.format()


def analyze_compiled(compiled, name, mesh=None, loop_trips=1):
    """Build a :class:`RooflineReport` from an ALREADY-compiled program
    (``jax.stages.Compiled``). Never executes anything; ONE HLO text
    read feeds both the kernel walk and the embedded collective
    inventory."""
    import jax
    text_ok = True
    try:
        hlo_text = compiled.as_text()
        if not hlo_text:
            text_ok = False
    except Exception as exc:
        import logging
        logging.warning("flopcheck: %s: compiled HLO text unavailable "
                        "(%r) — the inventory is empty for lack of "
                        "EVIDENCE, not because the program is free",
                        name, exc)
        hlo_text = ""
        text_ok = False
    flops = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        if ca:
            flops = float(ca.get("flops", 0.0)) or None
    except Exception:
        flops = None
    platform = jax.devices()[0].platform
    kernels = parse_kernels(hlo_text, loop_trips=loop_trips)
    comms = None
    entries = parse_collectives(hlo_text, mesh=mesh, loop_trips=loop_trips)
    if entries:
        n_dev = int(mesh.devices.size) if mesh is not None else 1
        comms = CommsReport(name, platform, n_dev, entries,
                            loop_trips=loop_trips, flops=flops,
                            hlo_unavailable=not text_ok)
    return RooflineReport(name, platform, kernels,
                          loop_trips=loop_trips, flops=flops, comms=comms,
                          hlo_unavailable=not text_ok)


def analyze(fn, args=(), kwargs=None, name=None, mesh=None, loop_trips=1):
    """Compile ``fn`` (never executed — args may be
    ``ShapeDtypeStruct``s; sharded programs must carry real shardings)
    and return its :class:`RooflineReport`."""
    import jax
    kwargs = dict(kwargs or {})
    if name is None:
        name = getattr(fn, "__name__", None) or repr(fn)
    if mesh is None:
        mesh = _infer_mesh(args, kwargs)
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args, **kwargs).compile()
    return analyze_compiled(compiled, name, mesh=mesh,
                            loop_trips=loop_trips)


# ---------------------------------------------------------------------------
# lints
# ---------------------------------------------------------------------------

def lint_report(report, hot_threshold=None, hot_floor=None,
                layout_threshold=None, layout_share=None,
                tiny_floor_us=None, tiny_threshold=None, mfu_floor=None):
    """The four roofline lints over one :class:`RooflineReport`:
    ``memory-bound-hot``, ``layout-copy``, ``tiny-dispatch``,
    ``predicted-mfu``. Returns findings with suppressions applied (like
    ``tracecheck.check_program``)."""
    hot_threshold = hot_frac() if hot_threshold is None \
        else float(hot_threshold)
    hot_floor = hot_bytes() if hot_floor is None else int(hot_floor)
    layout_threshold = layout_bytes() if layout_threshold is None \
        else int(layout_threshold)
    layout_share = layout_frac() if layout_share is None \
        else float(layout_share)
    tiny_floor_us = tiny_us() if tiny_floor_us is None \
        else float(tiny_floor_us)
    tiny_threshold = tiny_count() if tiny_threshold is None \
        else int(tiny_threshold)
    mfu_floor = min_mfu() if mfu_floor is None else float(mfu_floor)
    name = report.program
    findings = []
    step = report.predicted_step_seconds
    total_bytes = report.bytes_per_dispatch

    for k in report.kernels:
        frac = (k.total_seconds / step) if step > 0 else 0.0
        if (k.bound == "memory" and not k.is_layout
                and frac >= hot_threshold
                and k.bytes * k.multiplier >= hot_floor):
            findings.append(Finding(
                "memory-bound-hot", name,
                "kernel %r holds %.0f%% of the predicted step time "
                "(%.2fus of %.2fus) at intensity %.1f FLOP/B — below "
                "the ridge %.1f, so it is waiting on HBM (%s moved per "
                "dispatch); this is the Pallas-candidate signature: a "
                "hand kernel that keeps the working set in VMEM wins "
                "(threshold MXTPU_FLOPCHECK_HOT_FRAC=%.2f)"
                % (k.instruction, 100.0 * frac, k.total_seconds * 1e6,
                   step * 1e6, k.intensity, report.ridge_intensity,
                   _fmt_bytes(k.bytes * k.multiplier), hot_threshold),
                op_path=k.op_path or k.instruction,
                provenance=k.provenance))
        kb = k.bytes * k.multiplier
        byte_share = (kb / float(total_bytes)) if total_bytes > 0 else 0.0
        if (k.is_layout and kb > layout_threshold
                and byte_share >= layout_share):
            findings.append(Finding(
                "layout-copy", name,
                "kernel %r is pure data motion (%s) moving %s per "
                "dispatch — %.0f%% of the program's HBM traffic "
                "(> %.0f%%, MXTPU_FLOPCHECK_LAYOUT_FRAC) spent "
                "re-laying-out memory, zero FLOPs; fix the layout that "
                "forced the %s"
                % (k.instruction, k.opcode, _fmt_bytes(kb),
                   100.0 * byte_share, 100.0 * layout_share, k.opcode),
                op_path=k.op_path or k.instruction,
                provenance=k.provenance))

    tiny = [k for k in report.kernels
            if k.seconds * 1e6 < tiny_floor_us]
    tiny_execs = sum(k.multiplier for k in tiny)
    if tiny_execs > tiny_threshold:
        worst = tiny[0] if tiny else report.kernels[0]
        findings.append(Finding(
            "tiny-dispatch", name,
            "%d kernel execution(s) per dispatch each predicted under "
            "%.1fus (> %d, MXTPU_FLOPCHECK_TINY_COUNT) — dispatch "
            "overhead dominates compute: a fusion regression shattered "
            "the step (or the program genuinely needs fusing)"
            % (tiny_execs, tiny_floor_us, tiny_threshold),
            op_path=worst.op_path or worst.instruction,
            provenance=worst.provenance))

    mfu = report.predicted_mfu
    if mfu_floor > 0 and mfu is not None and mfu < mfu_floor:
        k = report.kernels[0]
        findings.append(Finding(
            "predicted-mfu", name,
            "predicted MFU %.4f is below the floor %.2f "
            "(MXTPU_FLOPCHECK_MIN_MFU): the roofline says the program "
            "CANNOT reach the target utilization — %.3f ms predicted "
            "step time at %s peak (%s). Inventory:\n  %s"
            % (mfu, mfu_floor, report.predicted_step_ms,
               "%.1f TFLOP/s" % (report.peak_flops_per_s / 1e12),
               report.peak_source, "\n  ".join(report.breakdown())),
            op_path=k.op_path or k.instruction, provenance=k.provenance))

    for f in findings:
        f.suppressed = _is_suppressed(f)
    return findings


def check_program(fn, args=(), kwargs=None, name=None, mesh=None,
                  loop_trips=1, **lint_kw):
    """Analyze + lint ONE program; returns ``(findings, report)``."""
    report = analyze(fn, args, kwargs=kwargs, name=name, mesh=mesh,
                     loop_trips=loop_trips)
    return lint_report(report, **lint_kw), report


def hotspot_report(fn, args=(), kwargs=None, name=None, mesh=None,
                   loop_trips=1, top=10, memory_only=True):
    """The Pallas tier's shopping list for ONE program: analyze and
    return the ranked hotspot entries as dicts (exposed to the autotune
    search driver as ``mxnet_tpu.autotune.hotspot_report``)."""
    report = analyze(fn, args, kwargs=kwargs, name=name, mesh=mesh,
                     loop_trips=loop_trips)
    step = report.predicted_step_seconds
    out = []
    for k in report.hotspots(top=top, memory_only=memory_only):
        d = k.as_dict()
        d["step_time_frac"] = ((k.total_seconds / step)
                               if step > 0 else 0.0)
        out.append(d)
    return out


# ---------------------------------------------------------------------------
# runtime hook (MXTPU_FLOPCHECK / engine.flopcheck_mode)
# ---------------------------------------------------------------------------

#: program names already audited by the dispatch hook — the audit pays
#: one extra compile, so it runs once per compiled program per process
_AUDITED = set()


def maybe_audit_dispatch(name, jitfn, call_args, loop_trips=1, mesh=None):
    """One-time roofline audit of a freshly-compiled dispatch program
    (``TrainStep`` calls this at first registration — single-device
    programs too, a fusion regression needs no mesh to hurt): under
    ``MXTPU_FLOPCHECK=warn`` unsuppressed findings are logged, under
    ``error`` they raise. Costs one extra compile; ``off`` (the default)
    skips entirely. Call arguments are reduced to ``ShapeDtypeStruct``s
    first, so already-donated buffers are never touched."""
    from . import engine
    mode = engine.flopcheck_mode()
    if mode == "off" or name in _AUDITED:
        return None
    _AUDITED.add(name)
    # knobs resolve BEFORE the analyzer guard: a malformed env var must
    # propagate as MXNetError instead of silently disarming the gate the
    # operator just configured (memcheck's load-audit hardening)
    kw = dict(hot_threshold=hot_frac(), hot_floor=hot_bytes(),
              layout_threshold=layout_bytes(), layout_share=layout_frac(),
              tiny_floor_us=tiny_us(), tiny_threshold=tiny_count(),
              mfu_floor=min_mfu())
    try:
        findings, report = check_program(
            jitfn, struct_args(tuple(call_args)), name=name, mesh=mesh,
            loop_trips=loop_trips, **kw)
    except Exception as exc:
        import logging
        logging.warning("flopcheck: dispatch audit of %s failed (%r) — "
                        "skipping", name, exc)
        return None
    if report.hlo_unavailable:
        # the armed gate must not pass vacuously: no HLO text means NO
        # audit ran (same contract as the CLI / baseline consumers)
        msg = ("flopcheck: compiled HLO text unavailable for %s — the "
               "MXTPU_FLOPCHECK audit could not run" % name)
        if mode == "error":
            raise MXNetError(msg)
        import logging
        logging.warning(msg)
        return report
    bad = unsuppressed(findings)
    if bad:
        msg = ("flopcheck: %d finding(s) on program %s "
               "(MXTPU_FLOPCHECK):\n%s"
               % (len(bad), name, "\n".join(f.format() for f in bad)))
        if mode == "error":
            raise MXNetError(msg)
        import logging
        logging.warning(msg)
    return report


# ---------------------------------------------------------------------------
# compile-once program sets (zoo + sharded) — ONE compile feeds all
# three analyzers (memcheck + commscheck + flopcheck)
# ---------------------------------------------------------------------------

def compiled_zoo_programs(names=None, k=2, guard=True, log=None):
    """Compile every zoo step program ONCE and yield
    ``(name, compiled, args, loop_trips, mesh)`` — the shared substrate
    of the combined CI gate (one compile per program instead of one per
    analyzer). Program names and shapes come from
    ``tracecheck.train_step_programs``, THE shared recipe, so the
    analyzers can never drift apart on what training dispatches."""
    from .tracecheck import train_step_programs, zoo_train_step
    names = list(names) if names else sorted(ZOO)
    for mname in names:
        if mname not in ZOO:
            raise MXNetError("flopcheck: unknown zoo model %r (have %s)"
                             % (mname, ", ".join(sorted(ZOO))))
        if log:
            log("flopcheck: compiling %s ..." % mname)
        ts, data_shapes, label_shapes = zoo_train_step(mname)
        for pname, jitfn, pargs in train_step_programs(
                ts, data_shapes, label_shapes, k=k, guard=guard,
                name=mname):
            trips = k if "/scan[" in pname or "-scan[" in pname else 1
            compiled = jitfn.lower(*pargs).compile()
            yield pname, compiled, pargs, trips, ts.mesh


def compiled_sharded_programs(n_devices=8, k=2, log=None):
    """Compile the sharded gate set (``commscheck.sharded_programs``)
    ONCE each; yields ``(name, compiled, args, loop_trips, mesh)``."""
    import contextlib
    from .commscheck import sharded_programs
    from .parallel.mesh import MeshScope
    for name, jitfn, args, trips, mesh, scope in sharded_programs(
            n_devices=n_devices, k=k):
        if log:
            log("flopcheck: compiling %s ..." % name)
        ambient = (MeshScope(scope) if scope is not None
                   else contextlib.nullcontext())
        with ambient:
            compiled = jitfn.lower(*args).compile()
        yield name, compiled, args, trips, mesh


def check_train_step(ts, data_shapes, label_shapes, k=2, guard=True,
                     name=None, **lint_kw):
    """Roofline-audit a :class:`~mxnet_tpu.train_step.TrainStep`'s full
    program set (``tracecheck.train_step_programs``). Returns
    ``(findings, reports)``."""
    from .tracecheck import train_step_programs
    name = name or "TrainStep(%s)" % ts.symbol.name
    findings, reports = [], {}
    for pname, jitfn, pargs in train_step_programs(
            ts, data_shapes, label_shapes, k=k, guard=guard, name=name):
        trips = k if "/scan[" in pname or "-scan[" in pname else 1
        fs, rep = check_program(jitfn, pargs, name=pname, mesh=ts.mesh,
                                loop_trips=trips, **lint_kw)
        findings += fs
        reports[pname] = rep
    return findings, reports


def check_zoo(names=None, k=2, guard=True, log=None, programs=None,
              **lint_kw):
    """Roofline-audit the model zoo's step programs (same configs as
    ``tracecheck.ZOO``); returns ``(findings, reports)``. Pass
    ``programs`` (an iterable from :func:`compiled_zoo_programs`) to
    reuse already-compiled executables — the combined gate path."""
    findings, reports = [], {}
    progs = programs if programs is not None else compiled_zoo_programs(
        names=names, k=k, guard=guard, log=log)
    for pname, compiled, _pargs, trips, mesh in progs:
        rep = analyze_compiled(compiled, pname, mesh=mesh,
                               loop_trips=trips)
        findings += lint_report(rep, **lint_kw)
        reports[pname] = rep
    return findings, reports


def check_sharded(n_devices=8, k=2, log=None, programs=None, **lint_kw):
    """Roofline-audit the sharded gate program set; returns
    ``(findings, reports)``."""
    findings, reports = [], {}
    progs = programs if programs is not None else \
        compiled_sharded_programs(n_devices=n_devices, k=k, log=log)
    for pname, compiled, _pargs, trips, mesh in progs:
        rep = analyze_compiled(compiled, pname, mesh=mesh,
                               loop_trips=trips)
        findings += lint_report(rep, **lint_kw)
        reports[pname] = rep
    return findings, reports


# ---------------------------------------------------------------------------
# the baseline drift gate (ci/flopcheck.sh)
# ---------------------------------------------------------------------------

#: metrics the baseline pins per program: kernel count (growth = a
#: fusion shattered), predicted step ms (growth = the roofline got
#: worse), predicted MFU (drop = ditto) and the top-hotspot identity
#: (change = the optimization target moved — a note, not a failure)
_BASELINE_METRICS = ("kernel_count", "predicted_step_ms", "predicted_mfu")


def write_baseline(reports, path, tol=None):
    """Write the committed baseline, keyed by platform (a CPU baseline
    must not gate a TPU run). Refuses evidence-free reports — committing
    a fabricated zero for a program whose HLO text could not be read
    would pin the drift gate on nothing."""
    import jax
    from .model import atomic_write_bytes
    blind = sorted(n for n, r in reports.items()
                   if getattr(r, "hlo_unavailable", False))
    if blind:
        raise MXNetError(
            "write_baseline: compiled HLO text was unavailable for %s — "
            "their inventories are absence of evidence, not zeros; "
            "refusing to commit a fabricated baseline" % ", ".join(blind))
    data = {
        "platform": jax.devices()[0].platform,
        "tolerance": tolerance() if tol is None else float(tol),
        "programs": {
            name: {
                "kernel_count": int(rep.kernel_count),
                "predicted_step_ms": round(rep.predicted_step_ms, 6),
                "predicted_mfu": (None if rep.predicted_mfu is None
                                  else round(rep.predicted_mfu, 6)),
                "top_hotspot": rep.top_hotspot,
            }
            for name, rep in sorted(reports.items())},
    }
    atomic_write_bytes(path, (json.dumps(data, indent=2, sort_keys=True)
                              + "\n").encode())
    return data


def compare_baseline(reports, baseline, tol=None):
    """The drift gate: kernel count or predicted step time growing past
    the tolerance band fails WITH the kernel breakdown (op paths +
    source provenance); predicted MFU dropping past the band fails too.
    A program missing from the baseline fails (new programs are added
    deliberately), and a nonzero-pinned kernel count collapsing to zero
    fails — a parser gone blind must not read as a win. Shrinks, MFU
    gains, hotspot moves and stale entries are notes; a platform-
    mismatched baseline skips the gate with one note. Returns
    ``(failures, notes)``."""
    import jax
    if isinstance(baseline, str):
        with open(baseline) as f:
            baseline = json.load(f)
    if tol is None:
        # precedence: explicit arg > MXTPU_FLOPCHECK_TOL env > the
        # baseline's stored band > 0.1 (memcheck's hardened ordering)
        tol = env_float("MXTPU_FLOPCHECK_TOL",
                        float(baseline.get("tolerance", 0.1)))
    else:
        tol = float(tol)
    platform = jax.devices()[0].platform
    failures, notes = [], []
    if baseline.get("platform") != platform:
        notes.append(
            "flopcheck baseline was written on platform %r but this run "
            "is %r — skipping the drift gate (re-run --write-baseline on "
            "this platform to arm it)"
            % (baseline.get("platform"), platform))
        return failures, notes
    base_progs = dict(baseline.get("programs") or {})
    for name, rep in sorted(reports.items()):
        base = base_progs.pop(name, None)
        if getattr(rep, "hlo_unavailable", False):
            failures.append(
                "%s: compiled HLO text unavailable on this backend — the "
                "kernel inventory could not be audited; the drift gate "
                "refuses to pass on absence of evidence" % name)
            continue
        if base is None:
            failures.append(
                "%s: not in the baseline — a new program must be added "
                "deliberately (run `python -m mxnet_tpu.flopcheck --zoo "
                "--sharded --write-baseline FLOPCHECK_baseline.json` and "
                "commit the diff)" % name)
            continue
        breakdown = "\n  ".join(rep.breakdown()) or "(empty)"
        # kernel count: integer growth past the band = fusion regression
        b_count = int(base.get("kernel_count", 0))
        count = int(rep.kernel_count)
        if count > b_count + int(b_count * tol):
            failures.append(
                "%s: kernel_count grew %d -> %d (tolerance %.0f%%, "
                "MXTPU_FLOPCHECK_TOL) — a fusion shattered or new "
                "kernels appeared. Inventory:\n  %s"
                % (name, b_count, count, 100.0 * tol, breakdown))
        elif count == 0 and b_count > 0:
            failures.append(
                "%s: kernel_count collapsed %d -> 0 — either the program "
                "genuinely vanished (refresh the baseline deliberately) "
                "or the HLO parser went blind (an XLA text-format "
                "drift); the gate refuses to treat a total collapse as "
                "a win" % (name, b_count))
        elif count < b_count - int(b_count * tol) and b_count > 0:
            notes.append("%s: kernel_count shrank %d -> %d — nice; "
                         "refresh the baseline to lock the win in"
                         % (name, b_count, count))
        # predicted step time: float growth past the band
        b_ms = float(base.get("predicted_step_ms", 0.0))
        ms = rep.predicted_step_ms
        if b_ms > 0 and ms > b_ms * (1.0 + tol):
            failures.append(
                "%s: predicted_step_ms grew %.4f -> %.4f (tolerance "
                "%.0f%%, MXTPU_FLOPCHECK_TOL) — the roofline says this "
                "dispatch got slower. Inventory:\n  %s"
                % (name, b_ms, ms, 100.0 * tol, breakdown))
        elif b_ms > 0 and ms < b_ms * (1.0 - tol):
            notes.append("%s: predicted_step_ms shrank %.4f -> %.4f — "
                         "nice; refresh the baseline to lock the win in"
                         % (name, b_ms, ms))
        # predicted MFU: a drop past the band fails
        b_mfu = base.get("predicted_mfu")
        mfu = rep.predicted_mfu
        if b_mfu and mfu is not None:
            if mfu < float(b_mfu) * (1.0 - tol):
                failures.append(
                    "%s: predicted_mfu dropped %.4f -> %.4f (tolerance "
                    "%.0f%%, MXTPU_FLOPCHECK_TOL). Inventory:\n  %s"
                    % (name, float(b_mfu), mfu, 100.0 * tol, breakdown))
            elif mfu > float(b_mfu) * (1.0 + tol):
                notes.append("%s: predicted_mfu rose %.4f -> %.4f — "
                             "refresh the baseline to lock the win in"
                             % (name, float(b_mfu), mfu))
        b_hot = base.get("top_hotspot")
        if b_hot and rep.top_hotspot and b_hot != rep.top_hotspot:
            notes.append(
                "%s: top hotspot moved %r -> %r — the Pallas shopping "
                "list reordered; refresh the baseline if intended"
                % (name, b_hot, rep.top_hotspot))
    for name in sorted(base_progs):
        notes.append("baseline entry %r matches no audited program "
                     "(stale — refresh the baseline)" % name)
    return failures, notes


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def report_table(reports, out=None):
    import sys
    out = out or sys.stdout
    w = max([len(n) for n in reports] + [8])
    out.write("%-*s  %7s %10s %10s %8s %8s\n"
              % (w, "program", "kernels", "flops", "bytes", "pred-ms",
                 "mfu"))
    for name in sorted(reports):
        r = reports[name]
        mfu = r.predicted_mfu
        out.write("%-*s  %7d %10.3g %10s %8.4f %8s\n"
                  % (w, name, r.kernel_count, r.flops_per_dispatch,
                     _fmt_bytes(r.bytes_per_dispatch),
                     r.predicted_step_ms,
                     "?" if mfu is None else "%.4f" % mfu))


def hotspot_table(reports, top=10, memory_only=False, out=None):
    """Print the ranked hotspot table — the Pallas shopping list."""
    import sys
    out = out or sys.stdout
    for name in sorted(reports):
        r = reports[name]
        ks = r.hotspots(top=top, memory_only=memory_only)
        if not ks:
            continue
        step = r.predicted_step_seconds
        out.write("%s (predicted %.4f ms, ridge %.1f FLOP/B, %s):\n"
                  % (name, r.predicted_step_ms, r.ridge_intensity,
                     r.peak_source))
        for k in ks:
            frac = (k.total_seconds / step) if step > 0 else 0.0
            out.write("  %5.1f%%  %s\n" % (100.0 * frac, k.format()))


def _combined_memcheck(programs_by_model, baseline, tol):
    """The memcheck leg of the combined compile-once gate: reuse each
    zoo program's compiled executable for the HBM lints + per-model
    resident-set + baseline drift, exactly as ci/memcheck.sh runs them
    (the sharded set is NOT in MEMCHECK_baseline.json, so only zoo
    programs feed this leg)."""
    from . import memcheck
    findings, reports = [], {}
    for model, progs in sorted(programs_by_model.items()):
        model_reports = {}
        for pname, compiled, pargs, _trips, _mesh in progs:
            rep = memcheck.analyze_compiled(compiled, pname, args=pargs,
                                            donate_argnums=(0,))
            findings += memcheck.lint_report(rep)
            model_reports[pname] = rep
        findings += memcheck.lint_resident_set(
            model_reports.values(), "%s/resident-set" % model)
        reports.update(model_reports)
    failures, notes = memcheck.compare_baseline(reports, baseline, tol=tol)
    return findings, failures, notes


def _combined_commscheck(all_programs, baseline, tol):
    """The commscheck leg of the combined gate: collective lints +
    baseline drift from the SAME compiled executables."""
    from . import commscheck
    findings, reports = [], {}
    for pname, compiled, _pargs, trips, mesh in all_programs:
        rep = commscheck.analyze_compiled(compiled, pname, mesh=mesh,
                                          loop_trips=trips)
        findings += commscheck.lint_report(rep)
        reports[pname] = rep
    failures, notes = commscheck.compare_baseline(reports, baseline,
                                                  tol=tol)
    return findings, failures, notes


def main(argv=None):
    import argparse
    import sys
    import time
    from . import tracecheck as _tc
    p = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.flopcheck",
        description="Static per-kernel compute/memory roofline analyzer:"
                    " kernel inventory (FLOPs/bytes/intensity/bound),"
                    " predicted step time + MFU, hotspot ranking for the"
                    " Pallas tier, roofline lints, and the baseline drift"
                    " gate (docs/static_analysis.md \"Roofline lints\").")
    p.add_argument("--zoo", action="store_true",
                   help="analyze every shipped model's step/scan programs")
    p.add_argument("--models", default=None,
                   help="comma-separated zoo subset (implies --zoo)")
    p.add_argument("--sharded", action="store_true",
                   help="also analyze the sharded gate set (needs 8 "
                        "visible devices)")
    p.add_argument("--devices", type=int, default=8,
                   help="device count for --sharded (default 8)")
    p.add_argument("--k", type=int, default=2,
                   help="scan depth for the K-step programs (default 2)")
    p.add_argument("--no-guard", action="store_true",
                   help="skip the guarded program variants")
    p.add_argument("--hotspots", type=int, default=None, metavar="N",
                   help="print the top-N hotspot kernels per program "
                        "(the Pallas shopping list)")
    p.add_argument("--memory-bound", action="store_true",
                   help="restrict --hotspots to memory-bound kernels")
    p.add_argument("--hot-frac", type=float, default=None,
                   help="memory-bound-hot step-share threshold (default "
                        "MXTPU_FLOPCHECK_HOT_FRAC or 0.6)")
    p.add_argument("--min-mfu", type=float, default=None,
                   help="predicted-mfu floor (default "
                        "MXTPU_FLOPCHECK_MIN_MFU or 0 = disabled)")
    p.add_argument("--baseline", default=None, metavar="FILE",
                   help="compare against a committed baseline (the CI "
                        "drift gate); exit non-zero on kernel-count / "
                        "step-time / MFU drift past tolerance")
    p.add_argument("--write-baseline", default=None, metavar="FILE",
                   help="write the per-program baseline JSON and exit 0 "
                        "(refreshing the baseline is a deliberate act)")
    p.add_argument("--tol", type=float, default=None,
                   help="baseline tolerance band (default "
                        "MXTPU_FLOPCHECK_TOL, the baseline's own, or 0.1)")
    p.add_argument("--memcheck-baseline", default=None, metavar="FILE",
                   help="ALSO run the memcheck gate from the same "
                        "compiled programs (the combined compile-once CI "
                        "gate; zoo programs only)")
    p.add_argument("--commscheck-baseline", default=None, metavar="FILE",
                   help="ALSO run the commscheck gate from the same "
                        "compiled programs")
    p.add_argument("--json", action="store_true", help="JSON output")
    p.add_argument("--list", action="store_true",
                   help="list zoo models and exit")
    p.add_argument("--quiet", action="store_true",
                   help="suppress progress lines")
    args = p.parse_args(argv)
    if args.list:
        for n in sorted(ZOO):
            print(n)
        return 0
    if not (args.zoo or args.models or args.sharded):
        p.error("nothing to check: pass --zoo, --models or --sharded")
    names = ([s.strip() for s in args.models.split(",") if s.strip()]
             if args.models else None)
    log = (lambda m: None) if (args.quiet or args.json) \
        else (lambda m: print(m, file=sys.stderr))
    combined = bool(args.memcheck_baseline or args.commscheck_baseline)

    # compile phase: ONE compile per program; when the combined gate is
    # on, the executables are kept and fed to all three analyzers
    t0 = time.time()
    zoo_progs, sharded_progs = [], []
    if args.zoo or args.models:
        zoo_progs = list(compiled_zoo_programs(
            names=names, k=args.k, guard=not args.no_guard, log=log))
    if args.sharded:
        sharded_progs = list(compiled_sharded_programs(
            n_devices=args.devices, k=args.k, log=log))
    compile_s = time.time() - t0
    n_progs = len(zoo_progs) + len(sharded_progs)
    n_analyzers = 1 + (1 if args.memcheck_baseline else 0) \
        + (1 if args.commscheck_baseline else 0)
    log("flopcheck: compiled %d program(s) once in %.1fs — %d analyzer(s)"
        " share them (a per-analyzer sweep would have paid ~%.1fs)"
        % (n_progs, compile_s, n_analyzers, n_analyzers * compile_s))

    lint_kw = {}
    if args.hot_frac is not None:
        lint_kw["hot_threshold"] = args.hot_frac
    if args.min_mfu is not None:
        lint_kw["mfu_floor"] = args.min_mfu
    findings, reports = [], {}
    fs, reps = check_zoo(programs=zoo_progs, **lint_kw)
    findings += fs
    reports.update(reps)
    fs, reps = check_sharded(programs=sharded_progs, **lint_kw)
    findings += fs
    reports.update(reps)

    if args.write_baseline:
        write_baseline(reports, args.write_baseline, tol=args.tol)
        log("flopcheck: baseline written to %s (%d programs)"
            % (args.write_baseline, len(reports)))
        return 0
    failures, notes = [], []
    if args.baseline:
        # compare_baseline already fails hlo_unavailable reports
        failures, notes = compare_baseline(reports, args.baseline,
                                           tol=args.tol)
    else:
        # no baseline gate running: the absence-of-evidence contract
        # still holds — an audit that never saw any HLO must not pass
        for n in sorted(reports):
            if reports[n].hlo_unavailable:
                failures.append(
                    "%s: compiled HLO text unavailable on this backend — "
                    "nothing was audited; refusing to pass on absence of "
                    "evidence" % n)

    if args.memcheck_baseline:
        by_model = {}
        for rec in zoo_progs:
            by_model.setdefault(rec[0].split("/")[0], []).append(rec)
        mfs, mfail, mnotes = _combined_memcheck(
            by_model, args.memcheck_baseline, args.tol)
        findings += mfs
        failures += ["[memcheck] " + f for f in mfail]
        notes += ["[memcheck] " + n for n in mnotes]
    if args.commscheck_baseline:
        cfs, cfail, cnotes = _combined_commscheck(
            zoo_progs + sharded_progs, args.commscheck_baseline, args.tol)
        findings += cfs
        failures += ["[commscheck] " + f for f in cfail]
        notes += ["[commscheck] " + n for n in cnotes]

    bad = unsuppressed(findings)
    if args.json:
        import jax
        print(json.dumps({
            "platform": jax.devices()[0].platform,
            "compile_seconds": round(compile_s, 2),
            "analyzers_sharing_compile": n_analyzers,
            "programs": {n: r.as_dict() for n, r in sorted(reports.items())},
            "findings": [f.as_dict() for f in findings],
            "suppressed": len(findings) - len(bad),
            "baseline_failures": failures,
            "baseline_notes": notes,
        }, indent=2))
    else:
        report_table(reports)
        if args.hotspots:
            hotspot_table(reports, top=args.hotspots,
                          memory_only=args.memory_bound)
        _tc.report(findings)
        for n in notes:
            print("note: %s" % n)
        for f in failures:
            print("BASELINE REGRESSION: %s" % f)
        print("flopcheck: %d finding(s) (%d suppressed), %d baseline "
              "regression(s) over %d program(s)%s"
              % (len(findings), len(findings) - len(bad), len(failures),
                 len(reports),
                 " [combined gate: %d analyzers, one compile]"
                 % n_analyzers if combined else ""))
    return 1 if (bad or failures) else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
