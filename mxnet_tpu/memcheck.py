"""memcheck: a static HBM analyzer for compiled step/serving programs.

The reference MXNet plans memory ahead of execution — NNVM's ``PlanMemory``
pass is a first-class pillar of the design and the paper credits it for
fitting larger models per device (arXiv:1512.01274, PAPER.md layer map #1);
TensorFlow makes the same argument for ahead-of-time buffer analysis
(arXiv:1605.08695). On the XLA substrate that plan exists too — the buffer
assignment of every compiled executable — but nothing in this stack audited
it: peak HBM was invisible until an OOM at full batch, and a regression
that silently doubles temp buffers passed every gate (tracecheck, PR 5,
audits the *semantics* of the program set; this module is its memory-side
complement and shares its :class:`~mxnet_tpu.tracecheck.Finding` framework,
suppressions and CLI shape).

``memcheck`` lowers AND compiles a program WITHOUT executing it — arguments
can be ``ShapeDtypeStruct``s, no buffer is ever allocated — and derives a
:class:`MemoryReport` from ``compiled.memory_analysis()`` plus the
scheduled-HLO view: peak HBM, argument/output/temp/alias bytes, and a
breakdown attributing the largest buffers to op paths and source provenance
(the same ``op_name``/``stack_frame_id`` metadata tracecheck's collective audit
reads).

Memory lint catalog (docs/static_analysis.md "Memory lints"):

==================  =====================================================
lint id             fires when
==================  =====================================================
``hbm-budget``      a program's peak HBM exceeds ``MXTPU_MEMCHECK_BUDGET``
                    (default derived from the device's ``bytes_limit``,
                    16 GiB when the backend reports none)
``donation-waste``  a donated input's bytes are NOT realized as alias
                    savings — the buffer is copied, so donation bought
                    nothing (the memory-side complement of tracecheck's
                    ``donation`` lint: that one says "not aliased", this
                    one accounts the wasted bytes per argument)
``temp-blowup``     temp bytes exceed ``MXTPU_MEMCHECK_TEMP_MULT`` (4.0)
                    times the argument+output estimate — the signature of
                    a rematerialization/fusion regression
``resident-set``    the co-resident footprint of a program SET — all
                    serving buckets of one engine, or the guard-on +
                    guard-off train programs — exceeds the budget. jit
                    caches keep every executable reachable, so their
                    temps are all retained: resident =
                    max(arg+out-alias) (state/params are shared, donated
                    buffers counted once) + sum(temp)
==================  =====================================================

CLI::

    python -m mxnet_tpu.memcheck --zoo                    # audit the zoo
    python -m mxnet_tpu.memcheck --models mlp,lenet --json
    python -m mxnet_tpu.memcheck --zoo --write-baseline MEMCHECK_baseline.json
    python -m mxnet_tpu.memcheck --zoo --baseline MEMCHECK_baseline.json

The ``--baseline`` mode is the CI regression gate (``ci/memcheck.sh``):
every zoo program's peak/temp bytes are compared against the committed
baseline with a tolerance band (``MXTPU_MEMCHECK_TOL``, default 10%) — any
program growing past tolerance fails with the buffer breakdown in the
message. Exit status is non-zero iff any unsuppressed finding or baseline
regression remains.
"""
from __future__ import annotations

import json
import re

import numpy as np

from .base import MXNetError, env_str
from .tracecheck import (Finding, MEM_LINTS, _is_suppressed,
                         unsuppressed, ZOO)

__all__ = [
    "MemoryReport", "analyze", "analyze_compiled", "lint_report",
    "lint_resident_set", "resident_bytes", "check_program",
    "check_registered", "check_train_step", "check_zoo",
    "compare_baseline", "write_baseline",
    "device_budget", "budget_bytes", "temp_multiple", "tolerance", "main",
    "MEM_LINTS",
]

#: fallback budget when the backend reports no ``bytes_limit`` (CPU): the
#: v5e HBM size — the chip this stack's perf story is written against
_DEFAULT_BUDGET = 16 << 30

#: ignore donation waste below this (a stray unaliased scalar — e.g. a
#: step counter returned transformed — is not worth a red gate)
_WASTE_FLOOR = 1024


def _parse_bytes(v, name):
    """Parse a byte count: plain number (int/float/scientific) or a
    K/M/G/T binary suffix (``MXTPU_MEMCHECK_BUDGET=12G``)."""
    v = str(v).strip()
    if not v:
        return None
    m = re.match(r"^([0-9.eE+\-]+)\s*([kKmMgGtT]?)i?[bB]?$", v)
    try:
        num = float(m.group(1)) if m else None
    except ValueError:
        num = None
    if num is None or num < 0:
        raise MXNetError("%s must be a byte count (optionally suffixed "
                         "K/M/G/T), got %r" % (name, v))
    scale = {"": 1, "k": 1 << 10, "m": 1 << 20,
             "g": 1 << 30, "t": 1 << 40}[m.group(2).lower()]
    return int(num * scale)


def _env_bytes(name):
    return _parse_bytes(env_str(name), name)


def device_budget(device=None):
    """Per-device HBM budget derivation (docs/static_analysis.md "Memory
    lints"): the backend's reported ``bytes_limit`` when it has one (TPU),
    else 16 GiB."""
    import jax
    device = device or jax.devices()[0]
    try:
        stats = device.memory_stats() or {}
    except Exception:
        stats = {}
    limit = stats.get("bytes_limit")
    return int(limit) if limit else _DEFAULT_BUDGET


def budget_bytes(device=None):
    """Effective peak-HBM budget: ``MXTPU_MEMCHECK_BUDGET`` (bytes, K/M/G/T
    suffixes accepted) or :func:`device_budget`."""
    env = _env_bytes("MXTPU_MEMCHECK_BUDGET")
    return env if env is not None else device_budget(device)


def temp_multiple():
    """``temp-blowup`` threshold: temps may be at most this multiple of the
    argument+output bytes (``MXTPU_MEMCHECK_TEMP_MULT``, default 4.0)."""
    from .base import env_float
    return env_float("MXTPU_MEMCHECK_TEMP_MULT", 4.0)


def tolerance():
    """Baseline-gate tolerance band (``MXTPU_MEMCHECK_TOL``, default 0.1 =
    10% growth allowed per program per metric)."""
    from .base import env_float
    return env_float("MXTPU_MEMCHECK_TOL", 0.1)


# ---------------------------------------------------------------------------
# scheduled-HLO parsing: shapes, aliasing, buffer attribution
# ---------------------------------------------------------------------------

#: bit widths of HLO element types (pred buffers are byte-addressed)
_DTYPE_BITS = {
    "pred": 8, "s4": 4, "u4": 4, "s8": 8, "u8": 8,
    "f8e4m3fn": 8, "f8e5m2": 8, "f8e4m3b11fnuz": 8, "f8e4m3fnuz": 8,
    "f8e5m2fnuz": 8,
    "s16": 16, "u16": 16, "f16": 16, "bf16": 16,
    "s32": 32, "u32": 32, "f32": 32,
    "s64": 64, "u64": 64, "f64": 64, "c64": 64, "c128": 128,
}

# one instruction: `%name = f32[8,64]{1,0} opcode(...)`
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<instr>[\w.\-]+)\s*=\s*"
    r"(?P<dtype>[a-z][a-z0-9]*)\[(?P<dims>[0-9,]*)\](?:\{[^}]*\})?\s+"
    r"(?P<opcode>[\w\-]+)\(")
# computation headers: `%fused_computation (...) -> ... {` / `ENTRY %main ...`
_COMP_RE = re.compile(r"^(?P<entry>ENTRY\s+)?%(?P<name>[\w.\-]+)\s*\(.*\{\s*$")
# op_name may contain escaped quotes: op_name="state[\'p\']"
_OPNAME_RE = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
# instruction metadata names a row of the module header's StackFrames
# table; FileLocations / FileNames resolve the row to file:line
_FRAME_ID_RE = re.compile(r"stack_frame_id=(\d+)")
_FRAME_ROW_RE = re.compile(r"^(\d+)\s+(.+)$")
_FRAME_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_FIRST_COMP_RE = re.compile(r"^(?:%|ENTRY )", re.M)
# input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {}, may-alias) }
_ALIAS_MAP_RE = re.compile(r"input_output_alias=\{(?P<body>.*?)\}\s*,?\s*"
                           r"entry_computation_layout", re.S)
_ALIAS_ENTRY_RE = re.compile(r"\{[0-9,\s]*\}:\s*\((\d+),")
_PARAM_RE = re.compile(r"parameter\((\d+)\)")

#: opcodes whose "output" is a view of an existing buffer, not a new one —
#: attributing bytes to them would double-count the real producer
_VIEW_OPCODES = frozenset({"get-tuple-element", "bitcast", "tuple"})


def _shape_bytes(dtype, dims):
    bits = _DTYPE_BITS.get(dtype)
    if bits is None:
        return 0
    n = 1
    for d in dims.split(","):
        d = d.strip()
        if d:
            n *= int(d)
    return (n * bits) // 8


def _unescape(s):
    return s.replace("\\'", "'").replace('\\"', '"')


def parse_stack_frames(hlo_text):
    """``{stack_frame_id: "file:line"}`` from the header tables of a
    compiled module's text. The frame an instruction names is the
    innermost user frame of the op that produced it — what the analyzers
    report as a finding's provenance."""
    tables, cur = {}, None
    # the tables sit above the first computation: split only that much of
    # a multi-MB module text
    first_comp = _FIRST_COMP_RE.search(hlo_text)
    header = hlo_text[:first_comp.start()] if first_comp else hlo_text
    for line in header.splitlines():
        line = line.strip()
        if line in _FRAME_TABLES:
            cur = tables.setdefault(line, {})
        elif cur is not None:
            m = _FRAME_ROW_RE.match(line)
            if m:
                cur[int(m.group(1))] = m.group(2)

    def field(row, key):
        m = re.search(r"\b%s=(\d+)" % key, row or "")
        return int(m.group(1)) if m else None

    frames = {}
    for fid, row in tables.get("StackFrames", {}).items():
        loc = tables.get("FileLocations", {}).get(
            field(row, "file_location_id"))
        fname = tables.get("FileNames", {}).get(field(loc, "file_name_id"))
        if fname is not None:
            frames[fid] = "%s:%s" % (fname.strip('"'), field(loc, "line"))
    return frames


def instr_provenance(line, frames):
    """``file:line`` of one instruction line (None when it carries no
    frame), against :func:`parse_stack_frames`' table."""
    m = _FRAME_ID_RE.search(line)
    return frames.get(int(m.group(1))) if m else None


def parse_hlo_buffers(hlo_text):
    """Walk the scheduled HLO text of a compiled program and return
    ``(buffers, entry_params, aliased_params)``:

    * ``buffers`` — one dict per buffer-producing instruction (fusion
      internals and pure views skipped) with ``bytes``, ``opcode``,
      ``instruction``, ``op_path`` (the op_name metadata — nesting through
      ``while`` bodies visible, same convention as tracecheck) and
      ``provenance`` (``file:line``), sorted largest first;
    * ``entry_params`` — ``{param_number: (label, bytes)}`` for the entry
      computation's parameters (jax labels them with the argument path,
      e.g. ``state['p']``);
    * ``aliased_params`` — parameter numbers the lowering aliased to an
      output (successful donation), from the ``input_output_alias`` header.
    """
    buffers, entry_params, aliased = [], {}, set()
    frames = parse_stack_frames(hlo_text)
    m = _ALIAS_MAP_RE.search(hlo_text)
    if m:
        for e in _ALIAS_ENTRY_RE.finditer(m.group("body")):
            aliased.add(int(e.group(1)))
    in_entry = False
    in_fusion = False
    for line in hlo_text.splitlines():
        cm = _COMP_RE.match(line)
        if cm:
            in_entry = bool(cm.group("entry"))
            in_fusion = cm.group("name").startswith("fused_computation")
            continue
        im = _INSTR_RE.match(line)
        if not im:
            continue
        nbytes = _shape_bytes(im.group("dtype"), im.group("dims"))
        opcode = im.group("opcode")
        if opcode == "parameter" and in_entry:
            pm = _PARAM_RE.search(line)
            if pm:
                op = _OPNAME_RE.search(line)
                label = _unescape(op.group(1)) if op else None
                entry_params[int(pm.group(1))] = (label, nbytes)
        if in_fusion or opcode in _VIEW_OPCODES or not nbytes:
            continue
        if opcode == "parameter" and not in_entry:
            continue  # sub-computation params alias their call operands
        op = _OPNAME_RE.search(line)
        buffers.append({
            "bytes": nbytes,
            "opcode": opcode,
            "instruction": im.group("instr"),
            "op_path": _unescape(op.group(1)) if op else None,
            "provenance": instr_provenance(line, frames),
        })
    buffers.sort(key=lambda b: b["bytes"], reverse=True)
    return buffers, entry_params, aliased


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------

def _fmt_bytes(n):
    if n is None:
        return "?"
    for unit, div in (("GiB", 1 << 30), ("MiB", 1 << 20), ("KiB", 1 << 10)):
        if n >= div:
            return "%.2f %s" % (n / div, unit)
    return "%d B" % n


class MemoryReport(object):
    """Static memory profile of ONE compiled program.

    ``peak_bytes`` is the program's high-water HBM estimate:
    ``argument + output + temp - alias`` (an aliased/donated buffer is
    counted once, not as both input and output — XLA's own accounting).
    ``top_buffers`` attributes the largest individual buffers to op paths
    and source provenance."""

    __slots__ = ("program", "platform", "argument_bytes", "output_bytes",
                 "temp_bytes", "alias_bytes", "generated_code_bytes",
                 "top_buffers", "donated", "unaliased_donated")

    def __init__(self, program, platform, argument_bytes, output_bytes,
                 temp_bytes, alias_bytes, generated_code_bytes=0,
                 top_buffers=(), donated=(), unaliased_donated=()):
        self.program = program
        self.platform = platform
        self.argument_bytes = int(argument_bytes)
        self.output_bytes = int(output_bytes)
        self.temp_bytes = int(temp_bytes)
        self.alias_bytes = int(alias_bytes)
        self.generated_code_bytes = int(generated_code_bytes)
        self.top_buffers = list(top_buffers)
        #: [(label, bytes)] of donated argument leaves
        self.donated = list(donated)
        #: [(label, bytes)] donated leaves the lowering did NOT alias
        self.unaliased_donated = list(unaliased_donated)

    @property
    def peak_bytes(self):
        return (self.argument_bytes + self.output_bytes + self.temp_bytes
                - self.alias_bytes)

    @property
    def donated_bytes(self):
        return sum(b for _, b in self.donated)

    @property
    def wasted_donation_bytes(self):
        return sum(b for _, b in self.unaliased_donated)

    def as_dict(self):
        return {
            "program": self.program,
            "platform": self.platform,
            "peak_bytes": self.peak_bytes,
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "alias_bytes": self.alias_bytes,
            "generated_code_bytes": self.generated_code_bytes,
            "donated_bytes": self.donated_bytes,
            "wasted_donation_bytes": self.wasted_donation_bytes,
            "top_buffers": self.top_buffers,
        }

    def breakdown(self, top=5):
        """Human-readable largest-buffer attribution, one line each."""
        lines = []
        for b in self.top_buffers[:top]:
            where = b["op_path"] or b["instruction"]
            if b["provenance"]:
                where += " @ " + b["provenance"]
            lines.append("%10s  %-16s %s"
                         % (_fmt_bytes(b["bytes"]), b["opcode"], where))
        return lines

    def format(self):
        return ("%s: peak %s (args %s + out %s + temp %s - alias %s)"
                % (self.program, _fmt_bytes(self.peak_bytes),
                   _fmt_bytes(self.argument_bytes),
                   _fmt_bytes(self.output_bytes),
                   _fmt_bytes(self.temp_bytes),
                   _fmt_bytes(self.alias_bytes)))

    def __repr__(self):
        return "MemoryReport(%s)" % self.format()


def _donated_leaves(args, kwargs, donate_argnums):
    """Flat-leaf index -> (label, bytes, keystr) bookkeeping for the
    donated positional args. The flat order matches the entry parameter
    numbering UNLESS the lowering pruned an unused argument (e.g. the RNG
    key of an rng-free step) — so :func:`analyze_compiled` aligns by the
    HLO's own parameter labels first and falls back to position."""
    import jax
    donated = {}
    offset = 0
    for i, a in enumerate(args):
        leaves = jax.tree_util.tree_flatten_with_path(a)[0]
        for j, (path, leaf) in enumerate(leaves):
            if i in (donate_argnums or ()):
                nbytes = int(np.prod(getattr(leaf, "shape", ()) or (1,))
                             * np.dtype(leaf.dtype).itemsize) \
                    if hasattr(leaf, "dtype") else 0
                ks = jax.tree_util.keystr(path)
                donated[offset + j] = (
                    "args[%d]%s" % (i, ks), nbytes, ks)
        offset += len(leaves)
    offset += len(jax.tree_util.tree_leaves(dict(kwargs or {})))
    return donated, offset


_IDENT_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*")


def _label_keystr(label):
    """The pytree-path part of an HLO entry-parameter label: jax labels
    parameters ``<argname><keystr>`` (``state['opt']['fc1_weight']``) —
    strip the leading identifier so donated leaves can be matched by
    keystr regardless of the function's parameter name."""
    if not label:
        return None
    m = _IDENT_RE.match(label)
    return label[m.end():] if m else None


def analyze_compiled(compiled, name, args=(), kwargs=None,
                     donate_argnums=(), top=8):
    """Build a :class:`MemoryReport` from an ALREADY-compiled program
    (``jax.stages.Compiled`` — e.g. a serving bucket executable). Never
    executes anything."""
    import jax
    ma = compiled.memory_analysis()
    try:
        hlo_text = compiled.as_text()
    except Exception:
        hlo_text = ""
    buffers, entry_params, aliased = parse_hlo_buffers(hlo_text or "")
    donated, total = _donated_leaves(args, kwargs, donate_argnums)
    # map each donated leaf to its HLO parameter number by LABEL keystr
    # first (robust to the lowering pruning an unused argument, which
    # shifts every later position), positionally only when labels cannot
    # disambiguate AND nothing was pruned
    by_keystr = {}
    for pnum, (plabel, _pb) in entry_params.items():
        ks = _label_keystr(plabel)
        if ks is not None:
            by_keystr.setdefault(ks, []).append(pnum)
    pruned = bool(entry_params) and len(entry_params) != total
    # a waste claim needs parseable aliasing EVIDENCE: if the HLO text was
    # unavailable/unparseable (no alias entries found even though the
    # compiler reports alias savings), claiming every donated leaf wasted
    # would fail healthy deploys under MXTPU_MEMCHECK=error
    evidence = bool(hlo_text) and (bool(aliased)
                                   or ma.alias_size_in_bytes == 0)
    donated_sizes, unaliased = [], []
    for idx, (label, nbytes, ks) in sorted(donated.items()):
        cands = by_keystr.get(ks, ())
        if len(cands) == 1:
            pnum = cands[0]
        elif pruned:
            continue  # cannot align this leaf — claim nothing about it
        else:
            pnum = idx
        if pnum in entry_params:
            plabel, pbytes = entry_params[pnum]
            label = plabel or label
            nbytes = pbytes or nbytes
        donated_sizes.append((label, nbytes))
        if evidence and pnum not in aliased:
            unaliased.append((label, nbytes))
    return MemoryReport(
        name, jax.devices()[0].platform,
        argument_bytes=ma.argument_size_in_bytes,
        output_bytes=ma.output_size_in_bytes,
        temp_bytes=ma.temp_size_in_bytes,
        alias_bytes=ma.alias_size_in_bytes,
        generated_code_bytes=ma.generated_code_size_in_bytes,
        top_buffers=buffers[:top],
        donated=donated_sizes,
        unaliased_donated=unaliased)


def analyze(fn, args=(), kwargs=None, donate_argnums=(), name=None, top=8):
    """Lower AND compile ``fn`` (never executed — args may be
    ``ShapeDtypeStruct``s) and return its :class:`MemoryReport`.

    ``fn`` may be a jitted function (its own donation settings are kept —
    pass ``donate_argnums`` anyway so the per-argument waste accounting
    knows which leaves were meant to alias) or a plain callable (wrapped in
    ``jax.jit(fn, donate_argnums=...)``)."""
    import jax
    kwargs = dict(kwargs or {})
    if name is None:
        name = getattr(fn, "__name__", None) or repr(fn)
    jitted = fn if hasattr(fn, "lower") \
        else jax.jit(fn, donate_argnums=donate_argnums or ())
    compiled = jitted.lower(*args, **kwargs).compile()
    return analyze_compiled(compiled, name, args=args, kwargs=kwargs,
                            donate_argnums=donate_argnums, top=top)


# ---------------------------------------------------------------------------
# lints
# ---------------------------------------------------------------------------

def _top_attr(report, skip_params=False):
    """(op_path, provenance) of the report's largest attributable buffer —
    the thing a budget/temp finding should point at."""
    for b in report.top_buffers:
        if skip_params and b["opcode"] == "parameter":
            continue
        return b["op_path"] or b["instruction"], b["provenance"]
    return None, None


def lint_report(report, budget=None, temp_mult=None, waste_floor=None):
    """Per-program memory lints over one :class:`MemoryReport`:
    ``hbm-budget``, ``donation-waste``, ``temp-blowup``. Returns findings
    with suppressions applied (like ``tracecheck.check_program``)."""
    findings = []
    budget = budget_bytes() if budget is None else int(budget)
    temp_mult = temp_multiple() if temp_mult is None else float(temp_mult)
    waste_floor = _WASTE_FLOOR if waste_floor is None else int(waste_floor)
    name = report.program

    if report.peak_bytes > budget:
        op_path, prov = _top_attr(report)
        findings.append(Finding(
            "hbm-budget", name,
            "peak HBM %s exceeds the budget %s (args %s + out %s + temp %s"
            " - alias %s; MXTPU_MEMCHECK_BUDGET). Largest buffers:\n  %s"
            % (_fmt_bytes(report.peak_bytes), _fmt_bytes(budget),
               _fmt_bytes(report.argument_bytes),
               _fmt_bytes(report.output_bytes),
               _fmt_bytes(report.temp_bytes),
               _fmt_bytes(report.alias_bytes),
               "\n  ".join(report.breakdown())),
            op_path=op_path, provenance=prov))

    for label, nbytes in report.unaliased_donated:
        if nbytes < waste_floor:
            continue
        findings.append(Finding(
            "donation-waste", name,
            "donated argument %s (%s) is NOT aliased to any output — its "
            "bytes are copied, not saved; the program's working set carries "
            "both the old and the new buffer (alias savings realized: %s of "
            "%s donated)"
            % (label, _fmt_bytes(nbytes), _fmt_bytes(report.alias_bytes),
               _fmt_bytes(report.donated_bytes)),
            op_path=label))

    estimate = report.argument_bytes + report.output_bytes
    if estimate > 0 and report.temp_bytes > temp_mult * estimate:
        op_path, prov = _top_attr(report, skip_params=True)
        findings.append(Finding(
            "temp-blowup", name,
            "temp buffers %s are %.1fx the param+activation estimate %s "
            "(threshold %.1fx, MXTPU_MEMCHECK_TEMP_MULT) — a "
            "rematerialization/fusion regression. Largest buffers:\n  %s"
            % (_fmt_bytes(report.temp_bytes),
               report.temp_bytes / estimate, _fmt_bytes(estimate),
               temp_mult, "\n  ".join(report.breakdown())),
            op_path=op_path, provenance=prov))

    for f in findings:
        f.suppressed = _is_suppressed(f)
    return findings


def resident_bytes(reports):
    """Co-resident footprint of a program set: arguments/outputs are shared
    state (the same params/batch buffers feed every variant — take the
    max), but every executable's temp allocation stays reachable through
    the jit cache — sum them."""
    reports = list(reports)
    if not reports:
        return 0
    return (max(r.argument_bytes + r.output_bytes - r.alias_bytes
                for r in reports)
            + sum(r.temp_bytes for r in reports))


def lint_resident_set(reports, set_name, budget=None):
    """``resident-set``: the summed footprint of co-resident programs (all
    serving buckets of one engine; guard-on + guard-off train programs)
    against the budget."""
    reports = list(reports)
    budget = budget_bytes() if budget is None else int(budget)
    total = resident_bytes(reports)
    findings = []
    if reports and total > budget:
        biggest = max(reports, key=lambda r: r.temp_bytes)
        members = ", ".join(
            "%s (temp %s)" % (r.program, _fmt_bytes(r.temp_bytes))
            for r in reports)
        findings.append(Finding(
            "resident-set", set_name,
            "co-resident program set needs %s (> budget %s): jit caches "
            "keep every executable's buffers reachable — "
            "max(args+out-alias) + sum(temps) over [%s]. Largest temp "
            "holder: %s\n  %s"
            % (_fmt_bytes(total), _fmt_bytes(budget), members,
               biggest.program, "\n  ".join(biggest.breakdown())),
            op_path=biggest.program))
    for f in findings:
        f.suppressed = _is_suppressed(f)
    return findings


def check_program(fn, args=(), kwargs=None, donate_argnums=(), name=None,
                  budget=None, temp_mult=None):
    """Analyze + lint ONE program; returns ``(findings, report)``."""
    report = analyze(fn, args, kwargs, donate_argnums=donate_argnums,
                     name=name)
    return lint_report(report, budget=budget, temp_mult=temp_mult), report


def check_registered(match=None, budget=None, temp_mult=None,
                     resident_name=None):
    """Memory-audit live programs from the tracecheck registry whose name
    contains ``match`` (a string, or a tuple — contains ANY): per-program
    lints plus ONE ``resident-set`` lint over the whole matched set. This
    is the bucketed-cache audit (``BucketingModule.check(memory=True)``,
    docs/perf.md "Packed accumulators"): every bucket shape's compiled
    scan stays reachable in its jit cache, so the set's co-resident
    footprint — max(args+out) + sum(temps) — is what the budget must
    cover. Returns ``(findings, reports)``."""
    from .tracecheck import registered_programs
    if match is None:
        matches = None                  # audit EVERY registered program
    else:
        matches = (match,) if isinstance(match, str) else tuple(match)
        if not matches:
            # an explicitly EMPTY prefix set audits nothing: a
            # BucketingModule that never dispatched must not sweep (and
            # attribute a resident-set over) unrelated programs
            return [], {}
    findings = []
    reports = {}
    for rec in registered_programs():
        if matches is not None and not any(m in rec.name
                                           for m in matches):
            continue
        fn = rec.fn_ref()
        if fn is None:
            continue
        fs, rep = check_program(fn, rec.arg_structs,
                                donate_argnums=rec.donate_argnums,
                                name=rec.name, budget=budget,
                                temp_mult=temp_mult)
        findings += fs
        reports[rec.name] = rep
    findings += lint_resident_set(
        reports.values(),
        "%s/resident-set" % (resident_name or "registered"),
        budget=budget)
    return findings, reports


# ---------------------------------------------------------------------------
# TrainStep / zoo auditing (mirrors tracecheck.check_train_step)
# ---------------------------------------------------------------------------

def check_train_step(ts, data_shapes, label_shapes, k=2, guard=True,
                     name=None, budget=None, temp_mult=None):
    """Memory-audit a :class:`~mxnet_tpu.train_step.TrainStep`'s full
    program set — unguarded step, guarded step, K-step scan, guarded K-step
    scan (``tracecheck.train_step_programs``, THE shared recipe for what
    training dispatches) — plus the ``resident-set`` lint over the whole
    set (the guard-on and guard-off executables are co-resident in the jit
    caches). No step program ever executes. Returns ``(findings,
    reports)`` where ``reports`` maps program name ->
    :class:`MemoryReport`."""
    from .tracecheck import train_step_programs
    name = name or "TrainStep(%s)" % ts.symbol.name
    findings = []
    reports = {}
    for pname, jitfn, pargs in train_step_programs(
            ts, data_shapes, label_shapes, k=k, guard=guard, name=name):
        fs, rep = check_program(jitfn, pargs, donate_argnums=(0,),
                                name=pname, budget=budget,
                                temp_mult=temp_mult)
        findings += fs
        reports[pname] = rep
    findings += lint_resident_set(reports.values(),
                                  "%s/resident-set" % name, budget=budget)
    return findings, reports


def check_zoo(names=None, k=2, guard=True, budget=None, temp_mult=None,
              log=None):
    """Memory-audit the model zoo's step programs (same configs as
    ``tracecheck.ZOO``); returns ``(findings, reports)``."""
    from .tracecheck import zoo_train_step
    names = list(names) if names else sorted(ZOO)
    findings = []
    reports = {}
    for mname in names:
        if mname not in ZOO:
            raise MXNetError("memcheck: unknown zoo model %r (have %s)"
                             % (mname, ", ".join(sorted(ZOO))))
        if log:
            log("memcheck: analyzing %s ..." % mname)
        ts, data_shapes, label_shapes = zoo_train_step(mname)
        fs, reps = check_train_step(
            ts, data_shapes, label_shapes,
            k=k, guard=guard, name=mname, budget=budget,
            temp_mult=temp_mult)
        findings += fs
        reports.update(reps)
    return findings, reports


# ---------------------------------------------------------------------------
# the baseline regression gate (ci/memcheck.sh)
# ---------------------------------------------------------------------------

#: metrics the baseline pins per program
_BASELINE_METRICS = ("peak_bytes", "temp_bytes")

#: absolute slack added to the tolerance band — the zoo programs are tiny
#: on purpose, and a 10% band around a 40 KiB program is measurement noise
_BASELINE_SLACK = 64 << 10


def write_baseline(reports, path, tol=None):
    """Write the committed baseline: per-program peak/temp bytes, keyed by
    platform (a CPU baseline must not gate a TPU run)."""
    import jax
    from .model import atomic_write_bytes
    data = {
        "platform": jax.devices()[0].platform,
        "tolerance": tolerance() if tol is None else float(tol),
        "programs": {
            name: {m: getattr(rep, m) for m in _BASELINE_METRICS}
            for name, rep in sorted(reports.items())},
    }
    atomic_write_bytes(path, (json.dumps(data, indent=2, sort_keys=True)
                              + "\n").encode())
    return data


def compare_baseline(reports, baseline, tol=None):
    """The regression gate: compare every report against the committed
    baseline. Returns ``(failures, notes)`` — ``failures`` are gate-red
    strings (program grew past the tolerance band, or is missing from the
    baseline), ``notes`` informational (program shrank well below
    baseline: refresh it; stale baseline entries). A platform-mismatched
    baseline produces one note and no failures — a CPU baseline cannot
    judge TPU numbers."""
    import jax
    if isinstance(baseline, str):
        with open(baseline) as f:
            baseline = json.load(f)
    if tol is None:
        # precedence: explicit arg > MXTPU_MEMCHECK_TOL env (the operator
        # loosening a gate run) > the baseline's stored band > 0.1
        from .base import env_float
        tol = env_float("MXTPU_MEMCHECK_TOL",
                        float(baseline.get("tolerance", 0.1)))
    else:
        tol = float(tol)
    platform = jax.devices()[0].platform
    failures, notes = [], []
    if baseline.get("platform") != platform:
        notes.append(
            "memcheck baseline was written on platform %r but this run is "
            "%r — skipping the regression gate (re-run --write-baseline on "
            "this platform to arm it)"
            % (baseline.get("platform"), platform))
        return failures, notes
    base_progs = dict(baseline.get("programs") or {})
    for name, rep in sorted(reports.items()):
        base = base_progs.pop(name, None)
        if base is None:
            failures.append(
                "%s: not in the baseline — a new program must be added "
                "deliberately (run `python -m mxnet_tpu.memcheck --zoo "
                "--write-baseline MEMCHECK_baseline.json` and commit the "
                "diff)" % name)
            continue
        for metric in _BASELINE_METRICS:
            b = int(base.get(metric, 0))
            cur = int(getattr(rep, metric))
            allowed = b + max(int(b * tol), _BASELINE_SLACK)
            if cur > allowed:
                failures.append(
                    "%s: %s grew %s -> %s (+%.1f%%, tolerance %.0f%% + "
                    "%s slack, MXTPU_MEMCHECK_TOL). Largest buffers:\n  %s"
                    % (name, metric, _fmt_bytes(b), _fmt_bytes(cur),
                       100.0 * (cur - b) / max(1, b), 100.0 * tol,
                       _fmt_bytes(_BASELINE_SLACK),
                       "\n  ".join(rep.breakdown())))
            elif b > _BASELINE_SLACK and cur < b - max(int(b * tol),
                                                       _BASELINE_SLACK):
                notes.append(
                    "%s: %s shrank %s -> %s — nice; refresh the baseline "
                    "to lock the win in"
                    % (name, metric, _fmt_bytes(b), _fmt_bytes(cur)))
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
    out.write("%-*s  %10s %10s %10s %10s %10s\n"
              % (w, "program", "peak", "args", "out", "temp", "alias"))
    for name in sorted(reports):
        r = reports[name]
        out.write("%-*s  %10s %10s %10s %10s %10s\n"
                  % (w, name, _fmt_bytes(r.peak_bytes),
                     _fmt_bytes(r.argument_bytes),
                     _fmt_bytes(r.output_bytes), _fmt_bytes(r.temp_bytes),
                     _fmt_bytes(r.alias_bytes)))


def main(argv=None):
    import argparse
    import sys
    from . import tracecheck as _tc
    p = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.memcheck",
        description="Static HBM analyzer for compiled step programs: "
                    "peak/argument/temp/alias accounting, donation-waste "
                    "and budget lints, and the baseline regression gate "
                    "(docs/static_analysis.md \"Memory lints\").")
    p.add_argument("--zoo", action="store_true",
                   help="analyze every shipped model's step/scan programs")
    p.add_argument("--models", default=None,
                   help="comma-separated zoo subset (implies --zoo)")
    p.add_argument("--k", type=int, default=2,
                   help="scan depth for the K-step programs (default 2)")
    p.add_argument("--no-guard", action="store_true",
                   help="skip the guarded program variants")
    p.add_argument("--budget", default=None,
                   help="peak-HBM budget in bytes (K/M/G/T suffixes ok; "
                        "default MXTPU_MEMCHECK_BUDGET or the device)")
    p.add_argument("--temp-mult", type=float, default=None,
                   help="temp-blowup multiple (default "
                        "MXTPU_MEMCHECK_TEMP_MULT or 4.0)")
    p.add_argument("--baseline", default=None, metavar="FILE",
                   help="compare against a committed baseline (the CI "
                        "regression gate); exit non-zero on growth past "
                        "tolerance")
    p.add_argument("--write-baseline", default=None, metavar="FILE",
                   help="write the per-program baseline JSON and exit 0 "
                        "(skips the findings/baseline gate — refreshing "
                        "the baseline is a deliberate act)")
    p.add_argument("--tol", type=float, default=None,
                   help="baseline tolerance band (default "
                        "MXTPU_MEMCHECK_TOL, the baseline's own, or 0.1)")
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
    if not (args.zoo or args.models):
        p.error("nothing to check: pass --zoo or --models")
    names = ([s.strip() for s in args.models.split(",") if s.strip()]
             if args.models else None)
    log = (lambda m: None) if (args.quiet or args.json) \
        else (lambda m: print(m, file=sys.stderr))
    budget = (None if args.budget is None
              else _parse_bytes(args.budget, "--budget"))
    findings, reports = check_zoo(names=names, k=args.k,
                                  guard=not args.no_guard, budget=budget,
                                  temp_mult=args.temp_mult, log=log)
    if args.write_baseline:
        write_baseline(reports, args.write_baseline, tol=args.tol)
        log("memcheck: baseline written to %s (%d programs)"
            % (args.write_baseline, len(reports)))
        return 0
    failures, notes = [], []
    if args.baseline:
        failures, notes = compare_baseline(reports, args.baseline,
                                           tol=args.tol)
    bad = unsuppressed(findings)
    if args.json:
        import jax
        print(json.dumps({
            "platform": jax.devices()[0].platform,
            "budget_bytes": budget if budget is not None else budget_bytes(),
            "programs": {n: r.as_dict() for n, r in sorted(reports.items())},
            "findings": [f.as_dict() for f in findings],
            "suppressed": len(findings) - len(bad),
            "baseline_failures": failures,
            "baseline_notes": notes,
        }, indent=2))
    else:
        report_table(reports)
        _tc.report(findings)
        for n in notes:
            print("note: %s" % n)
        for f in failures:
            print("BASELINE REGRESSION: %s" % f)
        print("memcheck: %d finding(s) (%d suppressed), %d baseline "
              "regression(s) over %d program(s)"
              % (len(findings), len(findings) - len(bad), len(failures),
                 len(reports)))
    return 1 if (bad or failures) else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
