"""commscheck: a static collective-communication analyzer for compiled
partitioned programs.

tracecheck (PR 5) audits the *semantics* of a compiled step program and
memcheck (PR 9) audits its *HBM*; this module completes the analyzer
trilogy with the third resource every partitioned program spends:
inter-chip bandwidth. The reference hand-routed its communication
(CommDevice reduce, ps-lite push/pull) so every byte on the wire was an
explicit line of code; on the XLA substrate GSPMD *places* the
collectives at compile time, and nothing audited what it placed — a
sharding mistake that sneaks an all-gather into the K-step scan body
replays its bandwidth K times per dispatch and is invisible until a
multichip run gets slow. The same motivation as TVM's static cost model
closing the loop between program structure and predicted performance
(arXiv:1802.04799), and TensorFlow's explicit Send/Recv accounting on its
dataflow edges (arXiv:1605.08695).

``commscheck`` compiles a program WITHOUT executing it (arguments may be
``ShapeDtypeStruct``s carrying real shardings — unsharded args compile an
unpartitioned program with no collectives at all) and walks the scheduled
partitioned HLO to build a per-program **collective inventory**: every
all-reduce / all-gather / reduce-scatter / all-to-all / collective-permute
with its mesh axes (inferred from replica groups against the mesh's device
grid), payload bytes (per HLO dtype width — memcheck's shape parser),
execution count (a ``while``-body collective runs K times per dispatch),
op path and source provenance. On top of the inventory ride four lints in
tracecheck's :class:`~mxnet_tpu.tracecheck.Finding` framework:

====================  ====================================================
lint id               fires when
====================  ====================================================
``resharding-copy``   an entry argument's declared sharding is re-laid-out
                      (a collective consumes the parameter directly)
                      before first use — the silent resharding copy the
                      PR 7 pre-sharded superbatch landing eliminated by
                      construction
``replicated-large``  an intermediate above
                      ``MXTPU_COMMSCHECK_REPL_BYTES`` (default 1 MiB) is
                      materialized replicated across a mesh axis where a
                      sharded operand exists (an all-gather that big means
                      every chip holds the full array)
``gather-in-loop``    a gather-type collective (anything but all-reduce /
                      collective-permute) sits inside the compiled while
                      body — it pays its bandwidth K times per dispatch
                      (generalizes the compiled half of tracecheck's
                      ``collective-in-scan``, which is now a thin alias
                      over this pass)
``comms-bound``       the static roofline predicts scaling efficiency
                      below ``MXTPU_COMMSCHECK_MIN_EFF`` (default 0.5):
                      predicted collective time (wire bytes / link
                      bandwidth per device kind) vs predicted compute
                      time (XLA cost-model FLOPs / peak) — the finding
                      carries the full inventory
====================  ====================================================

The roofline is a MODEL, not a measurement: ring-algorithm wire bytes
(all-reduce moves ``2(n-1)/n``x its payload, gather/scatter ``(n-1)/n``x,
ppermute 1x), a per-device-kind link-bandwidth table, and the existing
FLOPs lowering (``compiled.cost_analysis()``; the XLA cost model counts a
while body ONCE, so compute and per-iteration comm compare like with
like). No measurement on the chip has been held against it yet.

CLI::

    python -m mxnet_tpu.commscheck --zoo                  # 28 programs
    python -m mxnet_tpu.commscheck --zoo --sharded        # + the PR 7 set
    python -m mxnet_tpu.commscheck --models mlp,lenet --json
    python -m mxnet_tpu.commscheck --zoo --sharded \\
        --write-baseline COMMSCHECK_baseline.json

``--baseline`` is the CI drift gate (``ci/commscheck.sh``): every
program's per-dispatch collective count and payload bytes are compared
against the committed ``COMMSCHECK_baseline.json`` with a tolerance band
(``MXTPU_COMMSCHECK_TOL``, default 10%) — a refactor that sneaks an
all-gather into the scan body or triples the psum payload fails CI with
byte count and source provenance, before any multichip run. Exit status
is non-zero iff any unsuppressed finding or baseline regression remains.
"""
from __future__ import annotations

import itertools
import json
import re

import numpy as np

from .base import MXNetError, env_str
from .tracecheck import (Finding, COMM_LINTS, _is_suppressed, unsuppressed,
                         ZOO)
# ONE HLO-metadata parser set across the analyzer trilogy: byte/shape
# helpers and the op_name/source provenance regexes all live in memcheck
from .memcheck import (_parse_bytes, _shape_bytes, _fmt_bytes, _unescape,
                       _OPNAME_RE, instr_provenance, parse_stack_frames)

__all__ = [
    "CollectiveEntry", "CommsReport", "parse_collectives", "analyze",
    "analyze_compiled", "struct_args", "lint_report", "loop_findings",
    "check_program", "check_train_step", "check_zoo", "sharded_programs",
    "check_sharded", "compare_baseline", "write_baseline", "repl_bytes",
    "min_efficiency", "tolerance", "link_bandwidth", "peak_flops", "main",
    "COMM_LINTS",
]

#: collective kinds ordered as the lint catalog lists them; ``all-reduce``
#: is the expected grad/metric psum and ``collective-permute`` the
#: ring/pipeline schedule — the default in-loop allow list
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute",
                    "collective-broadcast")

DEFAULT_LOOP_ALLOW = ("all-reduce", "collective-permute")

# the per-device-kind capability rows live in ONE shared table
# (mxnet_tpu.devspec) consumed by this roofline and flopcheck's
from .devspec import link_bandwidth, peak_flops


def repl_bytes():
    """``replicated-large`` threshold (``MXTPU_COMMSCHECK_REPL_BYTES``,
    bytes with K/M/G/T binary suffixes; default 1 MiB)."""
    env = _parse_bytes(env_str("MXTPU_COMMSCHECK_REPL_BYTES"),
                       "MXTPU_COMMSCHECK_REPL_BYTES")
    return env if env is not None else (1 << 20)


def min_efficiency():
    """``comms-bound`` floor: predicted scaling efficiency below this
    fails (``MXTPU_COMMSCHECK_MIN_EFF``, default 0.5)."""
    from .base import env_float
    return env_float("MXTPU_COMMSCHECK_MIN_EFF", 0.5)


def tolerance():
    """Baseline drift-gate tolerance band (``MXTPU_COMMSCHECK_TOL``,
    default 0.1 = 10% growth allowed per program per metric)."""
    from .base import env_float
    return env_float("MXTPU_COMMSCHECK_TOL", 0.1)


# ---------------------------------------------------------------------------
# scheduled-HLO parsing: collectives, groups, axis attribution
# ---------------------------------------------------------------------------

# one collective instruction; the result type may be a TUPLE (a tiled
# all-to-all or a combined all-reduce returns one entry per shard/operand),
# so the type segment is matched lazily up to the opcode. ``-start``
# variants count; ``-done`` halves (the async retire) never match — the
# opcode must be followed directly by "(".
# a result type is either one array (`f32[8,4]{1,0}`) or a tuple of
# them. TPU layouts carry TILING PARENS inside the braces
# (`bf16[256,256]{1,0:T(8,128)}`), so the tuple alternative must allow
# one nesting level — a lazy `\(.*?\)` would truncate at T(…)'s `)` and
# the combined gradient all-reduce (tuple-typed, the dominant wire
# traffic on real chips) would silently vanish from the inventory
# NOTE the single-char `[^()]` branch: with `[^()]+` the star becomes
# ambiguous (many ways to chunk the same text) and a long non-matching
# paren line backtracks exponentially
_TYPE_PAT = (r"(?:\((?:[^()]|\([^()]*\))*\)"
             r"|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<instr>[\w.\-]+)\s*=\s*"
    r"(?P<type>" + _TYPE_PAT + r")\s+"
    r"(?P<kind>" + "|".join(COLLECTIVE_KINDS) + r")(?P<start>-start)?\(")
# the async retire half: its (single) result type IS the collective's
# true payload — an async -start's own type is a (operand..., result...)
# tuple whose naive sum double-counts
_DONE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*"
    r"(?P<type>" + _TYPE_PAT + r")\s+"
    r"(?:" + "|".join(COLLECTIVE_KINDS) + r")-done\("
    r"[^%]*%(?P<operand>[\w.\-]+)")

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
# replica_groups={{0,1},{2,3}} (explicit) or [G,S]<=[dims]T(perm) (iota);
# the bare {} spelling means "every participating device, one group"
_GROUPS_EMPTY_RE = re.compile(r"replica_groups=\{\s*\}")
_GROUPS_EXPL_RE = re.compile(
    r"replica_groups=\{(\{[0-9,\s]*\}(?:,\s*\{[0-9,\s]*\})*)\}")
_GROUP_RE = re.compile(r"\{([0-9,\s]*)\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?\s*)*)\}")
_PAIR_RE = re.compile(r"\{(\d+),(\d+)\}")
# entry-computation parameters (for resharding-copy: a collective whose
# operand IS an entry parameter re-lays-out a declared input sharding)
_ENTRY_RE = re.compile(r"^ENTRY\s+%[\w.\-]+\s*\(.*\{\s*$")
_COMP_END_RE = re.compile(r"^\}\s*$")
_PARAM_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<instr>[\w.\-]+)\s*=\s*[^ ]+\s+parameter\(\d+\)")


def _type_bytes(type_str):
    """Total bytes of an HLO result type (array or tuple of arrays)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        total += _shape_bytes(dtype, dims)
    return total


def _parse_groups(line):
    """Replica groups of one collective line as a tuple of tuples of
    partition ids, handling both the explicit and the iota spelling.
    Returns None when the line carries no replica_groups."""
    if _GROUPS_EMPTY_RE.search(line):
        return ()  # all devices, one group
    m = _GROUPS_EXPL_RE.search(line)
    if m:
        groups = []
        for g in _GROUP_RE.findall(m.group(1)):
            ids = tuple(int(x) for x in g.split(",") if x.strip())
            if ids:
                groups.append(ids)
        return tuple(groups) or None
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        ngroups, gsize = int(m.group(1)), int(m.group(2))
        dims = [int(d) for d in m.group(3).split(",")]
        ids = np.arange(int(np.prod(dims))).reshape(dims)
        if m.group(4):
            perm = [int(p) for p in m.group(4).split(",")]
            ids = np.transpose(ids, perm)
        ids = ids.reshape(ngroups, gsize)
        return tuple(tuple(int(x) for x in row) for row in ids)
    return None


def _parse_pairs(line):
    m = _PAIRS_RE.search(line)
    if not m:
        return None
    return tuple((int(a), int(b)) for a, b in _PAIR_RE.findall(m.group(1)))


def _mesh_axis_groups(mesh):
    """``{axes_tuple: frozenset of frozensets of flat ids}`` for every
    single mesh axis and every axis pair: the partition-id groups a
    collective communicating over exactly those axes would carry (XLA's
    partition ids follow the mesh's flat device order)."""
    shape = tuple(mesh.devices.shape)
    names = tuple(mesh.axis_names)
    idx = np.arange(int(np.prod(shape)) or 1).reshape(shape)
    out = {}
    for r in (1, 2):
        for combo in itertools.combinations(range(len(names)), r):
            others = [a for a in range(len(names)) if a not in combo]
            t = np.transpose(idx, others + list(combo))
            gsize = int(np.prod([shape[a] for a in combo]) or 1)
            rows = t.reshape(-1, gsize)
            out[tuple(names[a] for a in combo)] = frozenset(
                frozenset(int(x) for x in row) for row in rows)
    return out


def _axes_of_groups(groups, axis_groups):
    """Mesh axis names a collective's replica groups communicate over
    (smallest matching axis set wins); None when nothing matches."""
    if not groups:
        return None
    gset = frozenset(frozenset(g) for g in groups)
    best = None
    for axes, expected in axis_groups.items():
        if expected == gset and (best is None or len(axes) < len(best)):
            best = axes
    return best


def _axis_of_pairs(pairs, mesh):
    """Mesh axis a collective-permute's source→target pairs move along:
    every pair must differ in exactly one (and the same) mesh
    coordinate."""
    if not pairs or mesh is None:
        return None
    shape = tuple(mesh.devices.shape)
    names = tuple(mesh.axis_names)
    axis = None
    for s, t in pairs:
        try:
            cs = np.unravel_index(s, shape)
            ct = np.unravel_index(t, shape)
        except ValueError:
            return None
        diff = [i for i in range(len(shape)) if cs[i] != ct[i]]
        if len(diff) != 1:
            return None
        if axis is None:
            axis = diff[0]
        elif axis != diff[0]:
            return None
    return (names[axis],) if axis is not None else None


def _wire_bytes(kind, payload, group_size):
    """Predicted on-the-wire bytes per device for one execution of a
    collective (ring-algorithm costs): all-reduce moves 2(n-1)/n x its
    payload, all-gather/all-to-all (n-1)/n x the gathered result,
    reduce-scatter (n-1) x its (scattered) result, collective-permute
    exactly its payload (one hop). An UNKNOWN group size (groups the
    parser could not attribute, no mesh to default against) charges one
    full payload rather than zero — a collective that exists moves bytes,
    and pricing it at 0 would silently disarm the comms-bound roofline
    for exactly the instructions we understand least."""
    if group_size is None:
        return payload
    n = group_size
    if n <= 1:
        return 0 if kind != "collective-permute" else payload
    if kind == "all-reduce":
        return int(2 * (n - 1) * payload / n)
    if kind in ("all-gather", "all-to-all", "collective-broadcast"):
        return int((n - 1) * payload / n)
    if kind == "reduce-scatter":
        return int((n - 1) * payload)
    return payload  # collective-permute


class CollectiveEntry(object):
    """One collective instruction of the scheduled partitioned HLO."""

    __slots__ = ("instruction", "kind", "bytes", "wire_bytes", "group_size",
                 "axes", "groups", "in_loop", "multiplier", "op_path",
                 "provenance", "operand_params")

    def __init__(self, instruction, kind, nbytes, wire_bytes, group_size,
                 axes, groups, in_loop, multiplier, op_path, provenance,
                 operand_params=()):
        self.instruction = instruction
        self.kind = kind
        self.bytes = int(nbytes)
        self.wire_bytes = int(wire_bytes)
        self.group_size = group_size
        #: mesh axis names the groups communicate over (None = unknown)
        self.axes = axes
        self.groups = groups
        #: inside the compiled while body: runs K times per dispatch
        self.in_loop = bool(in_loop)
        #: executions per dispatch (loop trips when in_loop, else 1)
        self.multiplier = int(multiplier)
        self.op_path = op_path
        self.provenance = provenance
        #: entry-parameter labels this collective consumes DIRECTLY (a
        #: non-empty list means a declared input sharding is re-laid-out)
        self.operand_params = list(operand_params)

    def as_dict(self):
        return {
            "instruction": self.instruction, "kind": self.kind,
            "bytes": self.bytes, "wire_bytes": self.wire_bytes,
            "group_size": self.group_size,
            "axes": list(self.axes) if self.axes else None,
            "in_loop": self.in_loop, "multiplier": self.multiplier,
            "op_path": self.op_path, "provenance": self.provenance,
            "operand_params": list(self.operand_params),
        }

    def format(self):
        where = self.op_path or self.instruction
        if self.provenance:
            where += " @ " + self.provenance
        ax = "axes=%s" % ",".join(self.axes) if self.axes else "axes=?"
        return ("%10s x%-3d %-18s %-12s %s"
                % (_fmt_bytes(self.bytes), self.multiplier, self.kind,
                   ax, where))

    def __repr__(self):
        return "CollectiveEntry(%s)" % self.format()


def parse_collectives(hlo_text, mesh=None, loop_trips=1):
    """Walk the scheduled partitioned HLO text and return the collective
    inventory: one :class:`CollectiveEntry` per collective instruction
    (``-start``/``-done`` async pairs counted once), with payload bytes
    from the result type (tuple types — combined all-reduces, tiled
    all-to-alls — summed), mesh-axis attribution from the replica groups
    against ``mesh``'s device grid, the in-loop flag from the ``op_name``
    metadata (``/while/`` path = the scan body, runs ``loop_trips`` times
    per dispatch), op path and source provenance, and the entry-parameter
    labels of directly-consumed arguments (the ``resharding-copy``
    evidence)."""
    axis_groups = _mesh_axis_groups(mesh) if mesh is not None else {}
    lines = hlo_text.splitlines()  # multi-MB text: split once, scan thrice
    frames = parse_stack_frames(hlo_text)
    # entry-computation parameter instruction names -> op_name label
    entry_params = {}
    in_entry = False
    for line in lines:
        if _ENTRY_RE.match(line):
            in_entry = True
            continue
        if in_entry and _COMP_END_RE.match(line):
            in_entry = False
            continue
        if not in_entry:
            continue
        pm = _PARAM_RE.match(line)
        if pm:
            op = _OPNAME_RE.search(line)
            entry_params[pm.group("instr")] = (
                _unescape(op.group(1)) if op else pm.group("instr"))
    # async retire halves: start-instruction name -> true result type
    done_types = {}
    for line in lines:
        dm = _DONE_RE.match(line)
        if dm:
            done_types[dm.group("operand")] = dm.group("type")
    entries = []
    for line in lines:
        m = _COLL_RE.match(line)
        if not m:
            continue
        kind = m.group("kind")
        type_str = m.group("type")
        if m.group("start"):
            # an async -start's own result type bundles operands next to
            # results ((f32[shard], f32[full]) for all-gather-start, plus
            # context scalars for collective-permute-start): prefer the
            # matching -done's single result type; fall back to the
            # largest tuple element rather than the double-counting sum
            done = done_types.get(m.group("instr"))
            if done is not None:
                type_str = done
            elif type_str.startswith("("):
                parts = _SHAPE_RE.findall(type_str)
                if parts:
                    best = max(parts,
                               key=lambda p: _shape_bytes(p[0], p[1]))
                    type_str = "%s[%s]" % best
        payload = _type_bytes(type_str)
        groups = _parse_groups(line)
        pairs = _parse_pairs(line) if kind == "collective-permute" else None
        if groups:  # non-empty parsed groups
            gsize = max(len(g) for g in groups)
            axes = _axes_of_groups(groups, axis_groups)
        elif pairs is not None:
            gsize = None
            axes = _axis_of_pairs(pairs, mesh)
        elif mesh is not None:
            # the bare replica_groups={} spelling (groups == ()) — and a
            # group collective with no parseable attribute — mean every
            # partition participates: default the group to the whole mesh
            # instead of silently pricing the collective at zero wire
            gsize = int(mesh.devices.size)
            axes = tuple(mesh.axis_names) if groups == () else None
        else:
            gsize = None
            axes = None
        op = _OPNAME_RE.search(line)
        op_path = _unescape(op.group(1)) if op else None
        prov = instr_provenance(line, frames)
        in_loop = bool(op_path and "/while/" in op_path)
        # direct operands that are entry parameters: the operand list runs
        # from the opcode's "(" to its matching close — collectives take
        # plain array operands, so the first ")" ends it
        operand_seg = line[m.end():].split(")", 1)[0]
        consumed = [entry_params[nm]
                    for nm in re.findall(r"%([\w.\-]+)", operand_seg)
                    if nm in entry_params]
        entries.append(CollectiveEntry(
            m.group("instr"), kind, payload,
            _wire_bytes(kind, payload, gsize), gsize, axes, groups,
            in_loop, loop_trips if in_loop else 1, op_path, prov,
            operand_params=consumed))
    entries.sort(key=lambda e: e.bytes * e.multiplier, reverse=True)
    return entries


# ---------------------------------------------------------------------------
# the report + roofline
# ---------------------------------------------------------------------------

class CommsReport(object):
    """Static communication profile of ONE compiled partitioned program.

    ``collective_count`` / ``collective_bytes`` are PER-DISPATCH totals
    (in-loop entries multiplied by the loop trip count) — the two numbers
    the baseline drift gate pins. The roofline fields predict one
    iteration: ``comm_seconds`` spreads outside-loop wire bytes over the
    trips, ``compute_seconds`` is the XLA cost-model FLOPs (which counts
    a while body once) over the device-kind peak, and
    ``predicted_efficiency = compute / (compute + comm)`` — the
    zero-overlap scaling-efficiency bound the multichip gate compares
    against its measurement."""

    __slots__ = ("program", "platform", "n_devices", "entries",
                 "loop_trips", "flops", "link_bytes_per_s",
                 "peak_flops_per_s", "hlo_unavailable")

    def __init__(self, program, platform, n_devices, entries, loop_trips=1,
                 flops=None, link_bytes_per_s=None, peak_flops_per_s=None,
                 hlo_unavailable=False):
        self.program = program
        self.platform = platform
        self.n_devices = int(n_devices)
        self.entries = list(entries)
        self.loop_trips = max(1, int(loop_trips))
        self.flops = None if flops is None else float(flops)
        self.link_bytes_per_s = (link_bandwidth() if link_bytes_per_s is None
                                 else float(link_bytes_per_s))
        self.peak_flops_per_s = (peak_flops() if peak_flops_per_s is None
                                 else float(peak_flops_per_s))
        #: the executable's HLO text could not be read: the (empty)
        #: inventory is ABSENCE OF EVIDENCE, not a clean audit — the
        #: drift gate fails such programs and the roofline claims nothing
        self.hlo_unavailable = bool(hlo_unavailable)

    @property
    def collective_count(self):
        return sum(e.multiplier for e in self.entries)

    @property
    def collective_bytes(self):
        return sum(e.bytes * e.multiplier for e in self.entries)

    @property
    def wire_bytes(self):
        return sum(e.wire_bytes * e.multiplier for e in self.entries)

    @property
    def comm_seconds(self):
        """Predicted collective seconds per loop iteration (outside-loop
        collectives amortize over the trips)."""
        per_iter = sum(
            e.wire_bytes * (1.0 if e.in_loop else 1.0 / self.loop_trips)
            for e in self.entries)
        return per_iter / self.link_bytes_per_s

    @property
    def compute_seconds(self):
        if self.flops is None:
            return None
        return self.flops / self.peak_flops_per_s

    @property
    def predicted_efficiency(self):
        """Zero-overlap roofline bound on scaling efficiency; 1.0 for a
        collective-free program, None when the cost model reported no
        FLOPs for a program that does communicate — or when the HLO text
        was unavailable (an unreadable program is not a collective-free
        one)."""
        if self.hlo_unavailable:
            return None
        if not self.entries:
            return 1.0
        tc = self.compute_seconds
        if tc is None:
            return None
        comm = self.comm_seconds
        return tc / (tc + comm) if (tc + comm) > 0 else 1.0

    def counts_by_kind(self):
        out = {}
        for e in self.entries:
            out[e.kind] = out.get(e.kind, 0) + e.multiplier
        return out

    def breakdown(self, top=6):
        return [e.format() for e in self.entries[:top]]

    def as_dict(self):
        return {
            "program": self.program,
            "platform": self.platform,
            "n_devices": self.n_devices,
            "hlo_unavailable": self.hlo_unavailable,
            "collective_count": self.collective_count,
            "collective_bytes": self.collective_bytes,
            "wire_bytes": self.wire_bytes,
            "counts_by_kind": self.counts_by_kind(),
            "loop_trips": self.loop_trips,
            "flops": self.flops,
            "predicted_efficiency": self.predicted_efficiency,
            "entries": [e.as_dict() for e in self.entries],
        }

    def format(self):
        eff = self.predicted_efficiency
        return ("%s: %d collective(s)/dispatch, %s payload, predicted "
                "efficiency %s"
                % (self.program, self.collective_count,
                   _fmt_bytes(self.collective_bytes),
                   "?" if eff is None else "%.3f" % eff))

    def __repr__(self):
        return "CommsReport(%s)" % self.format()


def _infer_mesh(args, kwargs=None):
    """First mesh found on any argument leaf's NamedSharding (arguments
    carry the real shardings; the mesh names the axes for
    attribution)."""
    import jax
    for leaf in jax.tree_util.tree_leaves((tuple(args),
                                           dict(kwargs or {}))):
        sh = getattr(leaf, "sharding", None)
        mesh = getattr(sh, "mesh", None)
        if mesh is not None and getattr(mesh, "axis_names", None):
            return mesh
    return None


def struct_args(args):
    """args pytree -> ``ShapeDtypeStruct``s PRESERVING shardings: the
    abstract call signature of a sharded program, safe to build from
    donated (already-deleted) arrays — only metadata is read."""
    import jax

    def to_struct(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            sh = getattr(x, "sharding", None)
            # only MESH-aware shardings are worth pinning: a stray
            # SingleDeviceSharding (e.g. the uncommitted RNG key) pinned
            # into a struct would conflict with the mesh-sharded
            # arguments at lowering — left unspecified, the compiler
            # replicates it like the live dispatch does
            if getattr(getattr(sh, "mesh", None), "axis_names", None):
                try:
                    return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype,
                                                sharding=sh)
                except (TypeError, ValueError):
                    pass
            return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype)
        return x

    return jax.tree_util.tree_map(to_struct, args)


def analyze_compiled(compiled, name, mesh=None, loop_trips=1):
    """Build a :class:`CommsReport` from an ALREADY-compiled program
    (``jax.stages.Compiled`` — e.g. the executable another analyzer just
    read). Never executes anything."""
    import jax
    text_ok = True
    try:
        hlo_text = compiled.as_text()
        if not hlo_text:
            text_ok = False
    except Exception as exc:
        import logging
        logging.warning("commscheck: %s: compiled HLO text unavailable "
                        "(%r) — the inventory is empty for lack of "
                        "EVIDENCE, not because the program is "
                        "collective-free", name, exc)
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
    n_dev = 1
    if mesh is not None:
        n_dev = int(mesh.devices.size)
    entries = parse_collectives(hlo_text, mesh=mesh, loop_trips=loop_trips)
    return CommsReport(name, jax.devices()[0].platform, n_dev, entries,
                       loop_trips=loop_trips, flops=flops,
                       hlo_unavailable=not text_ok)


def analyze(fn, args=(), kwargs=None, name=None, mesh=None, loop_trips=1):
    """Compile ``fn`` (never executed — args may be ``ShapeDtypeStruct``s
    but MUST carry the real shardings: partitioning happens at compile
    time, and unsharded arguments compile an unpartitioned program with
    no collectives at all) and return its :class:`CommsReport`.
    ``mesh`` defaults to the first mesh found on an argument's sharding;
    ``loop_trips`` is the scan depth K — a while-body collective counts
    K executions per dispatch."""
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

def loop_findings(report_or_entries, name, lint="gather-in-loop",
                  allow=DEFAULT_LOOP_ALLOW):
    """In-loop collective findings over an inventory: every while-body
    collective whose kind is not in ``allow``. Shared by this module's
    ``gather-in-loop`` lint and tracecheck's ``collective-in-scan``
    compiled pass (which is a thin alias over this — one collective
    parser for both). Suppressions are NOT applied here; callers do."""
    entries = (report_or_entries.entries
               if isinstance(report_or_entries, CommsReport)
               else report_or_entries)
    findings = []
    for e in entries:
        if not e.in_loop or e.kind in (allow or ()):
            continue
        # only claim a concrete per-dispatch count when the caller told
        # us the trip count — the check_collectives alias analyzes with
        # loop_trips=1 and must not assert a false "x1"
        mult = (", x%d per dispatch" % e.multiplier
                if e.multiplier > 1 else "")
        findings.append(Finding(
            lint, name,
            "compiled program runs %r inside the scan body (%s per "
            "execution%s) — the partitioned K-step dispatch should sync "
            "only by all-reduce (grad + metric psum) and ppermute (the "
            "ring schedule); this collective pays its bandwidth every "
            "loop trip" % (e.kind, _fmt_bytes(e.bytes), mult),
            op_path=e.op_path or "while/body", provenance=e.provenance))
    return findings


def lint_report(report, repl_threshold=None, min_eff=None,
                allow=DEFAULT_LOOP_ALLOW):
    """The four communication lints over one :class:`CommsReport`:
    ``resharding-copy``, ``replicated-large``, ``gather-in-loop``,
    ``comms-bound``. Returns findings with suppressions applied (like
    ``tracecheck.check_program``)."""
    repl_threshold = (repl_bytes() if repl_threshold is None
                      else int(repl_threshold))
    min_eff = min_efficiency() if min_eff is None else float(min_eff)
    name = report.program
    findings = []

    for e in report.entries:
        # resharding-copy: a collective consuming an entry parameter
        # DIRECTLY re-lays-out a declared input sharding before first use
        # (all-reduce excluded: reducing a parameter is an application
        # sum, not a layout change)
        if e.operand_params and e.kind != "all-reduce":
            findings.append(Finding(
                "resharding-copy", name,
                "entry argument %s is re-laid-out by %r (%s%s) before "
                "first use — its declared sharding does not match what "
                "the program computes with; land it pre-sharded (the way "
                "the superbatch H2D does) or fix the declared sharding"
                % (", ".join(repr(p) for p in e.operand_params), e.kind,
                   _fmt_bytes(e.bytes),
                   ", axes " + ",".join(e.axes) if e.axes else ""),
                op_path=e.op_path or e.instruction,
                provenance=e.provenance))
        # replicated-large: an all-gather materializing a buffer this big
        # means every chip in the group holds the full array — a
        # replicated intermediate where a sharded operand existed
        if (e.kind in ("all-gather", "collective-broadcast")
                and e.bytes > repl_threshold):
            findings.append(Finding(
                "replicated-large", name,
                "%r materializes %s replicated%s (> %s, "
                "MXTPU_COMMSCHECK_REPL_BYTES): every chip in the group "
                "holds the full array where a sharded operand existed — "
                "keep it sharded (with_sharding_constraint) or raise the "
                "threshold if replication is intended"
                % (e.kind, _fmt_bytes(e.bytes),
                   " across axis " + ",".join(e.axes) if e.axes else "",
                   _fmt_bytes(repl_threshold)),
                op_path=e.op_path or e.instruction,
                provenance=e.provenance))

    findings += loop_findings(report, name, lint="gather-in-loop",
                              allow=allow)

    eff = report.predicted_efficiency
    if eff is not None and report.entries and eff < min_eff:
        findings.append(Finding(
            "comms-bound", name,
            "predicted scaling efficiency %.3f is below the floor %.2f "
            "(MXTPU_COMMSCHECK_MIN_EFF): predicted compute %.3g s vs "
            "collective %.3g s per iteration at %s/s link bandwidth — "
            "the program is communication-bound before it ever runs. "
            "Inventory:\n  %s"
            % (eff, min_eff, report.compute_seconds, report.comm_seconds,
               _fmt_bytes(int(report.link_bytes_per_s)),
               "\n  ".join(report.breakdown())),
            op_path=(report.entries[0].op_path
                     or report.entries[0].instruction),
            provenance=report.entries[0].provenance))

    for f in findings:
        f.suppressed = _is_suppressed(f)
    return findings


def check_program(fn, args=(), kwargs=None, name=None, mesh=None,
                  loop_trips=1, repl_threshold=None, min_eff=None,
                  allow=DEFAULT_LOOP_ALLOW):
    """Analyze + lint ONE program; returns ``(findings, report)``."""
    report = analyze(fn, args, kwargs=kwargs, name=name, mesh=mesh,
                     loop_trips=loop_trips)
    return lint_report(report, repl_threshold=repl_threshold,
                       min_eff=min_eff, allow=allow), report


# ---------------------------------------------------------------------------
# runtime hook (MXTPU_COMMSCHECK / engine.commscheck_mode)
# ---------------------------------------------------------------------------

#: program names already audited by the dispatch hook — the audit pays
#: one extra compile, so it runs once per compiled program per process
_AUDITED = set()


def maybe_audit_dispatch(name, jitfn, call_args, loop_trips=1, mesh=None):
    """One-time comms audit of a freshly-compiled SHARDED dispatch
    program (``TrainStep`` calls this at first registration when it has
    a mesh): under ``MXTPU_COMMSCHECK=warn`` unsuppressed findings are
    logged, under ``error`` they raise — a gather sneaked into the scan
    body fails at the FIRST dispatch instead of after a slow multichip
    run. Costs one extra compile of the program; ``off`` (the default)
    skips entirely. The call arguments are reduced to sharded
    ``ShapeDtypeStruct``s first, so already-donated buffers are never
    touched."""
    from . import engine
    mode = engine.commscheck_mode()
    if mode == "off" or name in _AUDITED:
        return None
    _AUDITED.add(name)
    # knobs resolve BEFORE the analyzer guard: a malformed env var must
    # propagate as MXNetError instead of silently disarming the gate the
    # operator just configured (memcheck's load-audit hardening)
    repl = repl_bytes()
    floor = min_efficiency()
    try:
        findings, report = check_program(
            jitfn, struct_args(tuple(call_args)), name=name, mesh=mesh,
            loop_trips=loop_trips, repl_threshold=repl, min_eff=floor)
    except Exception as exc:
        import logging
        logging.warning("commscheck: dispatch audit of %s failed (%r) — "
                        "skipping", name, exc)
        return None
    if report.hlo_unavailable:
        # the armed gate must not pass vacuously: no HLO text means NO
        # audit ran (same contract as the CLI / baseline / multichip
        # consumers of this flag)
        msg = ("commscheck: compiled HLO text unavailable for %s — the "
               "MXTPU_COMMSCHECK audit could not run" % name)
        if mode == "error":
            raise MXNetError(msg)
        import logging
        logging.warning(msg)
        return report
    bad = unsuppressed(findings)
    if bad:
        msg = ("commscheck: %d finding(s) on sharded program %s "
               "(MXTPU_COMMSCHECK):\n%s"
               % (len(bad), name, "\n".join(f.format() for f in bad)))
        if mode == "error":
            raise MXNetError(msg)
        import logging
        logging.warning(msg)
    return report


# ---------------------------------------------------------------------------
# TrainStep / zoo / sharded-set auditing
# ---------------------------------------------------------------------------

def check_train_step(ts, data_shapes, label_shapes, k=2, guard=True,
                     name=None, repl_threshold=None, min_eff=None):
    """Comms-audit a :class:`~mxnet_tpu.train_step.TrainStep`'s full
    program set (``tracecheck.train_step_programs`` — THE shared recipe,
    so the three analyzers can never drift apart on program shape).
    Returns ``(findings, reports)``. Single-device program sets carry no
    collectives — their inventory pins ZERO in the baseline, so a
    refactor that makes a nominally-local program communicate fails the
    drift gate."""
    from .tracecheck import train_step_programs
    name = name or "TrainStep(%s)" % ts.symbol.name
    findings = []
    reports = {}
    for pname, jitfn, pargs in train_step_programs(
            ts, data_shapes, label_shapes, k=k, guard=guard, name=name):
        trips = k if "/scan[" in pname or "-scan[" in pname else 1
        fs, rep = check_program(jitfn, pargs, name=pname, mesh=ts.mesh,
                                loop_trips=trips,
                                repl_threshold=repl_threshold,
                                min_eff=min_eff)
        findings += fs
        reports[pname] = rep
    return findings, reports


def check_zoo(names=None, k=2, guard=True, repl_threshold=None,
              min_eff=None, log=None):
    """Comms-audit the model zoo's step programs (same configs as
    ``tracecheck.ZOO``); returns ``(findings, reports)``."""
    from .tracecheck import zoo_train_step
    names = list(names) if names else sorted(ZOO)
    findings = []
    reports = {}
    for mname in names:
        if mname not in ZOO:
            raise MXNetError("commscheck: unknown zoo model %r (have %s)"
                             % (mname, ", ".join(sorted(ZOO))))
        if log:
            log("commscheck: analyzing %s ..." % mname)
        ts, data_shapes, label_shapes = zoo_train_step(mname)
        fs, reps = check_train_step(
            ts, data_shapes, label_shapes,
            k=k, guard=guard, name=mname, repl_threshold=repl_threshold,
            min_eff=min_eff)
        findings += fs
        reports.update(reps)
    return findings, reports


def _sds(shape, dtype, sharding=None):
    import jax
    if sharding is None:
        return jax.ShapeDtypeStruct(tuple(shape), dtype)
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def sharded_programs(n_devices=8, k=2):
    """The PR 7 sharded gate program set (docs/perf.md "Data-parallel
    scaling"), as ``(name, jitfn, args, loop_trips, mesh, scope_mesh)``
    tuples with arguments carrying REAL shardings:

    * ``dp8/lenet/scan[k=2]`` — the fused K-step scan over an 8-way
      'data' mesh (the multichip gate's measured workload: in-scan grad
      psum, pre-sharded superbatch, replicated params);
    * ``dp4xtp2/resnet18/step`` — the fused step over data x model with
      the classifier FC tensor-parallel;
    * ``dp4xsp2/transformer-ring/step`` — the ring-attention transformer
      over data x seq (ppermute ring in the attention body).

    ``scope_mesh`` (when set) must be entered as the ambient
    ``MeshScope`` while tracing — the attention op resolves its 'seq'
    axis from it."""
    import jax
    from jax.sharding import Mesh, NamedSharding
    from . import models
    from .train_step import TrainStep
    from .parallel.mesh import data_parallel_mesh, MeshScope
    P = jax.sharding.PartitionSpec
    devices = jax.devices()
    if len(devices) < n_devices:
        raise MXNetError(
            "commscheck --sharded needs %d devices but only %d are "
            "visible — on CPU raise the count with XLA_FLAGS="
            "--xla_force_host_platform_device_count=%d"
            % (n_devices, len(devices), n_devices))
    f32 = np.float32
    progs = []

    def state_structs(ts, data_shapes, label_shapes):
        state = ts.init(data_shapes, label_shapes,
                        initializer=lambda desc, arr: None, seed=0)
        return struct_args(state)

    # 1) dp lenet fused scan — the measured multichip workload
    mesh = data_parallel_mesh(n_devices)
    batch = 64
    ts = TrainStep(models.lenet(num_classes=10), optimizer="sgd",
                   learning_rate=0.1, momentum=0.9, mesh=mesh)
    st = state_structs(ts, {"data": (batch, 1, 28, 28)},
                       {"softmax_label": (batch,)})
    sb_shard = NamedSharding(mesh, P(None, "data"))
    repl = NamedSharding(mesh, P())
    sb = {"data": _sds((k, batch, 1, 28, 28), f32, sb_shard),
          "softmax_label": _sds((k, batch), f32, sb_shard)}
    progs.append(("dp%d/lenet/scan[k=%d]" % (n_devices, k),
                  ts._build_scan(batch, k),
                  (st, sb, ts._dispatch_key(), _sds((k,), f32, repl)),
                  k, mesh, None))

    # 2) resnet18 dp x tp fused step — classifier FC tensor-parallel
    tp = 2 if n_devices % 2 == 0 else 1
    dp = n_devices // tp
    mesh2 = Mesh(np.array(devices[:n_devices]).reshape(dp, tp),
                 ("data", "model"))
    ts2 = TrainStep(models.resnet(num_classes=64, num_layers=18,
                                  image_shape="3,32,32"),
                    optimizer="sgd", learning_rate=0.1, momentum=0.9,
                    mesh=mesh2,
                    param_shardings={"fc1_weight": P("model", None),
                                     "fc1_bias": P("model")})
    b2 = 2 * dp
    st2 = state_structs(ts2, {"data": (b2, 3, 32, 32)},
                        {"softmax_label": (b2,)})
    dsh = NamedSharding(mesh2, P("data"))
    batch2 = {"data": _sds((b2, 3, 32, 32), f32, dsh),
              "softmax_label": _sds((b2,), f32, dsh)}
    progs.append(("dp%dxtp%d/resnet18/step" % (dp, tp), ts2._build(b2),
                  (st2, batch2, ts2._dispatch_key(),
                   _sds((), f32, NamedSharding(mesh2, P()))),
                  1, mesh2, None))

    # 3) ring-attention transformer dp x sp fused step
    sp = max(n_devices // dp, 1)
    mesh3 = Mesh(np.array(devices[:n_devices]).reshape(dp, sp),
                 ("data", "seq"))
    seq_len = 8 * sp
    sym3 = models.transformer(vocab_size=64, embed=32, num_heads=4,
                              num_layers=2, seq_len=seq_len,
                              seq_parallel="ring")
    with MeshScope(mesh3):
        ts3 = TrainStep(sym3, optimizer="sgd", learning_rate=0.1,
                        mesh=mesh3)
        b3 = 2 * dp
        st3 = state_structs(ts3, {"data": (b3, seq_len)},
                            {"softmax_label": (b3, seq_len)})
    bsh = NamedSharding(mesh3, P("data", "seq"))
    batch3 = {"data": _sds((b3, seq_len), f32, bsh),
              "softmax_label": _sds((b3, seq_len), f32, bsh)}
    progs.append(("dp%dxsp%d/transformer-ring/step" % (dp, sp),
                  ts3._build(b3),
                  (st3, batch3, ts3._dispatch_key(),
                   _sds((), f32, NamedSharding(mesh3, P()))),
                  1, mesh3, mesh3))

    # 4) the flagship-LM multi-axis fused K-step scan (docs/perf.md
    # "Flagship LM"): the dp x sp ring transformer with the rank-3
    # preserve_shape head through the scan path Module.fit dispatches —
    # in-scan grad psum over 'data' composed with the ppermute ring over
    # 'seq', carry pinned by the jit-root state out_shardings, and no
    # batch x seq dim merge anywhere (the flat head's reshape would pay
    # an all-gather over 'seq' every trip)
    sym4 = models.transformer(vocab_size=64, embed=32, num_heads=4,
                              num_layers=2, seq_len=seq_len,
                              seq_parallel="ring", preserve_shape=True)
    with MeshScope(mesh3):
        # pos_embed rows live with their 'seq' shard — replicated, the
        # naturally seq-sharded grad would all-gather every trip in the
        # optimizer update
        ts4 = TrainStep(sym4, optimizer="sgd", learning_rate=0.1,
                        mesh=mesh3,
                        param_shardings={"pos_embed_weight":
                                         P("seq", None)})
        st4 = state_structs(ts4, {"data": (b3, seq_len)},
                            {"softmax_label": (b3, seq_len)})
        scan4 = ts4._build_scan(b3, k, state=st4)
    sbsh = NamedSharding(mesh3, P(None, "data", "seq"))
    sb4 = {"data": _sds((k, b3, seq_len), f32, sbsh),
           "softmax_label": _sds((k, b3, seq_len), f32, sbsh)}
    progs.append(("dp%dxsp%d/transformer-ring/scan[k=%d]" % (dp, sp, k),
                  scan4,
                  (st4, sb4, ts4._dispatch_key(),
                   _sds((k,), f32, NamedSharding(mesh3, P()))),
                  k, mesh3, mesh3))
    return progs


def check_sharded(n_devices=8, k=2, repl_threshold=None, min_eff=None,
                  log=None):
    """Comms-audit the sharded gate program set; returns ``(findings,
    reports)``."""
    import contextlib
    from .parallel.mesh import MeshScope
    findings = []
    reports = {}
    for name, jitfn, args, trips, mesh, scope in sharded_programs(
            n_devices=n_devices, k=k):
        if log:
            log("commscheck: analyzing %s ..." % name)
        ambient = (MeshScope(scope) if scope is not None
                   else contextlib.nullcontext())
        with ambient:
            fs, rep = check_program(jitfn, args, name=name, mesh=mesh,
                                    loop_trips=trips,
                                    repl_threshold=repl_threshold,
                                    min_eff=min_eff)
        findings += fs
        reports[name] = rep
    return findings, reports


# ---------------------------------------------------------------------------
# the baseline drift gate (ci/commscheck.sh)
# ---------------------------------------------------------------------------

#: metrics the baseline pins per program — HLO-deterministic counts, so
#: unlike memcheck's byte bands there is NO absolute slack: a collective
#: appearing where the baseline pinned zero fails at any tolerance
_BASELINE_METRICS = ("collective_count", "collective_bytes")


def write_baseline(reports, path, tol=None):
    """Write the committed baseline: per-program collective count/bytes,
    keyed by platform (a CPU baseline must not gate a TPU run). Refuses
    evidence-free reports — committing a fabricated zero for a program
    whose HLO text could not be read would pin the drift gate on
    nothing."""
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
            name: {m: int(getattr(rep, m)) for m in _BASELINE_METRICS}
            for name, rep in sorted(reports.items())},
    }
    atomic_write_bytes(path, (json.dumps(data, indent=2, sort_keys=True)
                              + "\n").encode())
    return data


def compare_baseline(reports, baseline, tol=None):
    """The drift gate: compare every report against the committed
    baseline. Returns ``(failures, notes)`` — a program whose collective
    count or payload bytes grew past the tolerance band fails WITH its
    inventory breakdown (byte counts + source provenance); a program
    missing from the baseline fails too (new programs are added
    deliberately). Shrinks and stale entries are notes; a
    platform-mismatched baseline skips the gate with one note."""
    import jax
    if isinstance(baseline, str):
        with open(baseline) as f:
            baseline = json.load(f)
    if tol is None:
        # precedence: explicit arg > MXTPU_COMMSCHECK_TOL env > the
        # baseline's stored band > 0.1 (memcheck's hardened ordering)
        from .base import env_float
        tol = env_float("MXTPU_COMMSCHECK_TOL",
                        float(baseline.get("tolerance", 0.1)))
    else:
        tol = float(tol)
    platform = jax.devices()[0].platform
    failures, notes = [], []
    if baseline.get("platform") != platform:
        notes.append(
            "commscheck baseline was written on platform %r but this run "
            "is %r — skipping the drift gate (re-run --write-baseline on "
            "this platform to arm it)"
            % (baseline.get("platform"), platform))
        return failures, notes
    base_progs = dict(baseline.get("programs") or {})
    for name, rep in sorted(reports.items()):
        base = base_progs.pop(name, None)
        if getattr(rep, "hlo_unavailable", False):
            # no HLO text = no evidence: the gate must not read the empty
            # inventory as a clean (or nicely-shrunk) audit
            failures.append(
                "%s: compiled HLO text unavailable on this backend — the "
                "collective inventory could not be audited; the drift "
                "gate refuses to pass on absence of evidence" % name)
            continue
        if base is None:
            failures.append(
                "%s: not in the baseline — a new program must be added "
                "deliberately (run `python -m mxnet_tpu.commscheck --zoo "
                "--sharded --write-baseline COMMSCHECK_baseline.json` and "
                "commit the diff)" % name)
            continue
        for metric in _BASELINE_METRICS:
            b = int(base.get(metric, 0))
            cur = int(getattr(rep, metric))
            allowed = b + int(b * tol)
            if cur > allowed:
                breakdown = "\n  ".join(rep.breakdown()) or "(empty)"
                failures.append(
                    "%s: %s grew %d -> %d (tolerance %.0f%%, "
                    "MXTPU_COMMSCHECK_TOL) — a collective was added or "
                    "its payload grew. Inventory:\n  %s"
                    % (name, metric, b, cur, 100.0 * tol, breakdown))
            elif cur == 0 and b > 0:
                # a nonzero-pinned program collapsing to ZERO collectives
                # is indistinguishable from a parser/HLO-format
                # regression that blinded the whole audit — fail, don't
                # note; a real de-communication is locked in deliberately
                # via --write-baseline
                failures.append(
                    "%s: %s collapsed %d -> 0 — either the program "
                    "genuinely stopped communicating (refresh the "
                    "baseline deliberately) or the HLO parser went blind "
                    "(an XLA text-format drift); the gate refuses to "
                    "treat a total collapse as a win" % (name, metric, b))
            elif cur < b - int(b * tol) and b > 0:
                notes.append(
                    "%s: %s shrank %d -> %d — nice; refresh the baseline "
                    "to lock the win in" % (name, metric, b, cur))
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
    out.write("%-*s  %6s %12s %12s %8s\n"
              % (w, "program", "coll", "payload", "wire", "pred-eff"))
    for name in sorted(reports):
        r = reports[name]
        eff = r.predicted_efficiency
        out.write("%-*s  %6d %12s %12s %8s\n"
                  % (w, name, r.collective_count,
                     _fmt_bytes(r.collective_bytes),
                     _fmt_bytes(r.wire_bytes),
                     "?" if eff is None else "%.3f" % eff))


def main(argv=None):
    import argparse
    import sys
    from . import tracecheck as _tc
    p = argparse.ArgumentParser(
        prog="python -m mxnet_tpu.commscheck",
        description="Static collective-communication analyzer: per-program"
                    " collective inventory (kind/axes/bytes/loop"
                    " multiplier), resharding/replication/in-loop-gather"
                    " lints, a comms roofline, and the baseline drift gate"
                    " (docs/static_analysis.md \"Communication lints\").")
    p.add_argument("--zoo", action="store_true",
                   help="analyze every shipped model's step/scan programs")
    p.add_argument("--models", default=None,
                   help="comma-separated zoo subset (implies --zoo)")
    p.add_argument("--sharded", action="store_true",
                   help="also analyze the PR 7 sharded gate set (dp lenet "
                        "scan, dp x tp resnet18, dp x sp ring transformer;"
                        " needs 8 visible devices)")
    p.add_argument("--devices", type=int, default=8,
                   help="device count for --sharded (default 8)")
    p.add_argument("--k", type=int, default=2,
                   help="scan depth for the K-step programs (default 2)")
    p.add_argument("--no-guard", action="store_true",
                   help="skip the guarded program variants")
    p.add_argument("--repl-bytes", default=None,
                   help="replicated-large threshold (K/M/G/T suffixes ok; "
                        "default MXTPU_COMMSCHECK_REPL_BYTES or 1 MiB)")
    p.add_argument("--min-eff", type=float, default=None,
                   help="comms-bound efficiency floor (default "
                        "MXTPU_COMMSCHECK_MIN_EFF or 0.5)")
    p.add_argument("--baseline", default=None, metavar="FILE",
                   help="compare against a committed baseline (the CI "
                        "drift gate); exit non-zero on collective "
                        "count/byte growth past tolerance")
    p.add_argument("--write-baseline", default=None, metavar="FILE",
                   help="write the per-program baseline JSON and exit 0 "
                        "(refreshing the baseline is a deliberate act)")
    p.add_argument("--tol", type=float, default=None,
                   help="baseline tolerance band (default "
                        "MXTPU_COMMSCHECK_TOL, the baseline's own, or "
                        "0.1)")
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
    repl = (None if args.repl_bytes is None
            else _parse_bytes(args.repl_bytes, "--repl-bytes"))
    findings, reports = [], {}
    if args.zoo or args.models:
        findings, reports = check_zoo(names=names, k=args.k,
                                      guard=not args.no_guard,
                                      repl_threshold=repl,
                                      min_eff=args.min_eff, log=log)
    if args.sharded:
        fs, reps = check_sharded(n_devices=args.devices, k=args.k,
                                 repl_threshold=repl,
                                 min_eff=args.min_eff, log=log)
        findings += fs
        reports.update(reps)
    if args.write_baseline:
        write_baseline(reports, args.write_baseline, tol=args.tol)
        log("commscheck: baseline written to %s (%d programs)"
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
    bad = unsuppressed(findings)
    if args.json:
        import jax
        print(json.dumps({
            "platform": jax.devices()[0].platform,
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
        print("commscheck: %d finding(s) (%d suppressed), %d baseline "
              "regression(s) over %d program(s)"
              % (len(findings), len(findings) - len(bad), len(failures),
                 len(reports)))
    return 1 if (bad or failures) else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
