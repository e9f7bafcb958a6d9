#!/usr/bin/env python
"""Itemized HBM byte budget of the compiled ResNet train step.

VERDICT r4 asked for the roofline *argument* to become an *artifact*: a
per-buffer table showing which tensors account for the step's HBM traffic
(the reference's analog is the memory section of docs/how_to/perf.md plus
the memonger study; here the source of truth is XLA itself).

Method: lower+compile the ResNet train step, then walk the
optimized HLO ENTRY computation. Every top-level instruction materializes
its output in HBM and reads its operands from HBM (internals of a fusion
are VMEM/register-resident and never touch HBM), so

    traffic(instr) = bytes(output) + sum(bytes(operands))

with bytes() honoring the TPU tiling annotation (e.g. ``{3,2,1,0:T(8,128)}``
pads the two minor dims). Attribution comes from the ``op_name`` metadata
that the op library threads through ``jax.named_scope`` — the same plumbing
the profiler uses — so each HLO fusion maps back to a framework op.

Outputs a markdown table (top-N instructions by traffic), per-framework-op
rollup, totals, and XLA's own aggregate memory/cost analysis for
cross-checking. Copy the tables into docs/perf.md.

Usage: python tools/byte_budget.py [--batch 128] [--top 15] [--dtype bfloat16]
"""
import argparse
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

# one HLO shape like  bf16[128,256,56,56]{3,2,1,0:T(8,128)(2,1)}
_SHAPE_RE = re.compile(
    r"(?P<dt>%s)\[(?P<dims>[\d,]*)\]"
    r"(?:\{(?P<layout>[\d,]*)(?::(?P<tiles>[^}]*))?\})?"
    % "|".join(_DTYPE_BYTES))
_TILE_RE = re.compile(r"T\((\d+),(\d+)\)")


def shape_bytes(m):
    """Physical bytes of one parsed shape, honoring minor-dim tiling pads.

    Shapes annotated with a memory space ``S(n)`` live outside default HBM
    (S(1) = VMEM/scoped prefetch destinations, S(2) = sync flags) — they
    count zero here; their HBM side is charged at the copy/slice-start that
    filled them."""
    dt = m.group("dt")
    dims_s = m.group("dims")
    dims = [int(d) for d in dims_s.split(",") if d] if dims_s else []
    tiles_all = m.group("tiles") or ""
    if "S(" in tiles_all:
        return 0
    if not dims:
        return _DTYPE_BYTES[dt]
    layout = m.group("layout")
    tiles = tiles_all
    tm = _TILE_RE.search(tiles)
    phys = list(dims)
    if tm and layout:
        # layout lists minor-to-major dim ids; tile pads the two minor dims
        order = [int(x) for x in layout.split(",") if x]
        t_sub, t_lane = int(tm.group(1)), int(tm.group(2))
        if len(order) >= 1:
            lane = order[0]
            phys[lane] = -(-phys[lane] // t_lane) * t_lane
        if len(order) >= 2:
            sub = order[1]
            phys[sub] = -(-phys[sub] // t_sub) * t_sub
    n = 1
    for d in phys:
        n *= d
    return n * _DTYPE_BYTES[dt]


def all_shapes_bytes(text):
    """Sum bytes over every shape in a type string (handles tuples)."""
    return sum(shape_bytes(m) for m in _SHAPE_RE.finditer(text))


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<type>\(?.*?\)?)\s+"
    r"(?P<op>[\w\-]+)\((?P<rest>.*)$")
_META_RE = re.compile(r'op_name="([^"]*)"')
_OPERAND_RE = re.compile(r"%?([\w.\-]+)")


def parse_entry(hlo_text):
    """Yield (name, opkind, out_bytes, operand_names, op_name_meta) for each
    instruction in the ENTRY computation."""
    lines = hlo_text.splitlines()
    in_entry = False
    depth = 0
    shapes = {}  # instr name -> output bytes (from its definition line)
    instrs = []
    for ln in lines:
        if ln.startswith("ENTRY "):
            in_entry = True
            depth = ln.count("{") - ln.count("}")
            continue
        if not in_entry:
            continue
        depth += ln.count("{") - ln.count("}")
        if depth < 0:
            break
        m = _INSTR_RE.match(ln)
        if not m:
            continue
        name, opkind = m.group("name"), m.group("op")
        out_b = all_shapes_bytes(m.group("type"))
        shapes[name] = out_b
        # operands: %-prefixed refs in the call args before any attribute
        rest = m.group("rest")
        args = rest.split("),", 1)[0]
        opnames = [x for x in _OPERAND_RE.findall(args) if x in shapes]
        meta = _META_RE.search(ln)
        instrs.append((name, opkind, out_b, opnames,
                       meta.group(1) if meta else ""))
    return instrs, shapes


# HLO ops that never move HBM bytes themselves. ``*-done`` halves of async
# pairs are also free (traffic charged at the ``*-start``).
_FREE_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "bitcast-convert", "after-all", "partition-id",
             "replica-id", "iota"}


def scope_of(op_name_meta):
    """Collapse a jax op_name path to the framework-level scope."""
    if not op_name_meta:
        return "(unattributed)"
    parts = [p for p in op_name_meta.split("/") if p and p != "jit(step_fn)"]
    # keep transpose marker + first named scope under it
    keep = []
    for p in parts:
        if p.startswith("jit("):
            continue
        keep.append(p)
        if len(keep) >= 2:
            break
    return "/".join(keep) if keep else "(unattributed)"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--depth", type=int, default=50)
    ap.add_argument("--image", type=int, default=224)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--storage-dtype", default="float32")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--layout", default="NCHW")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from mxnet_tpu import models
    from mxnet_tpu.train_step import TrainStep

    batch, image = args.batch, args.image
    dshape = ((batch, image, image, 3) if args.layout == "NHWC"
              else (batch, 3, image, image))
    sym = models.resnet(num_classes=1000, num_layers=args.depth,
                        image_shape="3,%d,%d" % (image, image),
                        layout=args.layout)
    step = TrainStep(sym, optimizer="sgd", learning_rate=0.1, momentum=0.9,
                     wd=1e-4, dtype=args.storage_dtype,
                     compute_dtype=None if args.dtype == "float32"
                     else args.dtype)
    state = step.init({"data": dshape}, {"softmax_label": (batch,)})
    rng = np.random.default_rng(0)
    data = {"data": jnp.asarray(rng.normal(size=dshape), np.float32),
            "softmax_label": jnp.asarray(rng.integers(0, 1000, batch),
                                         np.float32)}
    jitted = step._build(batch)
    lowered = jitted.lower(state, data, jax.random.key(0),
                           jnp.asarray(0.1, jnp.float32))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    instrs, _shapes = parse_entry(hlo)

    rows = []
    by_scope = collections.Counter()
    shapes = {}
    for name, opkind, out_b, opnames, meta in instrs:
        if opkind.endswith("-done"):
            # async pair: HBM read was charged at the -start; the S(1)
            # destination is not HBM. Result consumed from VMEM is free.
            shapes[name] = 0
            continue
        shapes[name] = out_b
        if opkind in _FREE_OPS:
            continue
        in_b = sum(shapes.get(o, 0) for o in opnames)
        if opkind.endswith("-start"):
            total = in_b  # HBM read side of the async copy/slice
            out_b = 0
        else:
            total = out_b + in_b
        rows.append((total, out_b, in_b, opkind, meta, name))
        scope = scope_of(meta)
        if not meta and ("copy" in opkind or opkind.endswith("-start")):
            scope = "(layout/prefetch copies)"
        by_scope[scope] += total
    rows.sort(reverse=True)
    grand = sum(r[0] for r in rows)

    print("## Per-instruction HBM traffic (top %d), b%d %s %s"
          % (args.top, batch, args.dtype, args.layout))
    print()
    print("| MB moved | out MB | in MB | HLO op | framework op |")
    print("|---:|---:|---:|---|---|")
    for total, out_b, in_b, opkind, meta, name in rows[:args.top]:
        print("| %.1f | %.1f | %.1f | %s | %s |"
              % (total / 1e6, out_b / 1e6, in_b / 1e6, opkind,
                 scope_of(meta) or name))
    print()
    print("## Rollup by framework op (top %d)" % args.top)
    print()
    print("| MB moved | MB/image | share | scope |")
    print("|---:|---:|---:|---|")
    for scope, b in by_scope.most_common(args.top):
        print("| %.1f | %.2f | %.1f%% | %s |"
              % (b / 1e6, b / 1e6 / batch, 100.0 * b / grand, scope))
    print()
    total_mb = grand / 1e6
    print("entry-instruction traffic (upper bound: assumes zero inter-op "
          "HBM reuse): %.1f MB/step = %.1f MB/image" % (total_mb,
                                                        total_mb / batch))
    try:
        ma = compiled.memory_analysis()
        print("XLA memory_analysis: args=%.1f MB out=%.1f MB temp=%.1f MB "
              "alias=%.1f MB peak(temp+args)=%.1f MB"
              % (ma.argument_size_in_bytes / 1e6,
                 ma.output_size_in_bytes / 1e6,
                 ma.temp_size_in_bytes / 1e6,
                 ma.alias_size_in_bytes / 1e6,
                 (ma.temp_size_in_bytes + ma.argument_size_in_bytes) / 1e6))
    except Exception as exc:
        print("memory_analysis unavailable: %r" % exc)
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        print("XLA cost_analysis: %.1f GFLOP/step, bytes accessed %.1f MB "
              "(%.1f MB/image), intensity %.1f FLOP/byte"
              % (ca["flops"] / 1e9, ca.get("bytes accessed", 0) / 1e6,
                 ca.get("bytes accessed", 0) / 1e6 / batch,
                 ca["flops"] / max(ca.get("bytes accessed", 1), 1)))
    except Exception as exc:
        print("cost_analysis unavailable: %r" % exc)


if __name__ == "__main__":
    main()
