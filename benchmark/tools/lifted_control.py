#!/usr/bin/env python3
"""Two planted controls of a cell whose reference has sliding windows and a
per-head gate: the reference with its WINDOW LIFTED (every layer attends
every position: ``sliding_window`` past any position) and with its GATE
LIFTED (``gating`` false: every head's gate 1), each in float32. One run of
the cell serves its mix as the benchmark does; then, at each position of
the served sequences, the token each lifted reference puts first is judged
by the true reference like a served token, and its readings go through the
cell's own comparison: ``correct`` has to come out false for both.

    python benchmark/tools/lifted_control.py \\
        --workload laguna-s-2.1-ep8.long_generation --seed 1 --seconds 30

The window lift takes the place of the entry's ``bf16`` control (its
readings are the result line's ``compared``), the gate lift that of the
``fp8`` control; the program's own readings are noted (``note program``).
The last line is one JSON object: each lift's compared readings and
whether it came out correct.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: control slot of the entry -> (lift, the configuration it is computed on)
LIFTS = {None: ("window_lifted",
                lambda cfg: dict(cfg, sliding_window=1 << 30)),
         "float8_e4m3fn": ("gate_lifted", lambda cfg: dict(cfg, gating=False))}


class Lifted(object):
    """The cell's reference, whose lower-precision forward (the entry's
    controls) is a float32 forward of a lifted configuration instead."""

    def __init__(self, ref):
        self._ref = ref

    def __getattr__(self, name):
        return getattr(self._ref, name)

    def forward(self, params, tokens, cfg, dtype="float32", operand=None,
                **kw):
        if dtype == "float32":
            return self._ref.forward(params, tokens, cfg, dtype, operand,
                                     **kw)
        return self._ref.forward(params, tokens, LIFTS[operand][1](cfg),
                                 "float32", **kw)


def run_lifted(workload, seed, seconds, root=ROOT, require_chip=True,
               compile_cache=True, out=None, err=None):
    """One run of ``workload`` with both lifts in the controls' places;
    returns ``{lift: {"correct": bool, "compared": {...}}}``."""
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness import cells, runner
    cell = cells.Cell(root, workload)
    ref = Lifted(cell.reference())
    cell.reference = lambda: ref
    run = runner.Run(cell, seed, seconds, 0, control="bf16",
                     require_chip=require_chip, compile_cache=compile_cache)
    line = cell.entry().run(run, out=out, err=err)
    exact = {k: line["compared"][k]["value"]
             for k in ("bad_requests", "requests_failed")}
    gate = dict(run.notes["control.fp8"], **exact)
    result = {}
    for name, (correct, compared) in (
            ("window_lifted", (line["correct"], line["compared"])),
            ("gate_lifted", runner.compare(gate, cell.limits()))):
        result[name] = {"correct": bool(correct), "compared": compared}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    result = run_lifted(args.workload, args.seed, args.seconds)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
