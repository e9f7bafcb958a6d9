#!/usr/bin/env python3
"""The benchmark's command:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

loads the cell, warms up its own shapes (set-up), measures for ``--seconds``,
checks what the timed path produced against the plain reference, and prints
one JSON object as its last line. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of a short part of the window. It needs a TPU the table of peaks
knows and exits non-zero without one. ``--control <name>`` (never passed by
the driver) puts the plain reference, computed in the next lower precision
or with a fault planted, in the program's place: its readings go through the
same comparison, and ``correct`` has to come out false. ``--control all``
notes every control's readings beside the program's, for setting limits.
"""
import argparse
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cell(workload, seed, seconds, trace, control="", root=ROOT,
             require_chip=True, compile_cache=True, t_process=None,
             out=None, err=None):
    """Drive one run in this process and return the result line's dict."""
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness import cells, runner
    cell = cells.Cell(root, workload)
    run = runner.Run(cell, seed, seconds, trace, control=control,
                     t_process=t_process, require_chip=require_chip,
                     compile_cache=compile_cache)
    return cell.entry().run(run, out=out, err=err)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="")
    args = ap.parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        import mxnet_tpu  # noqa: F401  (the system under test)
    except ImportError as e:
        sys.exit("benchmark: the system under test is not in this checkout "
                 "(%s)" % e)
    from benchmark.harness import cells, runner
    try:
        run_cell(args.workload, args.seed, args.seconds, args.trace,
                 control=args.control, t_process=T_PROCESS)
    except runner.NoChip as e:
        sys.exit("benchmark: needs a TPU and runs nowhere else: %s" % e)
    except cells.CellError as e:
        sys.exit("benchmark: %s" % e)


if __name__ == "__main__":
    main()
