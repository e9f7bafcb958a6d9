#!/usr/bin/env python3
"""Find an open-loop cell's knee, once, on the chip: the highest offered
rate the system sustains without a growing queue. One process, one loop,
the cell's own mix at each of a few rates:

    python benchmark/tools/sweep_rate.py --workload opt-1.3b.chat_steady \
        --rates 0.6,0.9,1.2,1.5 --seconds 30 --seed 1

prints one JSON line per rate: rate offered, rate completed inside the
window, requests still unfinished when the window closed (the queue at the
end), and the median and 90th percentile of ms per generated token. The
cell's rate is then WRITTEN into its traffic file as a number (about four
fifths of the knee); no run of the benchmark searches for it.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmark.harness import cells, runner, window
    cell = cells.Cell(ROOT, args.workload)
    run = runner.Run(cell, args.seed, args.seconds, trace=False)
    entry = cell.entry()
    cfg = cell.config
    ref = cell.reference()
    loop = cell.builder().build(cfg, ref.make_params(cfg, args.seed))
    client = entry.Client(loop, args.seed, int(cfg["vocab_size"]))
    try:
        entry.warm_slots(client, int(cfg["serve"]["slots"]))
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(cell.traffic, rate_per_s=rate)
            del client.records[:]
            res = entry.open_loop(run, client, mix,
                                  entry.WindowTracer(run))
            inside = res["inside"]
            done_in = [r for r in inside if r["t_done"] is not None
                       and r["t_done"] <= res["t_close"]]
            vals = res["per_token_ms"]
            print(json.dumps({
                "rate_offered": rate, "requests_due": len(inside),
                "rate_completed": len(done_in) / args.seconds,
                "queue_at_close": len(inside) - len(done_in),
                "ms_per_token_p50": window.percentile(vals, 50),
                "ms_per_token_p90": window.percentile(vals, 90),
                "failed": sum(1 for r in inside if r["error"] is not None
                              or r["t_done"] is None)}), flush=True)
            # drain what the lead-in and the window left before the next rate
            deadline = time.perf_counter() + 120.0
            while (any(r["t_done"] is None for r in client.records)
                   and time.perf_counter() < deadline):
                time.sleep(0.05)
    finally:
        loop.close()


if __name__ == "__main__":
    main()
