#!/usr/bin/env python
"""CI gate: elastic multi-process distributed training
(docs/robustness.md "Elastic distributed training").

What it proves, end to end, on REAL worker processes:

1. a 3-worker ``dist_sync`` run loses its highest rank to SIGKILL
   mid-epoch (the ``kv.worker_die`` fault site) and the survivors take
   an emergency checkpoint, re-form the control-plane ring at N-1,
   re-shard the data, and finish training to the accuracy floor — the
   per-rank asserts live in tests/dist_worker.py's ``elastic`` mode and
   a rank only prints its PASS line after every one of them held;
2. a FRESH module resuming from the surviving checkpoint prefix is
   bitwise-identical to the live post-reform parameters (same worker
   asserts).

Reports no rate: a throughput of timeshared CPU processes says nothing
about a chip. Run via ci/dist.sh.
"""
import os
import re
import socket
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NPROC = 3


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def main():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)   # workers are single-device processes
    env["JAX_PLATFORMS"] = "cpu"
    tmpdir = tempfile.mkdtemp(prefix="mxtpu_dist_gate_")
    env["MXTPU_TEST_TMPDIR"] = tmpdir

    # the elastic 3-worker run (mid-epoch SIGKILL baked into the
    # worker's elastic mode); nonzero launcher rc is by design — the
    # victim dies — so the verdict is the survivors' PASS lines
    worker = os.path.join(ROOT, "tests", "dist_worker.py")
    cmd = [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
           "-n", str(NPROC), "--coord-port", str(_free_port()),
           "%s %s elastic" % (sys.executable, worker)]
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=900)
    out = r.stdout + r.stderr
    for rank in range(NPROC - 1):
        if "RANK-%d-PASS" % rank not in out:
            sys.exit("dist_gate FAIL: survivor rank %d never passed "
                     "(re-form / bitwise-resume asserts live in the "
                     "worker):\n%s" % (rank, out))
    if "RANK-%d-PASS" % (NPROC - 1) in out:
        sys.exit("dist_gate FAIL: the victim rank survived its SIGKILL")

    stats = re.findall(
        r"RANK-\d+-ELASTIC-STATS reforms=(\d+) workers=(\d+)", out)
    if not stats:
        sys.exit("dist_gate FAIL: no survivor stats line:\n%s" % out)
    reforms = {int(r) for r, _w in stats}
    workers = {int(w) for _r, w in stats}
    if reforms != {1} or workers != {NPROC - 1}:
        sys.exit("dist_gate FAIL: expected exactly 1 re-form to %d "
                 "workers on every survivor, saw reforms=%s workers=%s"
                 % (NPROC - 1, sorted(reforms), sorted(workers)))

    print("dist_gate: 3->2 worker elastic run ok (1 re-form, bitwise "
          "resume)")


if __name__ == "__main__":
    main()
