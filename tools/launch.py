#!/usr/bin/env python
"""Multi-host launcher (ref: tools/launch.py over dmlc-core trackers —
local/ssh/mpi/sge/yarn, setting DMLC_ROLE/DMLC_PS_ROOT_* per process).

TPU-native: there are no parameter-server roles — every process is a worker
in one SPMD program; ``jax.distributed.initialize`` replaces the tracker
rendezvous (coordinator address + process_id + num_processes), and gradient
sync rides psum over ICI/DCN instead of ps-lite push/pull.

Launchers:
  local — spawn N worker processes on this host, on the CPU when N > 1
          (the reference's local tracker; for testing dist_sync semantics)
  ssh   — spawn one worker per host in --host-file via ssh

Each worker gets MXTPU_COORD / MXTPU_RANK / MXTPU_NPROC env vars; call
``mxnet_tpu.tools_init_distributed()`` (or jax.distributed.initialize
directly) at program start.
"""
import argparse
import os
import subprocess
import sys


def local_platform(n):
    """The JAX_PLATFORMS value ``n`` workers on THIS host run under. A chip
    belongs to one process at a time, and one process drives every chip of
    a host (``context=[mx.tpu(i) ...]``), so several local workers run on
    the CPU; asking for an accelerator platform for them is refused rather
    than left to hang on the chip the first worker took. One worker keeps
    whatever the caller set."""
    asked = os.environ.get("JAX_PLATFORMS", "").strip()
    if n <= 1:
        return asked
    if asked not in ("", "cpu"):
        raise SystemExit(
            "launch.py: %d local workers under JAX_PLATFORMS=%s would all "
            "claim the same device, and a chip belongs to one process. Run "
            "local workers with JAX_PLATFORMS=cpu (or unset), drive a "
            "host's chips from ONE process, or use --launcher ssh with one "
            "worker per host." % (n, asked))
    return "cpu"


def launch_local(n, command, coord_port=12421):
    platform = local_platform(n)
    procs = []
    for rank in range(n):
        env = dict(os.environ)
        env.update(MXTPU_COORD="localhost:%d" % coord_port,
                   MXTPU_RANK=str(rank), MXTPU_NPROC=str(n),
                   JAX_PLATFORMS=platform)
        procs.append(subprocess.Popen(command, shell=True, env=env))
    code = 0
    for p in procs:
        code = p.wait() or code
    return code


def launch_ssh(host_file, command, coord_port=12421):
    with open(host_file) as f:
        hosts = [h.strip() for h in f if h.strip()]
    coord = "%s:%d" % (hosts[0], coord_port)
    procs = []
    for rank, host in enumerate(hosts):
        env_prefix = ("MXTPU_COORD=%s MXTPU_RANK=%d MXTPU_NPROC=%d"
                      % (coord, rank, len(hosts)))
        procs.append(subprocess.Popen(
            ["ssh", "-o", "StrictHostKeyChecking=no", host,
             "cd %s && %s %s" % (os.getcwd(), env_prefix, command)]))
    code = 0
    for p in procs:
        code = p.wait() or code
    return code


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("--launcher", choices=["local", "ssh"],
                        default="local")
    parser.add_argument("--host-file", default=None)
    parser.add_argument("--coord-port", type=int, default=12421,
                        help="jax.distributed coordinator port")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    command = " ".join(args.command)
    if args.launcher == "local":
        sys.exit(launch_local(args.num_workers, command, args.coord_port))
    else:
        assert args.host_file, "ssh launcher needs --host-file"
        sys.exit(launch_ssh(args.host_file, command, args.coord_port))


if __name__ == "__main__":
    main()
