"""KVStore server entry (ref: python/mxnet/kvstore_server.py:11-58).

The reference branches on DMLC_ROLE: 'server' processes block in RunServer
applying pickled optimizers to pushed gradients; 'worker' processes continue
into user code. The TPU substrate has no server role — every process is an
SPMD worker and aggregation happens in-step (psum over ICI). This module
keeps the entry point so launch scripts that import it keep working, and
documents the role collapse.
"""
from __future__ import annotations

import os


def _init_distributed():
    """Initialize the jax.distributed control plane from MXTPU_* env vars
    (set by tools/launch.py — the tracker-rendezvous replacement).

    MXTPU_INIT_TIMEOUT (seconds) bounds the rendezvous: a mis-launched pod
    (wrong coordinator address, dead rank 0) fails fast with jax's timeout
    error instead of hanging the whole job forever.
    """
    coord = os.environ.get("MXTPU_COORD")
    if not coord:
        return False
    import jax
    # CPU multi-process needs a collectives implementation for the legacy
    # global-mesh transport (MXTPU_DIST_TRANSPORT=mesh): Gloo, configured
    # BEFORE the backend exists. Harmless for the default ring transport
    # (whose jits stay process-local); MXTPU_DIST_GLOO=0 opts out.
    if os.environ.get("MXTPU_DIST_GLOO", "1") != "0" \
            and os.environ.get("JAX_PLATFORMS", "").strip() in ("cpu", ""):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    kwargs = dict(
        coordinator_address=coord,
        num_processes=int(os.environ.get("MXTPU_NPROC", "1")),
        process_id=int(os.environ.get("MXTPU_RANK", "0")))
    timeout = os.environ.get("MXTPU_INIT_TIMEOUT")
    if timeout:
        kwargs["initialization_timeout"] = int(float(timeout))
    jax.distributed.initialize(**kwargs)
    return True


def _init_kvstore_server_module():
    """ref entry point: in the reference this blocks server processes.
    Here it initializes the distributed control plane (if launched via
    tools/launch.py) and returns — there are no server processes to block."""
    role = os.environ.get("DMLC_ROLE", os.environ.get("MXTPU_ROLE", "worker"))
    if role == "server":
        raise RuntimeError(
            "parameter-server roles do not exist on the TPU substrate: all "
            "processes are SPMD workers and gradient aggregation is an "
            "in-step psum (see mxnet_tpu.kvstore docs). Launch every process "
            "as a worker.")
    _init_distributed()


init = _init_kvstore_server_module
