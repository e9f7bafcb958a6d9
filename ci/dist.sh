#!/bin/sh
# CI gate: elastic multi-process distributed training (docs/robustness.md
# "Elastic distributed training"). Launches a REAL 3-worker dist_sync run
# that SIGKILLs its highest rank mid-epoch (kv.worker_die), and asserts —
# inside each surviving worker — the emergency checkpoint, the ring
# re-form at N-1 with re-derived data shards, training to the accuracy
# floor, bitwise-consistent survivor replicas, and a bitwise-identical
# fresh resume. Reports no rate.
set -e
cd "$(dirname "$0")/.."
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" PYTHONPATH=. \
    python tools/dist_gate.py
echo "dist PASS"
