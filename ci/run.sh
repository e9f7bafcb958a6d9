#!/bin/sh
# Local CI: same stages as ci/pipeline.yml (ref role: Jenkinsfile).
set -e
cd "$(dirname "$0")/.."
make -C src
make -C src/capi
c++ -O2 -std=c++14 -I cpp-package/include cpp-package/example/train_mlp.cpp \
    -L lib -lmxnet_tpu -Wl,-rpath,'$ORIGIN' -o lib/train_mlp_cpp
# C++ LeNet through the generated op wrappers (built by make -C src/capi;
# run gated on holdout accuracy >= 0.95)
PYTHONPATH=. JAX_PLATFORMS=cpu ./lib/lenet_cpp
# Perl XS binding consumes the same ABI (non-C language proof)
make -C perl-package
(cd perl-package && PYTHONPATH=.. JAX_PLATFORMS=cpu perl predict.pl)
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -m pytest tests/ -q
# static lints over the model zoo's compiled step programs
# (docs/static_analysis.md; tier-1 keeps a faster 2-model smoke)
./ci/tracecheck.sh
# combined compile-once static audit (docs/static_analysis.md "Roofline
# lints"): each zoo + sharded program compiles ONCE and the same
# executable feeds all three per-program analyzers — flopcheck's kernel
# inventory + roofline lints + drift gate vs FLOPCHECK_baseline.json,
# memcheck's HBM lints + resident sets vs MEMCHECK_baseline.json, and
# commscheck's collective inventory vs COMMSCHECK_baseline.json
# (ci/memcheck.sh and ci/commscheck.sh stay for standalone runs)
./ci/flopcheck.sh
# zoo-dispatch gate (docs/perf.md "Packed accumulators"): every zoo
# model must report a non-fallback K-step dispatch path (or a named,
# documented reason) — precheck sweep over the whole zoo + real
# steps_per_dispatch fits on the cheap models, tracecheck-clean
./ci/zoo_dispatch.sh
# autotuner smoke (docs/perf.md "Autotuning"): tiny grid over mlp —
# memcheck pruner rejects the over-budget candidate without executing
# it, a measured winner >= the default persists to the tuning DB, and a
# fresh Module.fit resolves it (obs-logged) with zero extra retraces
./ci/autotune.sh
# flagship-LM gate (docs/perf.md "Flagship LM"): dp2 x sp2 ring-attention
# fit parity vs single device, MID-FIT decode hot reload (zero recompiles,
# bitwise vs a fresh engine), zero retraces, and zero analyzer findings
# over the co-resident train + serve program set
./ci/lm.sh
# observability gate (docs/observability.md): fused fit + batcher serve
# under MXTPU_TRACE=1 — Chrome-trace schema validation (stages present,
# spans nested, dispatch/request IDs consistent), registry snapshot
# carries every legacy health key, tracing-off cost A/B
./ci/obs.sh
# elastic-distributed gate (docs/robustness.md "Elastic distributed
# training"): REAL 3-process dist_sync run that SIGKILLs a worker
# mid-epoch — emergency checkpoint, ring re-form at N-1 with re-derived
# shards, accuracy floor, bitwise-consistent survivors, bitwise fresh
# resume
./ci/dist.sh
# chaos gate (docs/robustness.md "Chaos harness"): RED self-test first
# (a deliberately inverted invariant must fail a run), then seeded
# composed-fault plans through all four scenarios — train/data/dist/
# serve, each in a watchdogged subprocess — with zero violations and
# zero hangs, committed-regression replays, and the shrinker loop;
# emits CHAOS_r*.json
./ci/chaos.sh
# multichip gate: the fused fit over an 8-device 'data' mesh runs, guard +
# bitwise checkpoint/resume compose, collective/donation audit of the
# sharded program set, dp+tp / dp+sp compile coverage; measures nothing
python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
# chip stage: hard convergence gates + the ImageNet recipe compile-check
# (uses the real TPU when attached; tools default to the ambient platform).
# The full-size gate (defaults: 2400 imgs, 6 epochs) passes too but takes
# ~27 min on a 1-core host; CI runs the mid-size config.
python tools/convergence_gate_realdata.py \
    --n-per-class 100 --epochs 5 --min-acc 0.9
python example/image-classification/train_imagenet.py --validate-recipe
echo "CI PASS"
