#!/usr/bin/env python
"""Benchmark: ResNet-50 training throughput and MFU on one chip.

Mirrors the reference's headline number (BASELINE.md: ResNet-50 train,
batch 32 — 45.52 img/s K80 / 90.74 M40 / 181.53 P100, from
docs/how_to/perf.md:159-190; script behavior ref:
example/image-classification/benchmark_score.py + train_imagenet.py).

vs_baseline is measured against the strongest single-GPU reference number
(P100, 181.53 img/s). Prints ONE JSON line.

Measurement notes (docs/perf.md has the full story):
- Each timed run ends in a host readback of the step counter, which
  waits for every step enqueued before it.
- The fixed cost of that readback and of the first dispatch is removed by
  differencing a 20-step and a 120-step run; the best of BENCH_ROUNDS
  rounds is reported.
- FLOPs come from XLA's own cost analysis of the compiled train step
  (~24.0 GFLOP/image for ResNet-50 fwd+bwd, i.e. 3x the 8.2 GFLOP forward),
  so MFU = achieved FLOP/s over the chip's peak bf16 FLOP/s.

Env knobs: BENCH_BATCH (default 128; 32 is the reference-parity config),
BENCH_ROUNDS (default 3), BENCH_DTYPE (float32|bfloat16 compute, default
bfloat16), BENCH_DEPTH (default 50), BENCH_IMAGE (default 224),
BENCH_STEPS_PER_DISPATCH (default 1; >=2 enables the steady-state bulked
mode: K steps per lax.scan dispatch over a device-resident superbatch with
metrics read back once per K — docs/perf.md "Dispatch bulking").

BENCH_DP_DEVICES=N adds a data-parallel scaling row to the JSON line
(docs/perf.md "Data-parallel scaling"): the same train-step config is
measured twice through the fused K-step scan — single device, and sharded
over an N-way 'data' mesh at the SAME global batch (params replicated,
batch axis split, gradient psum inside the donated body) — and the line
gains ``dp: {n_devices, img_per_sec, img_per_sec_1chip,
scaling_efficiency, collective_count, collective_bytes,
predicted_efficiency}`` (the last three from the commscheck static
inventory + roofline — docs/static_analysis.md "Communication lints";
the headline line carries the same three fields for the measured
program, zero collectives / efficiency 1.0 single-device). Needs N
visible devices (on CPU:
``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

BENCH_LM=1 switches to the flagship-LM training bench (docs/perf.md
"Flagship LM"): the transformer LM through the SAME fused K-step scan
harness as the headline number, reporting steady-state tokens/sec + MFU
(XLA cost-model FLOPs over the devspec peak-FLOPs table; on CPU /
unknown device kinds no MFU is printed), then one row per mesh spec in
BENCH_LM_MESHES (";"-separated — default "data=2;seq=2;data=2,seq=2":
data-parallel, ring-attention sequence-parallel, and the composed
dp x sp mesh) at the SAME global batch, each with measured scaling
efficiency plus the commscheck collective inventory and predicted
efficiency. Knobs: BENCH_LM_BATCH (32), BENCH_LM_SEQ (128),
BENCH_LM_VOCAB (1024), BENCH_LM_EMBED (256), BENCH_LM_LAYERS (4),
BENCH_LM_HEADS (8), BENCH_LM_DTYPE (bfloat16), BENCH_LM_MESHES,
BENCH_STEPS_PER_DISPATCH (default 4 in this mode; env > tuning DB >
default), BENCH_ROUNDS. Multi-axis rows need the devices visible (on
CPU: XLA_FLAGS=--xla_force_host_platform_device_count=N).

BENCH_SERVE=1 switches to the serving latency bench (docs/serving.md):
drive the dynamic batcher over the AOT shape-bucketed engine at a target
QPS with open-loop arrivals and report request latency p50/p99 plus
achieved throughput as one JSON line (the BENCH_serve_rNN.json number).
Knobs: BENCH_SERVE_MODEL (mlp|lenet, default mlp), BENCH_SERVE_QPS
(default 200), BENCH_SERVE_REQS (default 400), BENCH_SERVE_CLIENTS
(default 4), plus the MXTPU_SERVE_* batcher knobs (docs/env_var.md).

BENCH_DECODE=1 switches to the production-decode-path bench
(docs/serving.md "Production decode path"): per-leg A/B tokens/sec for
in-graph sampling, int8 weights (HBM reduction + quality gate), the
prefix cache and speculative decoding, each against the same greedy-f32
DecodeLoop baseline — the BENCH_decode_rNN.json number. Knobs:
BENCH_DECODE_REQS (8), BENCH_DECODE_NEW (24), BENCH_DECODE_SLOTS (4),
BENCH_DECODE_VOCAB (64), BENCH_DECODE_EMBED (32), BENCH_DECODE_LAYERS
(2), BENCH_DECODE_HEADS (2), BENCH_DECODE_LEN (64), BENCH_DECODE_SPEC_K
(2). Honest expectations on CPU: prefix reuse wins outright; speculation
is dispatch-bound (the draft chain adds K+1 host round-trips per round)
and ships default-off; int8 trades dequant compute for the recorded ~4x
weight-HBM win.

BENCH_FLEET=1 switches to the fleet latency bench (docs/serving.md "Fleet
tier"): N replicas (each its own AOT engine + Batcher) behind a
FleetRouter, open-loop arrivals at a QPS one replica cannot hold, a mixed
interactive/batch class workload, and a MID-RUN drain + rejoin of one
replica — reporting per-class p50/p99, achieved rps for the fleet AND for
a single replica measured by the same harness (their ratio is the
scaling number the BENCH_fleet_rNN.json gate pins), per-replica
utilization, and requeued/shed/failed counts (drain+death must shed
nothing). On hosts without a real accelerator the per-dispatch device
time is EMULATED by a labeled GIL-free sleep (BENCH_FLEET_DEVICE_MS,
default 40 — the emulation is printed in the JSON as emulated_device_ms;
set 0 on real hardware): one CPU core cannot demonstrate replica
parallelism, but the router/queue/drain path under test is fully real.
Knobs: BENCH_FLEET_REPLICAS (2), BENCH_FLEET_QPS (500),
BENCH_FLEET_REQS (600), BENCH_FLEET_SINGLE_REQS (200),
BENCH_FLEET_MAX_BATCH (8 — with the emulated device time this pins one
replica's capacity at max_batch/cycle, so both phases measure capacity),
BENCH_FLEET_MODEL (mlp|lenet), BENCH_FLEET_BATCH_FRAC (0.25),
BENCH_FLEET_DRAIN (1), BENCH_FLEET_DEADLINE_MS (20000), plus
MXTPU_FLEET_* / MXTPU_SERVE_*.

BENCH_ZOO_DISPATCH=1 switches to the zoo-dispatch mode (docs/perf.md
"Packed accumulators"): the models whose metric class used to silently
force k=1 — SSD's multi-head loc+cls under MultiBoxMetric and the
transformer LM under Perplexity — run Module.fit(steps_per_dispatch=K)
on the fused K-step scan at BENCH_ZD_DEVICES forced-host devices,
measured k=1 vs k=K through the SAME fit loop plus a 1-device run for a
dp-efficiency row; fails if any model falls back to k=1 or any
tracecheck/memcheck finding appears over the new program set (the
sharded programs are comms-audited at dispatch via MXTPU_COMMSCHECK=
error). Knobs: BENCH_ZD_MODELS (ssd,transformer), BENCH_ZD_DEVICES (8),
BENCH_ZD_BATCH (8*devices), BENCH_ZD_DISPATCHES (6), BENCH_ZD_IMAGE
(64), BENCH_ZD_SEQ (32), BENCH_STEPS_PER_DISPATCH (4). NOTE on reading
CPU numbers: XLA:CPU runs convolutions inside While/scan bodies ~3x
slower than outside (matmuls unaffected), so conv models can read <1x
on CPU hosts; the committed number's gate is engagement + parity +
zero findings, the speedup story is the TPU round-6 table.

BENCH_REAL_DATA=1 switches to the real-data input-tier gate (docs/perf.md
"Device-fed input pipeline"): generate a real-JPEG RecordIO set, run an
epoch of the SAME model/batch/K through the full
``mxnet_tpu.data`` tier — ImageRecordIter(num_workers=N) decode pool ->
DevicePrefetcher superbatch H2D -> fused K-step scan — and assert the
real-data img/s reaches ``MXTPU_REALDATA_MIN_RATIO`` (default 0.9) of the
synthetic device-resident number. One JSON line with both rates, the
ratio, per-stage PipelineStats, DataHealth and the tracecheck audit —
the BENCH_realdata_rNN.json number. Knobs: BENCH_RD_BATCH (128),
BENCH_RD_IMAGE (224), BENCH_RD_IMAGES (batch*k*8), BENCH_DEPTH (50),
BENCH_STEPS_PER_DISPATCH (4), MXTPU_DATA_WORKERS (min(4, cores)),
BENCH_RD_QUALITY (90), BENCH_RD_MODEL (resnet | lenet — the latter for
1-core CI hosts where resnet's XLA compile dominates),
BENCH_RD_MEASURE ("short,long" synthetic differencing steps).

BENCH_HOST_OVERHEAD=1 switches to the host-overhead mode (docs/perf.md
"Host off the critical path"): a full Module.fit loop with checkpointing
enabled, swept over BENCH_CKPT_CADENCES (default "8,16"), measuring
steady-state img/s and host_stall_frac — the fraction of wall time the
loop spent blocked on the host (packed-metric readbacks + checkpoint
serialization) — for the sync/eager baseline vs async checkpointing +
pipelined dispatch. Extra knobs: BENCH_HO_BATCHES (batches/epoch, default
32), BENCH_HO_IMAGE (default 112), BENCH_HO_BATCH (default 64),
BENCH_STEPS_PER_DISPATCH (default 4 in this mode),
MXTPU_DISPATCH_PIPELINE (depth for the pipelined config, default 1).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

# every BENCH_* knob is declared ONCE in the BenchConfig table
# (mxnet_tpu/autotune/benchcfg.py) and read through benv — integers and
# floats route through base.env_int/env_float, so a junk spelling raises
# MXNetError naming the variable instead of a raw ValueError / silent
# truncation. The autotuner's programmatic path reads the same table.
from mxnet_tpu.autotune.benchcfg import benv, env_set
# ONE measurement harness shared with the autotuner and the multichip CI
# gate (docs/perf.md "Autotuning"): bench re-exports it so existing
# `from bench import measure_scan_ips` callers keep working
from mxnet_tpu.autotune.harness import (measure_scan_ips,  # noqa: F401
                                        open_loop_run, serve_model)
from mxnet_tpu.base import env_float, env_int


def _peak_flops(device):
    """Peak dense bf16 FLOP/s by TPU generation — ONE table, owned by
    mxnet_tpu.devspec (commscheck's roofline, flopcheck's and this
    bench's MFU must agree on the same device). Unknown kinds return
    None here (MFU is omitted rather than guessed) instead of devspec's
    nominal CPU fallback."""
    from mxnet_tpu import devspec
    spec, source = devspec.lookup(device)
    kind = devspec.device_kind(device)
    if source == "spec":
        return spec.peak_flops_per_s, kind
    return None, kind


def _obs_block():
    """The unified-observability block every bench mode's JSON line
    carries (docs/observability.md): one metrics-registry snapshot — the
    five legacy health/stats objects ride it as views — plus host-tracer
    status and per-name span counts when MXTPU_TRACE=1."""
    from mxnet_tpu import obs
    snap = obs.REGISTRY.snapshot()
    block = {"trace_enabled": obs.enabled(),
             "counters": {k: v for k, v in sorted(snap.items())
                          if not k.endswith("last_error")}}
    if obs.enabled():
        by = {}
        for ev in obs.events():
            if ev.get("ph") in ("X", "i"):
                by[ev["name"]] = by.get(ev["name"], 0) + 1
        block["span_counts"] = by
        block["trace_path"] = obs.trace.trace_path()
    return block


def host_overhead_main():
    """Host-overhead mode: measure what checkpointing + metric readback
    COST the train loop, and how much of it the async writer + dispatch
    pipeline hide. One JSON line:

        {"metric": "...host_overhead...", "value": <best async img/s>,
         "host_stall_frac": <that same best-async config's frac>,
         "sweep": [{"cadence": N, "sync": {...}, "async": {...}}, ...]}

    Each config trains epoch 1 as compile/warmup and measures epoch 2's
    wall clock; host_stall_frac = (packed-readback stall + checkpoint
    save time on the loop thread) / epoch wall."""
    import tempfile
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.model import CheckpointManager

    batch = benv("BENCH_HO_BATCH")
    image = benv("BENCH_HO_IMAGE")
    depth = benv("BENCH_DEPTH")
    k = benv("BENCH_STEPS_PER_DISPATCH", 4)
    nbatches = benv("BENCH_HO_BATCHES")
    cadences = [int(c) for c in benv("BENCH_CKPT_CADENCES").split(",")
                if c.strip()]
    from mxnet_tpu import engine
    pl_depth = engine.dispatch_pipeline()

    sym = models.resnet(num_classes=1000, num_layers=depth,
                        image_shape="3,%d,%d" % (image, image))
    rng = np.random.default_rng(0)
    X = rng.normal(size=(nbatches * batch, 3, image, image)) \
        .astype(np.float32)
    y = rng.integers(0, 1000, nbatches * batch).astype(np.float32)

    def run(cadence, pipelined, async_ckpt, tmpdir, tag):
        mx.random.seed(0)
        it = mx.io.NDArrayIter(X, y, batch_size=batch)
        mod = mx.mod.Module(sym, context=mx.cpu()
                            if jax_platform() == "cpu" else None)
        mgr = CheckpointManager(os.path.join(tmpdir, tag, "ck"), keep=2)
        caps = {}

        def cb(p):
            caps["pipeline"] = p.locals.get("pipeline")

        marks = {}

        def epoch_cb(epoch, *_a):
            p = caps.get("pipeline")
            marks[epoch] = (time.perf_counter(),
                            getattr(p, "host_stall", 0.0), mgr.save_time)

        mod.fit(it, num_epoch=2, steps_per_dispatch=k,
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                checkpoint_prefix=mgr, checkpoint_every_n_batches=cadence,
                checkpoint_async=async_ckpt,
                dispatch_pipeline=pl_depth if pipelined else 0,
                batch_end_callback=cb, epoch_end_callback=epoch_cb)
        (t0, s0, c0), (t1, s1, c1) = marks[0], marks[1]
        wall = t1 - t0
        stall = (s1 - s0) + (c1 - c0)
        writer = mgr.async_writer or mgr.last_async_writer
        return {"images_per_sec": round(nbatches * batch / wall, 2),
                "host_stall_frac": round(max(0.0, stall) / wall, 4),
                "ckpt_skipped": writer.skipped if writer else 0}

    def jax_platform():
        import jax
        return jax.devices()[0].platform

    sweep = []
    best_async = None
    with tempfile.TemporaryDirectory() as tmpdir:
        for cadence in cadences:
            sync = run(cadence, False, False, tmpdir, "sync-%d" % cadence)
            asyn = run(cadence, True, True, tmpdir, "async-%d" % cadence)
            sweep.append({"cadence": cadence, "sync": sync, "async": asyn})
            if best_async is None or (asyn["images_per_sec"]
                                      > best_async["images_per_sec"]):
                best_async = asyn

    from mxnet_tpu import tracecheck
    out = {
        "metric": "resnet%d_host_overhead_b%d_k%d" % (depth, batch, k),
        "value": best_async["images_per_sec"],
        "unit": "images/sec",
        "steps_per_dispatch": k,
        "pipeline_depth": pl_depth,
        "host_stall_frac": best_async["host_stall_frac"],
        # unexpected jit-cache misses over the whole sweep: a nonzero count
        # means a config retraced a seen program (docs/static_analysis.md)
        "retraces": tracecheck.retrace_count(),
        "sweep": sweep,
    }
    out["obs"] = _obs_block()
    print(json.dumps(out))


def _zd_model(name, batch):
    """(symbol, data dict, label dict, data/label names, metric) for the
    zoo-dispatch bench — the models whose dispatch class used to force
    k=1: SSD's multi-head loc+cls and the transformer LM under
    Perplexity."""
    import mxnet_tpu as mx
    from mxnet_tpu import models
    rng = np.random.default_rng(0)
    if name == "ssd":
        image = benv("BENCH_ZD_IMAGE")
        sym = models.get_symbol("ssd", num_classes=3, width=16)
        X = rng.normal(size=(batch, 3, image, image)).astype(np.float32)
        lab = rng.random((batch, 4, 5)).astype(np.float32)
        lab[..., 0] = rng.integers(0, 3, (batch, 4))
        x1 = np.minimum(lab[..., 1], lab[..., 3])
        y1 = np.minimum(lab[..., 2], lab[..., 4])
        lab[..., 3] = np.maximum(lab[..., 1], lab[..., 3]) + 0.05
        lab[..., 4] = np.maximum(lab[..., 2], lab[..., 4]) + 0.05
        lab[..., 1], lab[..., 2] = x1, y1
        return (sym, {"data": X}, {"label": lab}, ("data",), ("label",),
                mx.metric.MultiBoxMetric())
    if name == "transformer":
        seq = benv("BENCH_ZD_SEQ")
        sym = models.get_symbol("transformer", vocab_size=64, embed=32,
                                num_heads=4, num_layers=2, seq_len=seq)
        X = rng.integers(0, 64, (batch, seq)).astype(np.float32)
        y = rng.integers(0, 64, (batch, seq)).astype(np.float32)
        return (sym, {"data": X}, {"softmax_label": y}, ("data",),
                ("softmax_label",), mx.metric.Perplexity(ignore_label=None))
    raise SystemExit("BENCH_ZD_MODELS entries must be ssd|transformer, "
                     "got %r" % name)


def zoo_dispatch_main():
    """BENCH_ZOO_DISPATCH=1 (docs/perf.md "Packed accumulators"): the
    scenario-diversity proof — the models whose metric class used to
    silently force steps_per_dispatch=1 (SSD multi-head, transformer-LM
    perplexity) run Module.fit on the fused K-step scan at
    BENCH_ZD_DEVICES forced-host devices, measured k=1 vs k=K through
    the SAME fit loop (epoch 1 compiles, epoch 2 is timed), plus the
    k=K run at 1 device for a dp scaling-efficiency row. One JSON line;
    fails if any model falls back to k=1 or any static finding appears
    across the new program set (the dispatch-time commscheck hook is
    armed in error mode for the sharded programs)."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import tracecheck, memcheck

    ndev = benv("BENCH_ZD_DEVICES")
    k = benv("BENCH_STEPS_PER_DISPATCH", 4)
    batch = benv("BENCH_ZD_BATCH") or 8 * max(1, ndev)
    dispatches = benv("BENCH_ZD_DISPATCHES")
    model_names = [m for m in benv("BENCH_ZD_MODELS").split(",")
                   if m.strip()]
    if len(jax.devices()) < ndev:
        raise SystemExit(
            "BENCH_ZD_DEVICES=%d but only %d device(s) visible — on CPU "
            "raise with XLA_FLAGS=--xla_force_host_platform_device_count"
            "=%d" % (ndev, len(jax.devices()), ndev))
    # the sharded scans get comms-audited at first dispatch; min_eff=0
    # because this gate checks the collective INVENTORY lints, not the
    # training-scale-out roofline (mirroring the serving-tier audits)
    os.environ.setdefault("MXTPU_COMMSCHECK", "error")
    os.environ.setdefault("MXTPU_COMMSCHECK_MIN_EFF", "0")

    def run_fit(name, spd, contexts, tag):
        sym, data, label, dnames, lnames, metric = _zd_model(name, batch)
        n = batch * spd * dispatches
        reps = (n + batch - 1) // batch
        Xr = {kk: np.concatenate([v] * reps)[:n] for kk, v in data.items()}
        yr = {kk: np.concatenate([v] * reps)[:n] for kk, v in label.items()}
        it = mx.io.NDArrayIter(Xr, yr, batch_size=batch)
        mod = mx.mod.Module(sym, data_names=dnames, label_names=lnames,
                            context=contexts)
        mx.random.seed(0)
        marks = {}

        def epoch_cb(epoch, *_a):
            marks[epoch] = time.perf_counter()

        mod.fit(it, num_epoch=2, steps_per_dispatch=spd,
                initializer=mx.initializer.Xavier(),
                eval_metric=metric,
                optimizer_params={"learning_rate": 0.01, "momentum": 0.9},
                epoch_end_callback=epoch_cb)
        wall = marks[1] - marks[0]
        scan_engaged = (mod._fused is not None
                        and any(key[1] == spd
                                for key in mod._fused._jit_scan))
        prefix = (mod._fused._watcher.name + "/"
                  if mod._fused is not None and mod._fused._watcher
                  else None)
        return n / wall, scan_engaged, prefix, metric

    ctx_n = [mx.Context("cpu" if jax.devices()[0].platform == "cpu"
                        else "tpu", i) for i in range(ndev)]
    ctx_1 = ctx_n[0]
    rows = {}
    prefixes = []
    failed = []
    for name in model_names:
        ips_k1, _, _, _ = run_fit(name, 1, ctx_n, "k1")
        ips_kk, engaged, prefix, metric = run_fit(name, k, ctx_n, "kk")
        ips_1dev, _, _, _ = run_fit(name, k, ctx_1, "kk1dev")
        if prefix:
            prefixes.append(prefix)
        if not engaged:
            failed.append(name)
        rows[name] = {
            "k": k,
            "img_per_sec_k1": round(ips_k1, 2),
            "img_per_sec_k%d" % k: round(ips_kk, 2),
            "dispatch_speedup": round(ips_kk / max(ips_k1, 1e-9), 3),
            "dp_devices": ndev,
            "img_per_sec_1dev": round(ips_1dev, 2),
            "dp_efficiency": round(ips_kk / max(ips_1dev, 1e-9), 3),
            "scan_engaged": engaged,
            "metric": type(metric).__name__,
        }
    # the new program set must be lint-clean as a unit: tracecheck full
    # lints + memcheck (incl. resident-set) over every program the fits
    # registered; commscheck already gated each sharded dispatch (error
    # mode raises inside fit)
    findings = []
    for p in prefixes:
        findings += tracecheck.unsuppressed(
            tracecheck.check_registered(match=p))
    mem_findings, _reports = memcheck.check_registered(
        match=tuple(prefixes), resident_name="zoo-dispatch")
    findings += [f for f in mem_findings if not f.suppressed]
    out = {
        "metric": "zoo_dispatch_b%d_k%d_dp%d" % (batch, k, ndev),
        "value": round(min(r["dispatch_speedup"] for r in rows.values()),
                       3),
        "unit": "min_dispatch_speedup_x",
        "models": rows,
        "findings": len(findings),
        "retraces": tracecheck.retrace_count(),
    }
    out["obs"] = _obs_block()
    print(json.dumps(out))
    if failed:
        raise SystemExit("BENCH_ZOO_DISPATCH gate: %s fell back to k=1 — "
                         "the packed-accumulator path did not engage"
                         % ", ".join(failed))
    if findings:
        for f in findings:
            print(f.format(), file=sys.stderr)
        raise SystemExit("BENCH_ZOO_DISPATCH gate: %d static finding(s) "
                         "across the new program set" % len(findings))


def _make_realdata_rec(path, n, size, quality, classes=8, seed=11):
    """Pack n real JPEGs (distinct per-class color/stripe textures, real
    libjpeg bytes) into an indexed .rec — the decode cost is the honest
    ImageNet-shaped cost, only the pixels are synthetic."""
    import io as _bio
    from PIL import Image
    from mxnet_tpu import recordio

    rng = np.random.default_rng(seed)
    ang = rng.uniform(0, np.pi, classes)
    freq = rng.uniform(3, 9, classes)
    base = rng.uniform(0.25, 0.75, (classes, 3))
    xs = np.linspace(0, 1, size)
    gx, gy = np.meshgrid(xs, xs, indexing="ij")
    idx_path = os.path.splitext(path)[0] + ".idx"
    rec = recordio.MXIndexedRecordIO(idx_path, path, "w")
    for i in range(n):
        c = i % classes
        wave = np.sin(2 * np.pi * freq[c]
                      * (gx * np.cos(ang[c]) + gy * np.sin(ang[c]))
                      + rng.uniform(0, 2 * np.pi))
        img = (base[c][:, None, None] + 0.22 * wave[None]
               + rng.normal(0, 0.05, (3, size, size)))
        arr = (np.clip(img, 0, 1) * 255).astype(np.uint8).transpose(1, 2, 0)
        buf = _bio.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG", quality=quality)
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(c), i, 0), buf.getvalue()))
    rec.close()
    return path


def realdata_main():
    """Real-data input-tier gate (docs/perf.md "Device-fed input
    pipeline"): the same fused K-step scan measured twice — superbatch
    device-resident (the synthetic headline methodology), and fed by the
    FULL data tier from real JPEG bytes (sharded reader -> decode worker
    pool -> superbatch stack -> prefetch-to-device). Asserts
    real/synthetic >= MXTPU_REALDATA_MIN_RATIO and prints one JSON line
    with per-stage PipelineStats — the number that says the input side no
    longer hides behind the synthetic bench."""
    import tempfile
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import models, engine, tracecheck
    from mxnet_tpu import data as mdata
    from mxnet_tpu.train_step import TrainStep

    batch = benv("BENCH_RD_BATCH")
    image = benv("BENCH_RD_IMAGE")
    depth = benv("BENCH_DEPTH")
    k = max(2, benv("BENCH_STEPS_PER_DISPATCH", 4))
    nimg = benv("BENCH_RD_IMAGES") or batch * k * 8
    # whole superbatches only: one compiled program, no epoch tail
    nimg = max(batch * k, nimg - nimg % (batch * k))
    quality = benv("BENCH_RD_QUALITY")
    workers = env_int("MXTPU_DATA_WORKERS", 0) \
        or min(4, os.cpu_count() or 1)
    min_ratio = env_float("MXTPU_REALDATA_MIN_RATIO", 0.9)
    rounds = benv("BENCH_ROUNDS", 2)
    cdtype = benv("BENCH_DTYPE")
    if jax.devices()[0].platform == "cpu":
        cdtype = "float32"  # bf16 matmuls emulate slowly on CPU

    model = benv("BENCH_RD_MODEL")
    if model == "resnet":
        sym = models.resnet(num_classes=8, num_layers=depth,
                            image_shape="3,%d,%d" % (image, image))
        mname = "resnet%d" % depth
    elif model == "lenet":
        # the multichip gate's conv workload: seconds to compile on a
        # 1-core CI host where resnet's XLA compile alone runs minutes —
        # same pipeline, same gate semantics
        sym = models.lenet(num_classes=8)
        mname = "lenet"
    else:
        raise SystemExit("BENCH_RD_MODEL must be resnet|lenet, got %r"
                         % model)

    def make_step():
        return TrainStep(
            sym, optimizer="sgd", learning_rate=0.1, momentum=0.9, wd=1e-4,
            compute_dtype=None if cdtype == "float32" else cdtype)

    dshape = (batch, 3, image, image)
    # -- synthetic side: device-resident superbatch, the headline
    # methodology (short/long differencing, best of rounds)
    step = make_step()
    state = step.init({"data": dshape}, {"softmax_label": (batch,)})
    rng = np.random.default_rng(0)
    sb = {"data": jnp.stack(
              [jnp.asarray(rng.normal(size=dshape), np.float32)] * k),
          "softmax_label": jnp.stack(
              [jnp.asarray(rng.integers(0, 8, batch), np.float32)] * k)}
    # BENCH_RD_MEASURE="short,long" differencing steps for the synthetic
    # side (defaults sized for chip hosts; the CI smoke shrinks them — a
    # CPU dispatch takes seconds, so the fixed-latency term the
    # differencing cancels is proportionally tiny there)
    meas = benv("BENCH_RD_MEASURE").split(",")
    n_short = max(1, (int(meas[0]) + k - 1) // k)
    n_long = max(n_short + 2, (int(meas[1]) + k - 1) // k)
    synth_ips = measure_scan_ips(step, state, sb, batch, k, n_short,
                                 n_long, rounds=rounds)
    if synth_ips <= 0:
        raise RuntimeError("realdata bench: synthetic measurement failed")

    # -- real side: JPEG -> reader -> decode pool -> prefetch-to-device ->
    # the SAME compiled scan, timed over whole epochs
    import mxnet_tpu as mx
    with tempfile.TemporaryDirectory(prefix="bench_rd_") as tmp:
        gen0 = time.perf_counter()
        rec = _make_realdata_rec(os.path.join(tmp, "train.rec"), nimg,
                                 int(image * 1.15), quality)
        gen_s = time.perf_counter() - gen0
        it = mx.image.ImageRecordIter(
            path_imgrec=rec, data_shape=(3, image, image),
            batch_size=batch, shuffle=True, seed=1, rand_crop=True,
            rand_mirror=True, resize=int(image * 1.1),
            mean_r=123.68, mean_g=116.28, mean_b=103.53,
            std_r=58.4, std_g=57.1, std_b=57.4, num_workers=workers)
        pf = mdata.DevicePrefetcher(it, k, depth=engine.dispatch_pipeline(),
                                    last_group_handle="discard")
        step2 = make_step()
        state2 = step2.init({"data": dshape}, {"softmax_label": (batch,)})

        def epoch(st):
            seen = 0
            for sb in pf:
                feed = {"data": sb.data[0].data,
                        "softmax_label": sb.label[0].data}
                st, _m = step2.run_steps(st, feed)
                seen += batch * sb.num_steps
            np.asarray(st["step"])  # forced readback: epoch fully retired
            pf.reset()
            return st, seen

        state2, _ = epoch(state2)        # warmup: compile + file cache
        it.data_stats.reset()
        best_real = 0.0
        for _ in range(rounds):
            t0 = time.perf_counter()
            state2, seen = epoch(state2)
            best_real = max(best_real, seen / (time.perf_counter() - t0))
        pf.close()
        it.close()
        health = it.data_health.report()
        pipeline_rep = it.data_stats.report()

    ratio = best_real / synth_ips
    findings = tracecheck.unsuppressed(tracecheck.check_registered())
    out = {
        "metric": "%s_realdata_images_per_sec_b%d_%s_k%d"
                  % (mname, batch, cdtype, k),
        "value": round(best_real, 2),
        "unit": "images/sec",
        "synthetic_img_per_sec": round(synth_ips, 2),
        "ratio": round(ratio, 3),
        "min_ratio": min_ratio,
        "images": nimg,
        "image_px": image,
        "workers": workers,
        "steps_per_dispatch": k,
        "jpeg_gen_seconds": round(gen_s, 1),
        "pipeline": pipeline_rep,
        "data_health": health,
        "tracecheck_findings": len(findings),
        "retraces": tracecheck.retrace_count(),
    }
    out["obs"] = _obs_block()
    print(json.dumps(out))
    if ratio < min_ratio:
        raise SystemExit(
            "BENCH_REAL_DATA gate: real-data %.2f img/s is %.3f of the "
            "synthetic %.2f img/s — below MXTPU_REALDATA_MIN_RATIO=%.2f "
            "(the input tier is not feeding the chip; see 'pipeline' "
            "stage seconds in the JSON line above)"
            % (best_real, ratio, synth_ips, min_ratio))


def _serve_model(name=None):
    """Build (engine kwargs) for the serving/fleet benches — ONE recipe
    shared with the autotuner's serving harness
    (``autotune.harness.serve_model``). ``name`` defaults to the
    BENCH_SERVE_MODEL env knob."""
    from mxnet_tpu.base import MXNetError
    if name is None:
        name = benv("BENCH_SERVE_MODEL")
    try:
        return serve_model(name)
    except MXNetError as e:
        raise SystemExit("bench serve/fleet: %s" % (e,))


def serve_main():
    """Serving latency bench: open-loop arrivals at a target QPS through
    the dynamic batcher; one JSON line with p50/p99 latency and achieved
    throughput (docs/serving.md "Latency bench")."""
    from mxnet_tpu import serving, tracecheck

    qps = benv("BENCH_SERVE_QPS")
    nreq = benv("BENCH_SERVE_REQS")
    nclients = benv("BENCH_SERVE_CLIENTS")
    name, sym, params, shape = _serve_model()

    eng = serving.ServingEngine(sym, params, {"data": shape})
    batcher = serving.Batcher(eng)
    rs = np.random.default_rng(1)
    x1 = rs.normal(size=(1,) + shape).astype(np.float32)
    batcher.infer({"data": x1})           # warm the smallest bucket path

    # open-loop arrivals through the shared client harness (also drives
    # the autotuner's serving trials): request i is DUE at t0+i/qps, so
    # queueing delay lands in the measured latency, never in offered load
    latencies, errors, wall = open_loop_run(
        batcher.infer, {"data": x1}, qps, nreq, nclients=nclients)
    batcher.close()
    if not latencies:
        raise RuntimeError("serving bench completed no requests: %s"
                           % errors[:3])
    lat_ms = np.asarray(latencies) * 1e3
    findings = tracecheck.unsuppressed(
        tracecheck.check_registered(match=eng.name + "/"))
    # static memory profile of the bucket set (already compiled — free):
    # per-bucket peak plus the co-resident footprint the AOT cache retains
    mem_fields = {}
    try:
        from mxnet_tpu import memcheck
        reports = eng.memory_report()
        if reports:
            mem_fields = {
                "hbm_peak_bytes": max(r.peak_bytes
                                      for r in reports.values()),
                "temp_bytes": max(r.temp_bytes for r in reports.values()),
                "hbm_resident_bytes": memcheck.resident_bytes(
                    reports.values()),
            }
    except Exception as exc:
        print("WARNING: memcheck analysis failed, no HBM fields emitted: %r"
              % exc, file=sys.stderr)
    out = {
        "metric": "serve_%s_latency_qps%g" % (name, qps),
        "value": round(float(np.percentile(lat_ms, 99)), 3),
        "unit": "ms_p99",
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 3),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 3),
        "mean_ms": round(float(lat_ms.mean()), 3),
        "throughput_rps": round(len(latencies) / wall, 2),
        "qps_target": qps,
        "completed": len(latencies),
        "failed": len(errors),
        "buckets": list(eng.buckets),
        "batches": eng.health.batches,
        "avg_batch": round(eng.health.examples
                           / max(1, eng.health.batches), 2),
        "padded_frac": round(eng.health.padded
                             / max(1, eng.health.examples
                                   + eng.health.padded), 4),
        # the serving program set must stay lint-clean while under load
        "tracecheck_findings": len(findings),
        "retraces": tracecheck.retrace_count(),
    }
    out.update(mem_fields)
    out["obs"] = _obs_block()
    print(json.dumps(out))


def _decode_lm_params(cfg, num_layers, seed):
    """Random f32 transformer-LM params for the decode bench (weights
    don't affect throughput; the int8 leg re-derives its own from these)."""
    from mxnet_tpu import models
    sym = models.transformer(vocab_size=cfg["vocab"], embed=cfg["embed"],
                             num_heads=cfg["heads"],
                             num_layers=num_layers, seq_len=cfg["len"])
    arg_shapes, _, _ = sym.infer_shape(data=(1, cfg["len"]),
                                       softmax_label=(1, cfg["len"]))
    rs = np.random.RandomState(seed)
    params = {n: (rs.randn(*s) * 0.3).astype(np.float32)
              for n, s in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    return sym, params


def decode_main():
    """Production-decode-path bench (docs/serving.md "Production decode
    path"): per-leg A/B tokens/sec for the four decode features —
    in-graph sampling, int8 weights (with the HBM win and the quality
    gate), prefix-cache reuse, speculative decoding (with the
    token-identity cross-check) — each against the same greedy-f32
    baseline loop. One JSON line (the BENCH_decode_rNN.json number)."""
    from mxnet_tpu import serving, tracecheck
    from mxnet_tpu.serving.quantize import check_quality

    nreq = benv("BENCH_DECODE_REQS")
    max_new = benv("BENCH_DECODE_NEW")
    slots = benv("BENCH_DECODE_SLOTS")
    spec_k = benv("BENCH_DECODE_SPEC_K")
    cfg = {"vocab": benv("BENCH_DECODE_VOCAB"),
           "embed": benv("BENCH_DECODE_EMBED"),
           "layers": benv("BENCH_DECODE_LAYERS"),
           "heads": benv("BENCH_DECODE_HEADS"),
           "len": benv("BENCH_DECODE_LEN")}
    sym, params = _decode_lm_params(cfg, cfg["layers"], seed=0)
    _dsym, draft = _decode_lm_params(cfg, 1, seed=1)

    rs = np.random.RandomState(2)
    shared = [int(t) for t in rs.randint(1, cfg["vocab"], 8)]
    tails = [[int(t) for t in rs.randint(1, cfg["vocab"], 2 + i % 3)]
             for i in range(nreq)]
    prompts = [shared + t for t in tails]
    seeds = [101 + i for i in range(nreq)]

    def run(loop, temp, plen=0):
        """One warmed A/B measurement: tokens/sec over the fixed request
        batch (and the emitted streams, for the identity cross-checks)."""
        def once():
            futs = [loop.generate(p, max_new, temperature=temp,
                                  seed=s, prefix_len=plen)
                    for p, s in zip(prompts, seeds)]
            return [f.result(timeout=300.0) for f in futs]
        once()                                    # warm (primes prefixes)
        t0 = time.perf_counter()
        outs = once()
        dt = time.perf_counter() - t0
        return sum(len(o) for o in outs) / dt, outs

    mk = lambda **kw: serving.DecodeLoop(
        params, num_layers=cfg["layers"], num_heads=cfg["heads"],
        max_len=cfg["len"], slots=slots, **kw)
    legs, findings = {}, 0

    base = mk(quantize="none", prefix_cache=False)
    base_tps, _ = run(base, temp=0.0)
    sampled_tps, sampled_outs = run(base, temp=0.8)
    findings += len(base.check(memory=True))
    base.close()
    legs["greedy_f32"] = {"tokens_per_sec": round(base_tps, 1)}
    legs["sampled"] = {"tokens_per_sec": round(sampled_tps, 1)}

    q = mk(quantize="int8", prefix_cache=False)
    int8_tps, _ = run(q, temp=0.8)
    findings += len(q.check(memory=True))
    int8_bytes = q.weight_bytes()
    q.close()
    # the quality gate runs through the engine pair — the documented
    # quant workflow (docs/serving.md "Quantized weights")
    ref_eng = serving.ServingEngine(sym, params, {"data": (cfg["len"],)},
                                    buckets=(4,))
    q_eng = serving.ServingEngine(sym, params, {"data": (cfg["len"],)},
                                  buckets=(4,), quantize="int8")
    probe = np.zeros((4, cfg["len"]), np.float32)
    probe[:, :8] = np.asarray([shared] * 4, np.float32)
    quality = q_eng.quality_report(ref_eng, {"data": probe})
    check_quality(quality, who="bench-decode int8")
    f32_bytes = ref_eng.weight_bytes()
    legs["int8"] = {
        "tokens_per_sec": round(int8_tps, 1),
        "weight_bytes_f32": f32_bytes,
        "weight_bytes_int8": int8_bytes,
        "weight_hbm_reduction": round(1.0 - int8_bytes / f32_bytes, 4),
        "top1_agreement": round(quality["top1_agreement"], 4),
    }

    pre = mk(quantize="none", prefix_cache=True)
    prefix_tps, _ = run(pre, temp=0.8, plen=len(shared))
    findings += len(pre.check(memory=True))
    legs["prefix"] = {"tokens_per_sec": round(prefix_tps, 1),
                      "prefix_hits": pre.health.prefix_hits,
                      "prefix_prefills": pre.health.prefix_prefills}
    pre.close()

    spec = mk(quantize="none", prefix_cache=False, spec_k=spec_k,
              draft_params=draft, draft_num_layers=1)
    spec_tps, spec_outs = run(spec, temp=0.8)
    findings += len(spec.check(memory=True))
    h = spec.health
    legs["spec_k%d" % spec_k] = {
        "tokens_per_sec": round(spec_tps, 1),
        "accept_rate": round(h.spec_accepted / max(1, h.spec_drafted), 4),
        # the correctness contract, measured, not assumed: speculative
        # output is token-identical to target-only under the same seeds
        "token_identical": spec_outs == sampled_outs,
    }
    spec.close()
    if spec_outs != sampled_outs:
        raise RuntimeError("speculative decode diverged from target-only "
                           "sampling under identical seeds")

    for leg in legs.values():
        leg["x_vs_greedy_f32"] = round(
            leg["tokens_per_sec"] / max(base_tps, 1e-9), 3)
    out = {
        "metric": "decode_path_l%d_e%d_v%d" % (cfg["layers"],
                                               cfg["embed"], cfg["vocab"]),
        "value": round(base_tps, 1),
        "unit": "tokens_per_sec_greedy_f32",
        "requests": nreq,
        "max_new": max_new,
        "slots": slots,
        "legs": legs,
        "tracecheck_findings": findings,
        "retraces": tracecheck.retrace_count(),
        "obs": _obs_block(),
    }
    print(json.dumps(out))


class _PacedEngine(object):
    """Bench-local engine proxy emulating device dispatch latency with a
    GIL-free sleep: on a host without a real accelerator, one core cannot
    demonstrate replica parallelism — the sleep stands in for the
    accelerator's execution time (overlapping across replicas exactly like
    real devices would) while the batcher/router/queue path under test
    stays fully real. The emulation is labeled in the bench JSON
    (``emulated_device_ms``); 0 disables it for real-hardware runs."""

    def __init__(self, engine, device_ms):
        self._engine = engine
        self._device_s = device_ms / 1e3

    def infer(self, inputs):
        if self._device_s > 0:
            time.sleep(self._device_s)
        return self._engine.infer(inputs)

    def __getattr__(self, name):
        return getattr(self._engine, name)


def _percentiles_ms(latencies):
    lat = np.asarray(latencies) * 1e3
    return {"p50_ms": round(float(np.percentile(lat, 50)), 3),
            "p99_ms": round(float(np.percentile(lat, 99)), 3),
            "mean_ms": round(float(lat.mean()), 3)}


def _fleet_open_loop(router, inputs, nreq, qps, classes, deadline_ms):
    """TRUE open-loop arrival harness for the fleet phases: one pacer
    thread issues NON-BLOCKING submissions (request i DUE at t0 + i/qps —
    queueing delay lands in measured latency, never caps the offered
    load the way a pool of blocking clients would), completions are
    timestamped by the router's settle callback. Returns (per-class
    latency lists, errors, wall seconds from first due to last
    completion)."""
    import threading
    lat = {c: [] for c in set(classes)}
    errors = []
    lock = threading.Lock()
    interval = 1.0 / qps
    done_ts = [0.0]

    def make_cb(cls, t_start):
        def cb(freq):
            now = time.perf_counter()
            with lock:
                if freq.error is None:
                    lat[cls].append(now - t_start)
                else:
                    errors.append(repr(freq.error))
                done_ts[0] = max(done_ts[0], now)
        return cb

    futs = []
    t0 = time.perf_counter() + 0.05
    for i in range(nreq):
        due = t0 + i * interval
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        # latency counts from the DUE time, not the actual submit
        # instant: a pacer running late must charge its lag to the
        # measured latency, not silently exclude it (coordinated
        # omission)
        try:
            futs.append(router.submit(inputs, priority=classes[i],
                                      deadline_ms=deadline_ms,
                                      on_done=make_cb(classes[i], due)))
        except Exception as e:
            with lock:
                errors.append(repr(e))
    for f in futs:
        f.event.wait(timeout=deadline_ms / 1e3 + 5.0)
    return lat, errors, max(done_ts[0], t0) - t0


def fleet_main():
    """Fleet latency bench (docs/serving.md "Fleet tier"): N replicas
    behind a FleetRouter at a QPS one replica cannot hold, with a mid-run
    drain + rejoin; one JSON line with per-class latency, fleet-vs-single
    achieved rps, per-replica utilization, and the static audit."""
    import threading
    from mxnet_tpu import serving, tracecheck

    nrep = benv("BENCH_FLEET_REPLICAS")
    qps = benv("BENCH_FLEET_QPS")
    nreq = benv("BENCH_FLEET_REQS")
    nreq_single = benv("BENCH_FLEET_SINGLE_REQS")
    batch_frac = benv("BENCH_FLEET_BATCH_FRAC")
    device_ms = benv("BENCH_FLEET_DEVICE_MS")
    deadline_ms = benv("BENCH_FLEET_DEADLINE_MS")
    # one dispatch serves at most this many co-riders: with the emulated
    # device time this pins a replica's capacity (max_batch/cycle) well
    # below the offered QPS, so BOTH phases measure capacity, not load
    max_batch = benv("BENCH_FLEET_MAX_BATCH")
    do_drain = benv("BENCH_FLEET_DRAIN")
    name, sym, params, shape = _serve_model(benv("BENCH_FLEET_MODEL"))
    rs = np.random.default_rng(1)
    x1 = rs.normal(size=(1,) + shape).astype(np.float32)

    def mk_replica():
        eng = serving.ServingEngine(sym, params, {"data": shape})
        return serving.Batcher(_PacedEngine(eng, device_ms),
                               max_batch=max_batch)

    # ---- phase A: ONE replica's achieved rps under the same open loop —
    # the capacity the fleet must beat (completions per wall second at an
    # offered load above what one replica can hold)
    single = serving.FleetRouter([mk_replica()], name="fleet-single")
    single.infer({"data": x1}, deadline_ms=deadline_ms)   # warm path
    cls_single = ["interactive"] * nreq_single
    lat1, err1, wall1 = _fleet_open_loop(single, {"data": x1},
                                         nreq_single, qps, cls_single,
                                         deadline_ms)
    single.close()
    done1 = sum(len(v) for v in lat1.values())
    rps_single = done1 / wall1

    # ---- phase B: the fleet, same open loop, mixed classes, and (by
    # default) a mid-run drain of r0 + a warm rejoin while serving
    replicas = {"r%d" % i: mk_replica() for i in range(nrep)}
    r0_engine = replicas["r0"].engine
    router = serving.FleetRouter(replicas, name="fleet-bench")
    router.infer({"data": x1}, deadline_ms=deadline_ms)
    stride = max(2, int(round(1.0 / batch_frac))) if batch_frac > 0 else 0
    cls = ["batch" if (stride and i % stride == 0) else "interactive"
           for i in range(nreq)]
    drain_state = {"event": None}

    def coordinator():
        # fire the membership event once ~35% of the run has been issued
        time.sleep(0.05 + (0.35 * nreq) / qps)
        try:
            router.drain("r0", timeout=60.0)
            # warm rejoin: same engine (already compiled), fresh batcher —
            # join() re-warms every bucket off the serving path
            router.join("r0b",
                        lambda: serving.Batcher(r0_engine,
                                                max_batch=max_batch),
                        warmup=True)
            drain_state["event"] = "drain+join ok"
        except Exception as e:
            drain_state["event"] = "FAILED: %r" % (e,)

    coord = None
    if do_drain:
        coord = threading.Thread(target=coordinator, daemon=True)
        coord.start()
    lat, errors, wall = _fleet_open_loop(router, {"data": x1}, nreq, qps,
                                         cls, deadline_ms)
    if coord is not None:
        coord.join(timeout=90.0)
    done = sum(len(v) for v in lat.values())
    rps_fleet = done / wall
    report = router.report()
    # static audit across EVERY replica's program set (tracecheck +
    # memory + comms lints; r0 and r0b share one engine/program set)
    findings = [f for f in router.check(memory=True, comms=True)
                if not f.suppressed]
    # utilization per DISTINCT engine: a warm rejoin (r0b) shares r0's
    # engine, so its counters must be attributed once, under a combined
    # key, not double-counted per replica name
    by_engine = {}
    for rname, r in sorted(report["replicas"].items()):
        key = r["engine"]
        names, _ = by_engine.get(key, ([], 0))
        by_engine[key] = (names + [rname], r["engine_health"]["examples"])
    total_examples = sum(ex for _, ex in by_engine.values()) or 1
    util = {"+".join(names): round(ex / total_examples, 3)
            for names, ex in by_engine.values()}
    router.close()
    if not done:
        raise RuntimeError("fleet bench completed no requests: %s"
                           % errors[:3])
    out = {
        "metric": "fleet_%s_r%d_qps%g" % (name, nrep, qps),
        "value": round(rps_fleet / max(rps_single, 1e-9), 3),
        "unit": "x_single_replica_rps",
        "replicas": nrep,
        "qps_target": qps,
        "rps_fleet": round(rps_fleet, 2),
        "rps_single": round(rps_single, 2),
        "scaling": round(rps_fleet / max(rps_single, 1e-9), 3),
        "completed": done,
        "failed": len(errors),
        "single_phase_failed": len(err1),
        "emulated_device_ms": device_ms,
        "drain_event": drain_state["event"] if do_drain else "disabled",
        "requeued": report["fleet"]["requeued"],
        "shed": report["fleet"]["shed"],
        "expired": report["fleet"]["expired"],
        "dropped": report["fleet"]["dropped"],
        "utilization": util,
        "tracecheck_findings": len(findings),
        "retraces": tracecheck.retrace_count(),
    }
    for c in serving.FLEET_CLASSES:
        if lat.get(c):
            out[c] = dict(_percentiles_ms(lat[c]),
                          completed=len(lat[c]))
    out["single"] = dict(_percentiles_ms(sum(lat1.values(), [])),
                         completed=done1)
    out["obs"] = _obs_block()
    print(json.dumps(out))


def _dp_scaling_row(sym, dshape, batch, sdtype, cdtype, remat, spd, rounds):
    """BENCH_DP_DEVICES=N: measure the fused K-step scan single-device and
    sharded over an N-way 'data' mesh at the SAME global batch (docs/perf.md
    "Data-parallel scaling"). Both sides run the identical run_steps harness
    so the efficiency ratio compares like with like; the superbatch is
    device-resident (landed sharded once), so this is pure step scaling,
    not input scaling."""
    import jax.numpy as jnp
    from mxnet_tpu.train_step import TrainStep
    from mxnet_tpu.parallel.mesh import data_parallel_mesh

    n = benv("BENCH_DP_DEVICES")
    k = max(1, spd)
    sharded = {}  # the n-device side's program + struct args for commscheck

    def measure(mesh):
        step = TrainStep(
            sym, optimizer="sgd", learning_rate=0.1, momentum=0.9, wd=1e-4,
            dtype=sdtype, mesh=mesh,
            remat={"conv": "conv", "full": True}.get(remat, False),
            compute_dtype=None if cdtype == "float32" else cdtype)
        state = step.init({"data": dshape}, {"softmax_label": (batch,)})
        rng = np.random.default_rng(0)
        sb = step.shard_superbatch({
            "data": np.stack([rng.normal(size=dshape).astype(np.float32)]
                             * k),
            "softmax_label": np.stack(
                [rng.integers(0, 1000, batch).astype(np.float32)] * k)})
        if mesh is not None:
            # struct capture BEFORE measuring: the scan donates the state
            # buffers, and the comms analyzer needs only shardings/shapes
            from mxnet_tpu import commscheck
            sharded["args"] = commscheck.struct_args(
                (state, sb, step._dispatch_key(),
                 jnp.zeros((k,), jnp.float32)))
            sharded["step"] = step
            sharded["mesh"] = mesh
        # keep measured *steps* roughly constant as K grows (as main does)
        n_short = max(2, (20 + k - 1) // k)
        n_long = max(n_short + 5, (120 + k - 1) // k)
        return measure_scan_ips(step, state, sb, batch, k, n_short, n_long,
                                rounds=rounds)

    ips1 = measure(None)
    ipsn = measure(data_parallel_mesh(n))
    row = {
        "n_devices": n,
        "img_per_sec": round(ipsn, 2),
        "img_per_sec_1chip": round(ips1, 2),
        "scaling_efficiency": (round(ipsn / ips1, 3) if ips1 > 0 else None),
    }
    # static comms profile of the measured sharded scan (one extra compile;
    # docs/static_analysis.md "Communication lints"): the roofline's
    # prediction rides next to the measured efficiency, so the gap between
    # model and machine is visible in every BENCH_DP_DEVICES line
    try:
        from mxnet_tpu import commscheck
        rep = commscheck.analyze(
            sharded["step"]._jit_scan[(batch, k)], sharded["args"],
            name="bench-dp-scan", mesh=sharded["mesh"], loop_trips=k)
        row["collective_count"] = rep.collective_count
        row["collective_bytes"] = rep.collective_bytes
        row["predicted_efficiency"] = (
            None if rep.predicted_efficiency is None
            else round(rep.predicted_efficiency, 3))
    except Exception as exc:
        print("WARNING: commscheck analysis failed, no dp comms fields "
              "emitted: %r" % exc, file=sys.stderr)
    return row


def lm_main():
    """BENCH_LM=1: flagship transformer-LM training bench (docs/perf.md
    "Flagship LM"): steady-state tokens/sec + MFU through the SAME fused
    K-step scan harness as the ResNet headline (measure_scan_ips — one
    methodology, so the LM and vision lines compare like with like),
    then one row per mesh spec in BENCH_LM_MESHES — dp, sp (ring
    attention over the 'seq' axis) and the composed dp x sp mesh — at
    the SAME global batch, each with measured scaling efficiency AND the
    commscheck roofline's prediction riding next to it, so the gap
    between model and machine is visible per mesh."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import models, tracecheck
    from mxnet_tpu.train_step import TrainStep
    from mxnet_tpu.parallel.mesh import mesh_from_spec

    batch = benv("BENCH_LM_BATCH")
    seq = benv("BENCH_LM_SEQ")
    vocab = benv("BENCH_LM_VOCAB")
    embed = benv("BENCH_LM_EMBED")
    layers = benv("BENCH_LM_LAYERS")
    heads = benv("BENCH_LM_HEADS")
    cdtype = benv("BENCH_LM_DTYPE")
    rounds = benv("BENCH_ROUNDS")
    mesh_specs = [m.strip() for m in benv("BENCH_LM_MESHES").split(";")
                  if m.strip()]

    sym = models.transformer(vocab_size=vocab, embed=embed,
                             num_heads=heads, num_layers=layers,
                             seq_len=seq)
    # meshes carrying a 'seq' axis run RING attention (the flagship
    # sequence-parallel mode: K/V rotate over the axis via ppermute)
    # with the rank-3 preserve_shape head, instead of leaving the
    # seq-sharded tensors to GSPMD's generic resharding — same math,
    # but the measured collectives are the ring's, and the head never
    # merges the sharded batch x seq dims (no per-trip all-gather)
    sym_ring = models.transformer(vocab_size=vocab, embed=embed,
                                  num_heads=heads, num_layers=layers,
                                  seq_len=seq, seq_parallel="ring",
                                  preserve_shape=True)

    # BENCH_STEPS_PER_DISPATCH resolution: env > tuning DB > mode default
    # (4 — the LM bench IS the steady-state story), the same precedence
    # chain as the headline bench, and the JSON line says which source won
    from mxnet_tpu import autotune as _autotune
    spd = benv("BENCH_STEPS_PER_DISPATCH", 4)
    at_block = {"steps_per_dispatch": {
        "value": spd,
        "source": "env" if env_set("BENCH_STEPS_PER_DISPATCH")
        else "default"}}
    if at_block["steps_per_dispatch"]["source"] == "default":
        db_key, db_knobs = _autotune.resolve_train_knobs(sym, batch)
        if db_knobs and "steps_per_dispatch" in db_knobs:
            spd = max(1, int(db_knobs["steps_per_dispatch"]))
            at_block = {"steps_per_dispatch": {"value": spd,
                                               "source": "db"},
                        "db_entry": db_key,
                        "db": _autotune.default_db_path()}
            _autotune.note_db_resolution(None, "bench.py", db_key,
                                         {"steps_per_dispatch": spd})
    k = max(1, spd)

    # every mesh spec is validated BEFORE the headline measurement
    # (mesh_from_spec fails with the XLA_FLAGS recipe on a device
    # shortfall; shard_superbatch names the failing axis + dimension on
    # a divisibility miss at each row's build) — a misconfigured env
    # must not discard minutes of already-measured throughput
    meshes = [(spec, mesh_from_spec(spec)) for spec in mesh_specs]

    rng = np.random.default_rng(0)
    data_h = rng.integers(0, vocab, (batch, seq)).astype(np.float32)
    label_h = rng.integers(0, vocab, (batch, seq)).astype(np.float32)
    # keep measured *steps* roughly constant as K grows (as the headline
    # bench does; the LM is heavier per step so the counts start lower)
    n_short = max(2, (12 + k - 1) // k)
    n_long = max(n_short + 3, (48 + k - 1) // k)

    def measure(mesh):
        """(samples/sec, TrainStep, scan struct-args) for one mesh. The
        struct capture happens BEFORE measuring: the scan donates the
        state buffers, and the analyzers need only shapes + shardings."""
        from mxnet_tpu import commscheck
        from mxnet_tpu.parallel.mesh import AXIS_SEQ
        seq_mesh = mesh is not None and AXIS_SEQ in mesh.axis_names
        s = sym_ring if seq_mesh else sym
        # pos_embed rows live with their 'seq' shard (replicated, the
        # naturally seq-sharded grad pays an all-gather every trip)
        shardings = ({"pos_embed_weight":
                      jax.sharding.PartitionSpec(AXIS_SEQ, None)}
                     if seq_mesh else None)
        step = TrainStep(
            s, optimizer="sgd", learning_rate=0.1, momentum=0.9,
            wd=1e-4, mesh=mesh, param_shardings=shardings,
            compute_dtype=None if cdtype == "float32" else cdtype)
        state = step.init({"data": (batch, seq)},
                          {"softmax_label": (batch, seq)})
        sb = step.shard_superbatch({
            "data": np.stack([data_h] * k),
            "softmax_label": np.stack([label_h] * k)})
        args = commscheck.struct_args(
            (state, sb, step._dispatch_key(),
             jnp.zeros((k,), jnp.float32)))
        ips = measure_scan_ips(step, state, sb, batch, k, n_short,
                               n_long, rounds=rounds)
        return ips, step, args

    ips1, step1, args1 = measure(None)
    if ips1 <= 0.0:
        raise RuntimeError(
            "LM benchmark produced no valid measurement (rounds=%d)"
            % rounds)

    # exact FLOPs from XLA's cost model on the SINGLE LM step (lowered
    # from the captured structs — the live state is already donated; the
    # scan lowers to a While whose body the cost model counts once, so
    # the per-token figure must come from the per-step computation)
    flops_per_sample = None
    try:
        state_s, sb_s, key_s, _lrs = args1
        if batch not in step1._jit:
            step1._jit[batch] = step1._build(batch)
        step_args = (state_s,
                     {n: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
                      for n, v in sb_s.items()},
                     key_s, jax.ShapeDtypeStruct((), np.float32))
        lowered = step1._jit[batch].lower(*step_args)
        try:
            ca = lowered.cost_analysis()
        except Exception:
            ca = None
        if ca is None:  # pre-compile analysis unsupported on this backend
            ca = lowered.compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        flops_per_sample = float(ca["flops"]) / batch
    except Exception as exc:  # MFU is a headline metric: never drop silently
        print("WARNING: cost analysis failed, no MFU emitted: %r" % exc,
              file=sys.stderr)

    # static memory + comms profile of the measured single-device scan
    # (ONE extra compile shared by both analyzers, exactly as the
    # headline bench does for its measured program)
    mem = None
    comms = None
    compiled1 = None
    try:
        from mxnet_tpu import memcheck
        compiled1 = step1._jit_scan[(batch, k)].lower(*args1).compile()
        mem = memcheck.analyze_compiled(
            compiled1, "bench-lm-scan", args=args1, donate_argnums=(0,))
    except Exception as exc:  # the bench number must survive an analyzer bug
        print("WARNING: memcheck analysis failed, no HBM fields emitted: "
              "%r" % exc, file=sys.stderr)
    try:
        from mxnet_tpu import commscheck
        if compiled1 is not None:
            comms = commscheck.analyze_compiled(
                compiled1, "bench-lm-scan", loop_trips=k)
    except Exception as exc:
        print("WARNING: commscheck analysis failed, no comms fields "
              "emitted: %r" % exc, file=sys.stderr)
    roof = None
    try:
        from mxnet_tpu import flopcheck
        if compiled1 is not None:
            roof = flopcheck.analyze_compiled(
                compiled1, "bench-lm-scan", loop_trips=k)
    except Exception as exc:
        print("WARNING: flopcheck analysis failed, no roofline fields "
              "emitted: %r" % exc, file=sys.stderr)

    # per-mesh rows: SAME global batch, SAME harness; the sharded scan's
    # comms audit (commscheck.analyze compiles from the captured sharded
    # structs) puts the roofline prediction next to the measured ratio
    rows = []
    for spec, mesh in meshes:
        ipsn, stepn, argsn = measure(mesh)
        row = {
            "mesh": spec,
            "n_devices": int(np.prod(list(mesh.shape.values()))),
            "tokens_per_sec": round(ipsn * seq, 1),
            "samples_per_sec": round(ipsn, 2),
            "scaling_efficiency": (round(ipsn / ips1, 3)
                                   if ips1 > 0 else None),
        }
        try:
            from mxnet_tpu import commscheck
            rep = commscheck.analyze(
                stepn._jit_scan[(batch, k)], argsn,
                name="bench-lm-scan[%s]" % spec, mesh=mesh, loop_trips=k)
            row["collective_count"] = rep.collective_count
            row["collective_bytes"] = rep.collective_bytes
            row["predicted_efficiency"] = (
                None if rep.predicted_efficiency is None
                else round(rep.predicted_efficiency, 3))
        except Exception as exc:
            print("WARNING: commscheck analysis failed for mesh %s, no "
                  "comms fields emitted: %r" % (spec, exc),
                  file=sys.stderr)
        rows.append(row)

    peak, kind = _peak_flops(jax.devices()[0])
    out = {
        "metric": "lm_train_tokens_per_sec_b%d_s%d_%s_k%d"
                  % (batch, seq, cdtype, k),
        "value": round(ips1 * seq, 1),
        "unit": "tokens/sec",
        "samples_per_sec": round(ips1, 2),
        "tokens_per_sample": seq,
        "model": {"vocab_size": vocab, "embed": embed,
                  "num_layers": layers, "num_heads": heads,
                  "seq_len": seq, "batch": batch},
        "steps_per_dispatch": k,
        # unexpected jit-cache misses during the measured run — a retrace
        # storm invalidates the steady-state number
        "retraces": tracecheck.retrace_count(),
    }
    if mem is not None:
        out["hbm_peak_bytes"] = mem.peak_bytes
        out["temp_bytes"] = mem.temp_bytes
        out["alias_bytes"] = mem.alias_bytes
    if comms is not None:
        out["collective_count"] = comms.collective_count
        out["collective_bytes"] = comms.collective_bytes
        out["predicted_efficiency"] = (
            None if comms.predicted_efficiency is None
            else round(comms.predicted_efficiency, 3))
    if roof is not None and not roof.hlo_unavailable:
        # the flopcheck roofline's forecast rides next to the measured
        # number: a widening measured-vs-predicted MFU gap means either
        # the wire model drifted or the schedule did
        out["predicted_step_ms"] = round(roof.predicted_step_ms, 4)
        if roof.predicted_mfu is not None:
            out["predicted_mfu"] = round(roof.predicted_mfu, 6)
    if flops_per_sample:
        out["gflop_per_token_xla"] = round(flops_per_sample / seq / 1e9, 4)
        out["achieved_tflops"] = round(ips1 * flops_per_sample / 1e12, 4)
        # MFU only for bf16 compute: the peak table is the bf16 peak,
        # and fp32 runs against it would understate utilization
        if peak and cdtype == "bfloat16":
            out["mfu"] = round(ips1 * flops_per_sample / peak, 6)
            out["device_kind"] = kind
            out["peak_tflops_bf16"] = peak / 1e12
    out["meshes"] = rows
    out["autotune"] = at_block
    out["obs"] = _obs_block()
    print(json.dumps(out))


def main():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import models
    from mxnet_tpu.train_step import TrainStep

    batch = benv("BENCH_BATCH")
    rounds = benv("BENCH_ROUNDS")
    depth = benv("BENCH_DEPTH")
    image = benv("BENCH_IMAGE")
    cdtype = benv("BENCH_DTYPE")
    dp_n = benv("BENCH_DP_DEVICES")
    if dp_n > 1:
        # validate BEFORE the headline measurement: a misconfigured env
        # must not discard minutes of already-measured throughput
        if len(jax.devices()) < dp_n:
            raise SystemExit(
                "BENCH_DP_DEVICES=%d but only %d device(s) are visible — "
                "on CPU raise the count with XLA_FLAGS="
                "--xla_force_host_platform_device_count=%d"
                % (dp_n, len(jax.devices()), dp_n))
        if batch % dp_n:
            raise SystemExit(
                "BENCH_DP_DEVICES=%d does not divide BENCH_BATCH=%d — the "
                "sharded scan needs equal per-chip shards"
                % (dp_n, batch))
    baseline = 181.53  # P100, ResNet-50 train b32 (docs/how_to/perf.md:183-190)

    # measured r4: remat=conv loses ~17% on v5e (recompute re-reads conv
    # outputs; chip is HBM-bound) — remat stays a memory knob, not a default
    remat = benv("BENCH_REMAT")  # conv|full|off
    # measured r4: NHWC+Pallas conv+BN-stats fusion is 2x SLOWER than
    # letting XLA fuse (docs/perf.md r4 section) — NCHW/XLA stays default
    layout = benv("BENCH_LAYOUT")
    dshape = ((batch, image, image, 3) if layout == "NHWC"
              else (batch, 3, image, image))
    # BENCH_STORAGE_DTYPE=bfloat16 stores params+optimizer state in bf16
    # (no f32 masters) — measured r5, see docs/perf.md
    sdtype = benv("BENCH_STORAGE_DTYPE")
    sym = models.resnet(num_classes=1000, num_layers=depth,
                        image_shape="3,%d,%d" % (image, image),
                        layout=layout)
    step = TrainStep(sym, optimizer="sgd", learning_rate=0.1, momentum=0.9,
                     wd=1e-4, dtype=sdtype,
                     remat={"conv": "conv", "full": True}.get(remat, False),
                     compute_dtype=None if cdtype == "float32" else cdtype)
    # storage dtype != f32 forces compute to the storage dtype inside
    # TrainStep; label the run by what actually executed
    if step.compute_dtype is not None:
        cdtype = np.dtype(step.compute_dtype).name
    state = step.init({"data": dshape}, {"softmax_label": (batch,)})

    rng = np.random.default_rng(0)
    data = {"data": jnp.asarray(rng.normal(size=dshape), np.float32),
            "softmax_label": jnp.asarray(rng.integers(0, 1000, batch),
                                         np.float32)}

    # steady-state bulked mode: K steps per dispatch via TrainStep.run_steps
    # (lax.scan). The superbatch is built ON DEVICE once — input cost is out
    # of the loop, so this measures the pure dispatch-amortization win the
    # per-step mode leaves on the table.
    # BENCH_STEPS_PER_DISPATCH resolution (docs/perf.md "Autotuning"):
    # env > tuning DB > default — and the JSON line SAYS which source won,
    # so a bench number is always attributable to its configuration
    from mxnet_tpu import autotune as _autotune
    spd = benv("BENCH_STEPS_PER_DISPATCH")
    at_block = {"steps_per_dispatch": {
        "value": spd,
        "source": "env" if env_set("BENCH_STEPS_PER_DISPATCH")
        else "default"}}
    if at_block["steps_per_dispatch"]["source"] == "default":
        db_key, db_knobs = _autotune.resolve_train_knobs(sym, batch)
        if db_knobs and "steps_per_dispatch" in db_knobs:
            spd = max(1, int(db_knobs["steps_per_dispatch"]))
            at_block = {"steps_per_dispatch": {"value": spd,
                                               "source": "db"},
                        "db_entry": db_key,
                        "db": _autotune.default_db_path()}
            _autotune.note_db_resolution(None, "bench.py", db_key,
                                         {"steps_per_dispatch": spd})
    if spd > 1:
        sbatch = {n: jnp.stack([v] * spd) for n, v in data.items()}

        def run(state, dispatches):
            t0 = time.perf_counter()
            for _ in range(dispatches):
                state, _metrics = step.run_steps(state, sbatch)
            np.asarray(state["step"])  # readback ends the timed region
            return time.perf_counter() - t0, state

        # keep measured *steps* roughly constant as K grows
        n_short = max(2, (20 + spd - 1) // spd)
        n_long = max(n_short + 5, (120 + spd - 1) // spd)
        imgs_per_dispatch = batch * spd
    else:
        def run(state, steps):
            t0 = time.perf_counter()
            for _ in range(steps):
                state, _outs = step.step(state, data)
            np.asarray(state["step"])  # readback ends the timed region
            return time.perf_counter() - t0, state

        n_short, n_long = 20, 120
        imgs_per_dispatch = batch

    # warmup / compile: a compile failure on the chip is the finding
    _, state = run(state, 3)

    best_ips = 0.0
    for _ in range(rounds):
        t_short, state = run(state, n_short)
        t_long, state = run(state, n_long)
        if t_long > t_short:
            best_ips = max(best_ips, imgs_per_dispatch * (n_long - n_short)
                           / (t_long - t_short))
    if best_ips <= 0.0:
        raise RuntimeError(
            "benchmark produced no valid measurement (rounds=%d)" % rounds)
    ips = best_ips

    # exact FLOPs from XLA's cost model on the SINGLE step (lowered, not
    # recompiled) in both modes: the scan lowers to a While whose body the
    # cost model counts once, not trip-count times, so the per-image figure
    # must come from the per-step computation
    flops_per_img = None
    step_compiled = None  # shared with the memory profile below
    step_args = None
    try:
        key = jax.random.key(0)
        lr_base = jnp.asarray(0.1, jnp.float32)
        if batch not in step._jit:
            step._jit[batch] = step._build(batch)
        step_args = (state, data, key, lr_base)
        lowered = step._jit[batch].lower(*step_args)
        try:
            ca = lowered.cost_analysis()
        except Exception:
            ca = None
        if ca is None:  # pre-compile analysis unsupported on this backend
            step_compiled = lowered.compile()
            ca = step_compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        flops_per_img = float(ca["flops"]) / batch
    except Exception as exc:  # MFU is a headline metric: never drop silently
        print("WARNING: cost analysis failed, no MFU emitted: %r" % exc,
              file=sys.stderr)
        lowered = None

    # static memory profile of the program that actually ran (docs/
    # static_analysis.md "Memory lints"): peak HBM + temp bytes ride next
    # to img/s, so a fusion/remat regression that doubles temps is visible
    # in the same JSON line that would show the throughput cost. The
    # single-step mode reuses the cost-analysis lowering (at most ONE
    # extra compile); the scan mode pays one compile of the scan — the
    # measured program — since jit exposes no handle to its executable.
    mem = None
    comms = None
    measured_compiled = None  # ONE compile shared by both analyzers
    try:
        from mxnet_tpu import memcheck
        if spd > 1:
            scan_args = (state, sbatch, step._dispatch_key(),
                         jnp.zeros((spd,), jnp.float32))
            measured_compiled = step._jit_scan[(batch, spd)] \
                .lower(*scan_args).compile()
            mem = memcheck.analyze_compiled(
                measured_compiled, "bench-scan", args=scan_args,
                donate_argnums=(0,))
        elif lowered is not None:
            if step_compiled is None:
                step_compiled = lowered.compile()
            measured_compiled = step_compiled
            mem = memcheck.analyze_compiled(
                step_compiled, "bench-step", args=step_args,
                donate_argnums=(0,))
    except Exception as exc:  # the bench number must survive an analyzer bug
        print("WARNING: memcheck analysis failed, no HBM fields emitted: %r"
              % exc, file=sys.stderr)
    # static comms profile of the same executable (docs/static_analysis.md
    # "Communication lints"): collective count/bytes + the roofline's
    # predicted scaling efficiency ride next to img/s and hbm_peak_bytes —
    # zero collectives and efficiency 1.0 on a single-device run, so a
    # sharding change that makes the headline program communicate shows in
    # the same JSON line as its throughput cost
    try:
        from mxnet_tpu import commscheck
        if measured_compiled is not None:
            comms = commscheck.analyze_compiled(
                measured_compiled,
                "bench-scan" if spd > 1 else "bench-step",
                mesh=step.mesh, loop_trips=max(1, spd))
    except Exception as exc:
        print("WARNING: commscheck analysis failed, no comms fields "
              "emitted: %r" % exc, file=sys.stderr)
    # static roofline forecast of the same executable (docs/
    # static_analysis.md "Roofline lints"): predicted step time + MFU
    # ride next to the measured img/s so the forecast-vs-measured gap is
    # one JSON line — the third analyzer sharing measured_compiled's
    # single compile
    roof = None
    try:
        from mxnet_tpu import flopcheck
        if measured_compiled is not None:
            roof = flopcheck.analyze_compiled(
                measured_compiled,
                "bench-scan" if spd > 1 else "bench-step",
                mesh=step.mesh, loop_trips=max(1, spd))
    except Exception as exc:
        print("WARNING: flopcheck analysis failed, no roofline fields "
              "emitted: %r" % exc, file=sys.stderr)

    peak, kind = _peak_flops(jax.devices()[0])
    metric = "resnet%d_train_images_per_sec_b%d_%s" % (depth, batch, cdtype)
    if sdtype != "float32":
        metric += "_store_%s" % sdtype
    if spd > 1:
        metric += "_k%d" % spd
    from mxnet_tpu import tracecheck
    out = {
        "metric": metric,
        "value": round(ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(ips / baseline, 3),
        # unexpected jit-cache misses during the measured run — a retrace
        # storm invalidates the steady-state number (docs/static_analysis.md)
        "retraces": tracecheck.retrace_count(),
    }
    if spd > 1:
        out["steps_per_dispatch"] = spd
    if mem is not None:
        out["hbm_peak_bytes"] = mem.peak_bytes
        out["temp_bytes"] = mem.temp_bytes
        out["alias_bytes"] = mem.alias_bytes
    if comms is not None:
        out["collective_count"] = comms.collective_count
        out["collective_bytes"] = comms.collective_bytes
        out["predicted_efficiency"] = (
            None if comms.predicted_efficiency is None
            else round(comms.predicted_efficiency, 3))
    if roof is not None and not roof.hlo_unavailable:
        out["predicted_step_ms"] = round(roof.predicted_step_ms, 4)
        if roof.predicted_mfu is not None:
            out["predicted_mfu"] = round(roof.predicted_mfu, 6)
    if flops_per_img:
        out["gflop_per_image_xla"] = round(flops_per_img / 1e9, 2)
        out["achieved_tflops"] = round(ips * flops_per_img / 1e12, 1)
        # MFU only for bf16 compute: the peak table is the bf16 peak, and
        # fp32 runs against it would understate utilization several-fold
        if peak and cdtype == "bfloat16":
            out["mfu"] = round(ips * flops_per_img / peak, 4)
            out["device_kind"] = kind
            out["peak_tflops_bf16"] = peak / 1e12
    if dp_n > 1:
        out["dp"] = _dp_scaling_row(sym, dshape, batch, sdtype, cdtype,
                                    remat, spd, rounds)
    out["autotune"] = at_block
    out["obs"] = _obs_block()
    print(json.dumps(out))


if __name__ == "__main__":
    from mxnet_tpu import engine
    engine.setup_compile_cache()
    if benv("BENCH_ZOO_DISPATCH"):
        zoo_dispatch_main()
    elif benv("BENCH_REAL_DATA"):
        realdata_main()
    elif benv("BENCH_LM"):
        lm_main()
    elif benv("BENCH_FLEET"):
        fleet_main()
    elif benv("BENCH_DECODE"):
        decode_main()
    elif benv("BENCH_SERVE"):
        serve_main()
    elif benv("BENCH_HOST_OVERHEAD"):
        host_overhead_main()
    else:
        main()
