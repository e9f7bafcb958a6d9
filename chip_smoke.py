#!/usr/bin/env python
"""chip_smoke.py: the quickest proof that the system starts on the chip.

One process drives the two hot paths once through the entry points a user
calls, at the full width of models the repo ships, with random weights made
from a seed, and checks what comes out:

* train  — what ``example/image-classification/train_imagenet.py
  --synthetic`` does: ResNet-50, 1000 classes, 3x224x224 through
  ``Module.fit(steps_per_dispatch=4)`` on ``mx.tpu(0)``;
* deploy — ``save_checkpoint`` then ``serving.ServingEngine`` over the
  files, against ``mod.predict`` on the same rows;
* decode — ``serving.DecodeLoop`` over a GPT-2-small-shaped
  ``models.transformer`` with more requests than slots, against a full
  forward of the plain symbol.

* latent — ``serving.DecodeLoop(arch=DeepseekV3Arch)`` at Kimi-K2's
  published widths and depth 2 (the dense layer and one expert layer that
  holds 12 of 384 experts), bfloat16: the step program's inventory against
  the latent cache, and the routing counters against the steps.

On a host with four chips the train leg also runs data-parallel over all of
them. Times are printed as set-up facts; this script measures no rate. It
refuses to run without a TPU (no CPU stand-in), catches no leg's exception,
and prints as its LAST stdout line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The legs are functions of their shapes so ``tests/test_chip_smoke.py`` runs
them tiny on CPU.
"""
import importlib.util
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the batch of the 2026-07 chip record (CHANGES.md PR 21). If the f32
#: program stops fitting 16 GB, lower the BATCH (64, then 32, the
#: reference-parity size) — never depth, width or image size — and record
#: which batch ran in CHANGES.md
TRAIN_BATCH = 128
#: served-vs-predict tolerance on softmax probabilities. The bucket-1 and
#: padded bucket-8 programs are different programs from the batch-8 predict
#: one and may associate their sums differently, so bitwise is not
#: promised; observed on the v5e: 0.0 at 1, 3 and 8 rows (chip run, PR 21)
DEPLOY_ATOL = 1e-5
#: greedy-token identity is asserted only where the reference forward's
#: top-2 log-probability margin exceeds this (nats): f32 matmuls run at the
#: chip's default precision and the decode body associates differently
#: from the full forward, so near-ties may legitimately flip. Observed on
#: the v5e: all 12 first tokens agree, down to a margin of 0.033, and 9 of
#: the 12 prompts clear 0.1 (chip run, PR 21)
DECODE_MARGIN = 0.1
#: the most instructions that may take one weight matrix of the prefill
#: program (``weight_reads``): the chip's compiler fetches a matrix in
#: four pieces (chip run, PR 37); a chunk has 128 positions
WEIGHT_PIECES = 8
#: Kimi-K2-Instruct's published widths (``config.json``), cut in depth, in
#: experts held and in vocabulary as ``benchmark/configs/kimi-k2-ep32.json``
#: cuts them, and to depth 2 here: the dense layer and one expert layer
KIMI_K2_DEPTH2 = {
    "hidden_size": 7168, "num_attention_heads": 64, "q_lora_rank": 1536,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
    "n_shared_experts": 1, "n_routed_experts": 12, "router_width": 384,
    "first_k_dense_replace": 1, "num_hidden_layers": 2, "vocab_size": 20480,
    "rms_norm_eps": 1e-6, "rope_theta": 50000,
    "routed_scaling_factor": 2.827, "scoring_func": "sigmoid",
    "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"}}


class CompileMeter(object):
    """What JAX itself reports about compilation while it is alive:
    seconds inside the backend-compile call (a persistent-cache hit costs
    only its retrieval, which is how a warm cache shows) and the number of
    programs and cache hits. ``cache_dir`` rides along so every leg's
    report names the directory in use."""

    def __init__(self, cache_dir=None):
        import jax
        self.cache_dir = cache_dir
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += seconds
                self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def snapshot(self):
        with self._lock:
            return (self.seconds, self.programs, self.cache_hits)

    def since(self, snap):
        now = self.snapshot()
        return {"compile_seconds": round(now[0] - snap[0], 2),
                "programs_compiled": now[1] - snap[1],
                "cache_hits": now[2] - snap[2]}


def device_facts():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _report(leg, facts, meter, snap):
    import jax
    import jaxlib
    out = {"leg": leg, "jax": jax.__version__, "jaxlib": jaxlib.__version__,
           "device": device_facts(), "cache_dir": meter.cache_dir}
    out.update(meter.since(snap))
    out.update(facts)
    print("chip_smoke %s" % json.dumps(out), flush=True)
    return out


def _memory_stats(device):
    """``device.memory_stats()`` (None where the backend reports none)."""
    stats = device.memory_stats()
    if not stats:
        return None
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                       "bytes_limit") if k in stats}


def _synthetic_iter_class():
    """The trainer's own SyntheticIter, loaded from the example script so
    the leg feeds ``fit`` exactly what ``--synthetic`` feeds it."""
    path = os.path.join(ROOT, "example", "image-classification",
                        "train_imagenet.py")
    spec = importlib.util.spec_from_file_location("train_imagenet", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.SyntheticIter


def train_leg(meter, contexts, batch, image_shape, num_layers,
              num_classes, k=4, dispatches=6):
    """``Module.fit(steps_per_dispatch=k)`` on a fixed synthetic batch over
    ``contexts`` (one context = one chip; several = the data-parallel
    mesh). Returns ``(module, facts)``; raises on any failed check."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import models, tracecheck
    from mxnet_tpu.parallel.mesh import data_axis_size

    snap = meter.snapshot()
    devices = [c.to_device() for c in contexts]
    mem_before = [_memory_stats(d) for d in devices]
    sym = models.resnet(num_classes=num_classes, num_layers=num_layers,
                        image_shape=",".join(str(d) for d in image_shape))
    mod = mx.mod.Module(sym, context=contexts)
    train = _synthetic_iter_class()(batch, tuple(image_shape), num_classes,
                                    epoch_size=k * dispatches)

    losses = []          # mean cross-entropy of each retired dispatch
    seen = [0.0, 0]
    warm = []            # (retrace count, seconds) when dispatch 1 retired

    def on_dispatch(param):
        ce = param.eval_metric.metrics[1]
        dsum, dnum = ce.sum_metric - seen[0], ce.num_inst - seen[1]
        seen[0], seen[1] = ce.sum_metric, ce.num_inst
        losses.append(dsum / dnum)
        if not warm:
            warm.append((tracecheck.retrace_count(),
                         time.perf_counter() - t0))

    t0 = time.perf_counter()
    mod.fit(train, num_epoch=1, eval_metric=["acc", "ce"],
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in", magnitude=2),
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                              "wd": 1e-4},
            steps_per_dispatch=k, batch_end_callback=on_dispatch)
    jax.block_until_ready(mod._fused_state["params"])
    fit_seconds = time.perf_counter() - t0

    # the scan engaged at (batch, k): no silent k=1 fallback
    scan_keys = list(mod._fused._jit_scan)
    if not any(key[0] == batch and key[1] == k for key in scan_keys):
        raise AssertionError("fused scan did not engage at (batch=%d, k=%d): "
                             "_jit_scan keys %r" % (batch, k, scan_keys))
    if len(losses) != dispatches:
        raise AssertionError("expected %d dispatches, retired %d"
                             % (dispatches, len(losses)))
    if not all(np.isfinite(losses)):
        raise AssertionError("non-finite loss: %r" % (losses,))
    if not losses[-1] < losses[0]:
        raise AssertionError("loss did not fall on a fixed batch: first "
                             "dispatch %.4f, last %.4f" % (losses[0],
                                                           losses[-1]))
    retraced = tracecheck.retrace_count() - warm[0][0]
    if retraced:
        raise AssertionError("%d retrace(s) after warm-up: %r"
                             % (retraced, tracecheck.RETRACE_EVENTS[-1]))
    # every parameter leaf lives on exactly the devices that were asked for
    for name, leaf in mod._fused_state["params"].items():
        if leaf.devices() != set(devices):
            raise AssertionError("param %s on %r, expected %r"
                                 % (name, leaf.devices(), devices))
    facts = {"batch": batch, "k": k, "dispatches": dispatches,
             "scan_keys": [repr(key[:2]) for key in scan_keys],
             "loss_first": round(float(losses[0]), 4),
             "loss_last": round(float(losses[-1]), 4),
             "first_dispatch_seconds": round(warm[0][1], 2),
             "fit_seconds": round(fit_seconds, 2),
             "param_devices": [str(d) for d in devices],
             "memory": _memory_stats(devices[0])}

    if len(devices) > 1:
        from jax.sharding import PartitionSpec as P
        mesh = mod._fused.mesh
        if mesh is None or data_axis_size(mesh) != len(devices):
            raise AssertionError("no %d-way 'data' mesh: %r"
                                 % (len(devices), mesh))
        if set(mesh.devices.flat) != set(devices):
            raise AssertionError("mesh over %r, asked for %r"
                                 % (list(mesh.devices.flat), devices))
        for name, leaf in mod._fused_state["params"].items():
            if not leaf.sharding.is_fully_replicated:
                raise AssertionError("param %s is not replicated: %r"
                                     % (name, leaf.sharding))
        # the superbatch the producer lands: step axis replicated, batch
        # axis split, one shard per chip, and the dispatch passes it
        # through without a resharding copy
        train.reset()
        feed = mx.data.DevicePrefetcher(
            train, k, sharding=mod._superbatch_sharding())
        sb = next(iter(feed))
        arr = sb.data[0].data
        if arr.sharding.spec != P(None, "data"):
            raise AssertionError("superbatch spec %r" % (arr.sharding.spec,))
        shard_devs = {s.device for s in arr.addressable_shards}
        if shard_devs != set(devices):
            raise AssertionError("superbatch shards on %r" % (shard_devs,))
        if mod._fused.shard_superbatch({"data": arr})["data"] is not arr:
            raise AssertionError("shard_superbatch copied a landed batch")
        mem_after = [_memory_stats(d) for d in devices]
        for d, before, after in zip(devices, mem_before, mem_after):
            if before is not None and not (after["bytes_in_use"]
                                           > before["bytes_in_use"]):
                raise AssertionError("%s holds no new state: %r -> %r"
                                     % (d, before, after))
        facts["memory_per_chip"] = mem_after
    facts = _report("train[%d chip]" % len(devices), facts, meter, snap)
    return mod, facts


def deploy_leg(meter, mod, image_shape, buckets=(1, 8), contexts=None,
               atol=DEPLOY_ATOL):
    """Checkpoint files -> ``ServingEngine`` -> ``infer`` of 1, 3 and 8
    rows, compared with ``mod.predict`` on the same rows."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving

    snap = meter.snapshot()
    rows = np.random.default_rng(1).normal(
        size=(8,) + tuple(image_shape)).astype(np.float32)
    ref = mod.predict(mx.io.NDArrayIter(rows, batch_size=8)).asnumpy()
    with tempfile.TemporaryDirectory() as tmp:
        prefix = os.path.join(tmp, "smoke")
        mod.save_checkpoint(prefix, 1)
        eng = serving.ServingEngine(
            prefix + "-symbol.json", prefix + "-0001.params",
            {"data": tuple(image_shape)}, buckets=buckets, contexts=contexts)
    worst = 0.0
    for n in (1, 3, 8):
        out = eng.infer({"data": rows[:n]})[0]
        if out.shape != (n, ref.shape[1]) or not np.all(np.isfinite(out)):
            raise AssertionError("infer(%d rows): shape %r / non-finite"
                                 % (n, out.shape))
        worst = max(worst, float(np.max(np.abs(out - ref[:n]))))
    if worst > atol:
        raise AssertionError("served outputs differ from mod.predict by "
                             "%.3g (tolerance %.3g)" % (worst, atol))
    facts = {"buckets": list(eng.buckets), "rows": [1, 3, 8],
             "max_abs_diff_vs_predict": worst, "tolerance": atol,
             "engine_devices": [str(d) for d in eng.devices],
             "dispatches": eng.health.batches}
    facts = _report("deploy", facts, meter, snap)
    return eng, facts


def lm_params(vocab, embed, heads, layers, max_len, seed=0):
    """Random f32 transformer-LM parameters under the
    ``models/transformer.py`` names (what DecodeLoop and the plain symbol
    both consume)."""
    from mxnet_tpu import models
    sym = models.transformer(vocab_size=vocab, embed=embed, num_heads=heads,
                             num_layers=layers, seq_len=max_len)
    arg_shapes, _, _ = sym.infer_shape(data=(1, max_len),
                                       softmax_label=(1, max_len))
    rs = np.random.RandomState(seed)
    return {n: (rs.randn(*s) * 0.3).astype(np.float32)
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}


def cache_relayouts(compiled, name, cache_bytes):
    """What ``flopcheck`` finds in the step program's own executable
    against a cache that must pass through a step untouched but for one
    row a slot: its ``layout-copy`` findings, and every kernel of its
    inventory that only moves data (copy, transpose, a fusion of nothing
    else) and moves as much as one cache holds. The lint alone is not
    enough: it fires on a kernel's SHARE of the program's traffic (25%),
    and each of the four cache copies the v5e trace showed before PR 28
    was 3% of this program's (PERF.md, PR 28)."""
    from mxnet_tpu import flopcheck
    rep = flopcheck.analyze_compiled(compiled, name)
    found = [f.format() for f in flopcheck.lint_report(rep)
             if f.lint == "layout-copy" and not f.suppressed]
    moved = [k for k in rep.kernels if k.is_layout]
    found += ["%s %s moves %d bytes, the cache holds %d"
              % (k.opcode, k.instruction, k.bytes * k.multiplier, cache_bytes)
              for k in moved if k.bytes * k.multiplier >= cache_bytes]
    facts = {"kernels": len(rep.kernels), "layout_kernels": len(moved),
             "layout_bytes_max": max(
                 [k.bytes * k.multiplier for k in moved] or [0]),
             "cache_bytes": cache_bytes}
    return found, facts


def weight_reads(compiled):
    """``{parameter: instructions of the entry computation that take it}``
    for every matrix among an executable's parameters, from its own text.
    A pass that batches its positions takes a weight in ONE product, or in
    the few pieces the chip's compiler fetches it into fast memory by
    (quarters: four); one unrolled over its positions takes it once a
    position."""
    import re
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    names = re.findall(r"%(params__\w+?weight\w*\.\d+) = \w+\[\d+,\d+\]\S* "
                       r"parameter\(", entry)
    return {n: len(re.findall(r"%" + re.escape(n) + r"\b", entry)) - 1
            for n in names}


def decode_leg(meter, context, layers, embed, heads, vocab,
               max_len, slots=8, requests=12, prompt_range=(32, 128),
               max_new=32, margin=DECODE_MARGIN):
    """``DecodeLoop`` with more requests than slots (join/leave), checked
    against a full forward of the plain symbol over every prompt; its
    compiled step program must not re-lay the KV cache out."""
    import mxnet_tpu as mx
    from mxnet_tpu import models, serving

    snap = meter.snapshot()
    params = lm_params(vocab, embed, heads, layers, max_len)
    rs = np.random.RandomState(2)
    lo, hi = prompt_range
    prompts = [[int(t) for t in rs.randint(0, vocab, rs.randint(lo, hi + 1))]
               for _ in range(requests)]

    loop = serving.DecodeLoop(params, layers, heads, max_len, slots=slots)
    try:
        cache = int(loop._state["k"].nbytes)
        relaid, step_facts = cache_relayouts(
            loop._step_c, loop.name + "/step", cache)
        # the prefill program holds the same rule, and reads its weights
        # once a chunk: a window unrolled position by position (the verify
        # body's form) would read them once a position
        again, prefill_facts = cache_relayouts(
            loop._prefill_c, loop.name + "/prefill", cache)
        relaid += again
        reads = weight_reads(loop._prefill_c)
        prefill_facts.update(chunk=loop._chunk, weights=len(reads),
                             weight_reads_max=max(reads.values()))
        futures = [loop.generate(p, max_new) for p in prompts]
        outs = [f.result(timeout=900.0) for f in futures]
    finally:
        loop.close()
    if relaid:
        raise AssertionError("a decode program re-lays the KV cache out: "
                             "%s" % "; ".join(relaid))
    if prefill_facts["weights"] < 4 * (layers - 1) + 3 \
            or prefill_facts["weight_reads_max"] > WEIGHT_PIECES:
        raise AssertionError("the prefill program does not read its weights "
                             "once a chunk: %r" % (reads,))
    health = loop.health.report()
    fed = sum(len(p) - 1 for p in prompts)
    if health["prompt_positions"] != fed \
            or not 0 < health["prefill_positions"] <= fed \
            or not 0 < health["prefill_passes"] < health["decode_steps"]:
        raise AssertionError("prompts were not fed in chunks: %r" % (health,))
    if not (health["joined"] == health["retired"] == requests):
        raise AssertionError("join/retire mismatch: %r" % (health,))
    if health["errors"] or health["shed"] or loop.dead is not None:
        raise AssertionError("decode loop unhealthy: %r dead=%r"
                             % (health, loop.dead))
    for out in outs:
        if len(out) != max_new or not all(0 <= t < vocab for t in out):
            raise AssertionError("bad generation: %r" % (out,))

    # reference: ONE full forward of the plain symbol over all prompts,
    # right-padded (causal attention: row len-1 sees only the prompt)
    seq = max(len(p) for p in prompts)
    tokens = np.zeros((requests, seq), np.float32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    sym = models.transformer(vocab_size=vocab, embed=embed, num_heads=heads,
                             num_layers=layers, seq_len=seq,
                             max_seq_len=max_len)
    ref = mx.mod.Module(sym, context=context)
    ref.bind(data_shapes=[("data", (requests, seq))],
             label_shapes=[("softmax_label", (requests, seq))],
             for_training=False)
    ref.set_params({k: mx.nd.array(v) for k, v in params.items()}, {})
    probs = ref.predict(mx.io.NDArrayIter(
        tokens, np.zeros((requests, seq), np.float32),
        batch_size=requests)).asnumpy().reshape(requests, seq, vocab)
    asserted, agree, margins = 0, 0, []
    for i, (p, out) in enumerate(zip(prompts, outs)):
        logp = np.log(np.maximum(probs[i, len(p) - 1].astype(np.float64),
                                 1e-300))
        top2 = np.argsort(logp)[-2:]
        gap = float(logp[top2[1]] - logp[top2[0]])
        margins.append(round(gap, 3))
        agree += out[0] == int(top2[1])
        if gap > margin:
            asserted += 1
            if out[0] != int(top2[1]):
                raise AssertionError(
                    "request %d: first token %d, full forward argmax %d at "
                    "margin %.3f" % (i, out[0], int(top2[1]), gap))
    if not asserted:
        raise AssertionError("every prompt's top-2 margin is under %.2f — "
                             "the token check asserted nothing: %r"
                             % (margin, margins))
    facts = {"shape": {"layers": layers, "embed": embed, "heads": heads,
                       "vocab": vocab, "max_len": max_len},
             "slots": slots, "requests": requests, "max_new": max_new,
             "prompt_lens": [len(p) for p in prompts],
             "decode_steps": health["decode_steps"],
             "prefill_passes": health["prefill_passes"],
             "prefill_positions": health["prefill_positions"],
             "prompt_positions": health["prompt_positions"],
             "prefill_program": prefill_facts,
             "first_token_checked": asserted,
             "first_token_agrees": int(agree), "margins": margins,
             "margin_tolerance": margin, "step_program": step_facts,
             "loop_devices": [str(d) for d in loop.devices]}
    facts = _report("decode", facts, meter, snap)
    return facts


def latent_decode_leg(meter, config, max_len, slots=8, requests=12,
                      prompt_range=(8, 24), max_new=8):
    """``DecodeLoop`` over the DeepSeek-V3 block (latent attention, a share
    of a routed-expert layer) in bfloat16, more requests than slots: its
    compiled step program must not re-lay the latent cache out, and its
    routing counters must account for every position it processed. From
    PR 39 its prompts go in by PACKED prefill passes (rows of several
    slots behind one read of the weights): the pass's executable holds the
    same rule against the cache, the passes must have engaged, and the
    last expert layer counts no row of a pass (the pass stops there at
    its latent row)."""
    import jax.numpy as jnp
    from mxnet_tpu import serving
    from mxnet_tpu.serving.decode import MIN_PREFILL

    snap = meter.snapshot()
    arch = serving.DeepseekV3Arch(config)
    # one seeded block, repeated to every leaf's size: a smoke needs
    # weights that are not degenerate, not 3e9 fresh normals
    rs = np.random.RandomState(3)
    block = rs.standard_normal(1 << 22).astype(np.float32)
    params = {}
    for i, (name, shape) in enumerate(sorted(arch.param_shapes().items())):
        x = np.resize(np.roll(block, 7919 * i), shape)
        x = 1.0 + 0.1 * x if name.endswith("_gamma") else x * (
            1.0 if name == "tok_embed_weight" else 0.02)
        params[name] = x.astype(jnp.bfloat16)
    vocab = arch.vocab_size
    lo, hi = prompt_range
    prompts = [[int(t) for t in rs.randint(0, vocab, rs.randint(lo, hi + 1))]
               for _ in range(requests)]
    loop = serving.DecodeLoop(params, max_len=max_len, slots=slots, arch=arch,
                              quantize="bf16", prefix_cache=False, spec_k=0)
    try:
        cache = int(loop._state["latent"].nbytes)
        relaid, step_facts = cache_relayouts(
            loop._step_c, loop.name + "/step", cache)
        again, prefill_facts = cache_relayouts(
            loop._prefill_c, loop.name + "/prefill", cache)
        relaid += again
        prefill_facts.update(rows=loop._chunk)
        futures = [loop.generate(p, max_new) for p in prompts]
        outs = [f.result(timeout=900.0) for f in futures]
        health = loop.health.report()
    finally:
        loop.close()
    if relaid:
        raise AssertionError("the step or the prefill program re-lays the "
                             "latent cache out: %s" % "; ".join(relaid))
    if not (health["joined"] == health["retired"] == requests) \
            or health["errors"] or health["shed"] or loop.dead is not None:
        raise AssertionError("decode loop unhealthy: %r dead=%r"
                             % (health, loop.dead))
    for out in outs:
        if len(out) != max_new or not all(0 <= t < vocab for t in out):
            raise AssertionError("bad generation: %r" % (out,))
    positions = sum(len(p) for p in prompts) + requests * (max_new - 1)
    passed = health["prefill_positions"]
    due = sum(len(p) - 1 for p in prompts if len(p) > MIN_PREFILL)
    if not (bool(passed) == bool(due) and passed <= due
            and health["prefill_passes"] <= health["prefill_slots"]):
        raise AssertionError("the prompts did not go in by passes: %r"
                             % {k: health[k] for k in (
                                 "prefill_passes", "prefill_slots",
                                 "prefill_positions", "prompt_positions")})
    routed = arch.num_experts_per_tok * (len(arch.moe_layers) * positions
                                         - passed)
    if health["moe_pairs_routed"] != routed \
            or not 0 <= health["moe_pairs_here"] <= routed:
        raise AssertionError("routing counters: %d routed, %d here, %d "
                             "positions" % (health["moe_pairs_routed"],
                                            health["moe_pairs_here"],
                                            positions))
    facts = {"shape": {k: config[k] for k in (
                 "hidden_size", "num_attention_heads", "kv_lora_rank",
                 "num_hidden_layers", "n_routed_experts", "router_width",
                 "vocab_size")},
             "max_len": max_len, "slots": slots, "requests": requests,
             "weight_bytes": loop.weight_bytes(),
             "decode_steps": health["decode_steps"],
             "moe_pairs_routed": health["moe_pairs_routed"],
             "moe_pairs_here": health["moe_pairs_here"],
             "prefill_passes": health["prefill_passes"],
             "prefill_slots": health["prefill_slots"],
             "prefill_positions": passed,
             "step_program": step_facts, "prefill_program": prefill_facts}
    return _report("latent", facts, meter, snap)


def main():
    import jax
    found = device_facts()
    if found["platform"] != "tpu":
        sys.exit("chip_smoke: needs a TPU and runs nowhere else; JAX found "
                 "platform %r (%s x%d)"
                 % (found["platform"], found["kind"], found["count"]))
    import mxnet_tpu as mx
    from mxnet_tpu import engine

    meter = CompileMeter(engine.setup_compile_cache())
    image = (3, 224, 224)
    mod, _ = train_leg(meter, [mx.tpu(0)], TRAIN_BATCH, image,
                       num_layers=50, num_classes=1000)
    eng, _ = deploy_leg(meter, mod, image)
    if eng.devices != [jax.devices()[0]]:
        raise AssertionError("engine on %r" % (eng.devices,))
    if found["count"] >= 4:
        chips = [mx.tpu(i) for i in range(4)]
        # a one-chip replica that names a chip lives on that chip
        eng3, _ = deploy_leg(meter, mod, image, buckets=(8,),
                             contexts=[chips[3]])
        if eng3.devices != [jax.devices()[3]]:
            raise AssertionError("contexts=[tpu(3)] engine on %r"
                                 % (eng3.devices,))
        del eng3
        # data-parallel over the host's four chips, same per-chip batch
        train_leg(meter, chips, 4 * TRAIN_BATCH, image,
                  num_layers=50, num_classes=1000)
    del mod, eng
    decode_leg(meter, mx.tpu(0), layers=12, embed=768, heads=12,
               vocab=50304, max_len=1024)
    latent_decode_leg(meter, KIMI_K2_DEPTH2, max_len=1024)
    print(json.dumps({"ok": True, "device": found}), flush=True)


if __name__ == "__main__":
    main()
