#!/usr/bin/env python
"""Memory-cost control: remat (gradient checkpointing) as the TPU analog of
MXNET_BACKWARD_DO_MIRROR (ref: example/memcost/, graph_executor.cc:213-226
need_mirror; docs/how_to env var MXNET_BACKWARD_DO_MIRROR).

Measures compiled peak memory of a ResNet train step at several remat
settings via XLA's memory analysis — the bs-vs-speed trade the reference's
memonger documents.

  python memonger.py --depth 50 --batch 64
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def peak_bytes(step, shapes):
    import jax
    import jax.numpy as jnp
    state = step.init(*shapes)
    data = {"data": jnp.zeros(shapes[0]["data"], jnp.float32),
            "softmax_label": jnp.zeros(shapes[1]["softmax_label"],
                                       jnp.float32)}
    bs = shapes[0]["data"][0]
    key = jax.random.key(0)
    lr = jnp.asarray(0.1, jnp.float32)
    state, _ = step.step(state, data)     # builds + caches the jit
    state = step.init(*shapes)            # donated buffers: fresh state
    compiled = step._jit[bs].lower(state, data, key, lr).compile()
    try:
        mem = compiled.memory_analysis()
        return int(mem.temp_size_in_bytes + mem.output_size_in_bytes
                   + mem.argument_size_in_bytes)
    except Exception:
        return -1


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, default=18)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--image", type=int, default=64)
    args = ap.parse_args()

    import jax
    from mxnet_tpu import models
    from mxnet_tpu.train_step import TrainStep

    shapes = ({"data": (args.batch, 3, args.image, args.image)},
              {"softmax_label": (args.batch,)})
    results = {}
    for mode, remat in (("none", False), ("conv-outputs", "conv"),
                        ("full", True)):
        sym = models.resnet(num_classes=100, num_layers=args.depth,
                            image_shape="3,%d,%d" % (args.image, args.image))
        step = TrainStep(sym, optimizer="sgd", learning_rate=0.1,
                         remat=remat)
        results[mode] = peak_bytes(step, shapes)
        print("remat=%-12s peak %s MB"
              % (mode, "n/a" if results[mode] < 0
                 else "%.1f" % (results[mode] / 1e6)))
    if all(v > 0 for v in results.values()):
        # measured v5e, resnet-50 b32 @224: none 3114 MB, conv-outputs
        # 2439 MB (-22%), full 3183 MB — a single whole-forward checkpoint
        # HURTS peak (the recompute backward holds a larger live set), so
        # the designed knob is the conv-outputs policy
        assert results["conv-outputs"] <= results["none"] * 1.01, \
            "remat=conv should not exceed baseline peak"
        print("remat=conv saves %.1f%% peak memory"
              % (100 * (1 - results["conv-outputs"] / results["none"])))
    print("OK  (speed trade measured on-chip in docs/perf.md: remat=conv "
          "-17%% img/s on v5e — spend it only when memory-bound)")


if __name__ == "__main__":
    main()
