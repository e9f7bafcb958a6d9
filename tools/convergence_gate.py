#!/usr/bin/env python
"""On-chip ResNet convergence gate (ref: tests/nightly/test_all.sh:44-67
check_val — train jobs gated on validation accuracy; this is the
ResNet-scale step beyond the MNIST/LeNet unit gates).

Trains ResNet on a synthetic 10-class dataset that lives ON DEVICE (a
fixed pool of structured color/texture images), so the input pipeline
and the host->device link are out of the loop and the gate measures
the training machinery itself: fused step, BN statistics, optimizer, lr
schedule. Asserts held-out accuracy.

  python tools/convergence_gate.py            # resnet-18 @64px, ~3 min
  python tools/convergence_gate.py --depth 50 --steps 400
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np


def make_pool(rng, n, size, classes):
    """Structured, augment-robust class templates: per-class base color +
    per-class stripe frequency, plus instance noise."""
    ang = rng.uniform(0, np.pi, classes)
    freq = rng.uniform(2, 8, classes)
    base = rng.uniform(0.2, 0.8, (classes, 3))
    xs = np.linspace(0, 1, size)
    gx, gy = np.meshgrid(xs, xs, indexing="ij")
    imgs = np.empty((n, 3, size, size), np.float32)
    labels = np.empty((n,), np.float32)
    for i in range(n):
        k = i % classes
        wave = np.sin(2 * np.pi * freq[k]
                      * (gx * np.cos(ang[k]) + gy * np.sin(ang[k])))
        img = base[k][:, None, None] + 0.25 * wave[None]
        img = img + rng.normal(0, 0.15, img.shape)
        imgs[i] = img.astype(np.float32)
        labels[i] = k
    return imgs, labels


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, default=18)
    ap.add_argument("--size", type=int, default=64)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--pool", type=int, default=2048)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adam",
                    help="adam converges in <50 steps; sgd works with a "
                         "tuned lr schedule")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--min-acc", type=float, default=0.9)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from mxnet_tpu import models
    from mxnet_tpu.train_step import TrainStep

    rng = np.random.default_rng(0)
    imgs, labels = make_pool(rng, args.pool, args.size, args.classes)
    n_train = args.pool * 3 // 4
    # device-resident data pool: one upload, minibatches sliced on device
    d_imgs = jnp.asarray(imgs[:n_train])
    d_labels = jnp.asarray(labels[:n_train])
    v_imgs = jnp.asarray(imgs[n_train:])
    v_labels = labels[n_train:]

    from mxnet_tpu import optimizer as opt_mod, lr_scheduler
    sym = models.resnet(num_classes=args.classes, num_layers=args.depth,
                        image_shape="3,%d,%d" % (args.size, args.size))
    sched = lr_scheduler.MultiFactorScheduler(
        step=[args.steps * 2 // 3], factor=0.1)
    # rescale_grad must be set explicitly on instance optimizers:
    # TrainStep only defaults to 1/batch for string-named ones
    if args.optimizer == "adam":
        opt = opt_mod.create("adam", learning_rate=args.lr,
                             rescale_grad=1.0 / args.batch,
                             lr_scheduler=sched)
    else:
        opt = opt_mod.create("sgd", learning_rate=args.lr, momentum=0.9,
                             wd=1e-4, rescale_grad=1.0 / args.batch,
                             lr_scheduler=sched)
    step = TrainStep(sym, optimizer=opt,
                     compute_dtype=None if args.dtype == "float32"
                     else args.dtype)
    state = step.init({"data": (args.batch, 3, args.size, args.size)},
                      {"softmax_label": (args.batch,)})

    t0 = time.perf_counter()
    order = rng.permutation(n_train)
    for s in range(args.steps):
        idx = jnp.asarray(order[(np.arange(args.batch)
                                 + s * args.batch) % n_train])
        batch = {"data": d_imgs[idx], "softmax_label": d_labels[idx]}
        state, _ = step.step(state, batch)
    np.asarray(state["step"])
    train_s = time.perf_counter() - t0

    # held-out accuracy via an eval-mode forward (moving BN stats)
    from mxnet_tpu.executor import _build_graph_runner
    run, _nodes = _build_graph_runner(sym)

    @jax.jit
    def fwd(params, aux, data):
        vals = dict(params)
        vals["data"] = data
        vals["softmax_label"] = jnp.zeros((data.shape[0],), jnp.float32)
        outs, _ = run(vals, aux, None, False)
        return outs[0]

    correct = 0
    for i in range(0, len(v_labels) - args.batch + 1, args.batch):
        out = fwd(state["params"], state["aux"], v_imgs[i:i + args.batch])
        pred = np.asarray(out).argmax(axis=1)
        correct += int((pred == v_labels[i:i + args.batch]).sum())
    n_eval = (len(v_labels) // args.batch) * args.batch
    if n_eval == 0:
        raise SystemExit("holdout split (%d) smaller than --batch (%d); "
                         "raise --pool or lower --batch"
                         % (len(v_labels), args.batch))
    acc = correct / n_eval
    print(json.dumps({
        "metric": "resnet%d_synthetic10_holdout_acc" % args.depth,
        "value": round(acc, 4),
        "steps": args.steps,
        "train_seconds": round(train_s, 1),
        "images_per_sec": round(args.steps * args.batch / train_s, 1),
    }))
    assert acc >= args.min_acc, "convergence gate: %.3f < %.3f" % (
        acc, args.min_acc)
    print("CONVERGENCE PASS")


if __name__ == "__main__":
    main()
