#!/usr/bin/env python
"""Hard convergence gate: 12-class real-JPEG dataset through the FULL
native data plane (ref: tests/nightly/test_all.sh:44-67 check_val — the
reference gates multi-epoch conv-net training on real decoded images).

Generates a few thousand JPEG images (12 texture/color classes whose
signal survives random crops and mirrors — augmentation pressure is
real), packs them into RecordIO with the IRHeader format, trains ResNet-18
THROUGH ImageRecordIter (native fused JPEG decode + crop/mirror
augmenters, src/io/image_decode.cc) for multiple epochs under a
MultiFactor LR schedule, and gates held-out accuracy from a separate
val .rec. Unlike the synthetic on-device gate (convergence_gate.py),
every byte crosses the real pipeline: JPEG -> decode -> augment ->
normalize -> batch -> device.

  python tools/convergence_gate_realdata.py               # ~5 min cpu
  python tools/convergence_gate_realdata.py --epochs 8 --min-acc 0.9
"""
import argparse
import io as _io
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np


def make_jpeg_dataset(root, n_per_class, classes, size, rng, quality=90):
    """Class = base color + stripe orientation/frequency; instances vary in
    phase, brightness and noise, so crops/mirrors preserve the label but
    memorizing pixels does not work."""
    from PIL import Image
    from mxnet_tpu import recordio

    ang = rng.uniform(0, np.pi, classes)
    freq = rng.uniform(3, 9, classes)
    base = rng.uniform(0.25, 0.75, (classes, 3))
    xs = np.linspace(0, 1, size)
    gx, gy = np.meshgrid(xs, xs, indexing="ij")

    def render(k):
        phase = rng.uniform(0, 2 * np.pi)
        bright = rng.uniform(0.85, 1.15)
        wave = np.sin(2 * np.pi * freq[k]
                      * (gx * np.cos(ang[k]) + gy * np.sin(ang[k])) + phase)
        img = (base[k][:, None, None] + 0.22 * wave[None]) * bright
        img = img + rng.normal(0, 0.06, img.shape)
        arr = (np.clip(img, 0, 1) * 255).astype(np.uint8)
        return np.transpose(arr, (1, 2, 0))  # HWC for PIL

    def pack_split(fname, n_each):
        path = os.path.join(root, fname)
        idx_path = os.path.splitext(path)[0] + ".idx"
        rec = recordio.MXIndexedRecordIO(idx_path, path, "w")
        order = rng.permutation(classes * n_each)
        entries = [(i % classes) for i in range(classes * n_each)]
        for i, idx in enumerate(order):
            k = entries[idx]
            buf = _io.BytesIO()
            Image.fromarray(render(k)).save(buf, format="JPEG",
                                            quality=quality)
            header = recordio.IRHeader(flag=0, label=float(k), id=int(idx),
                                       id2=0)
            rec.write_idx(i, recordio.pack(header, buf.getvalue()))
        rec.close()
        return path

    train = pack_split("train.rec", n_per_class)
    val = pack_split("val.rec", max(n_per_class // 4, 8))
    return train, val


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--classes", type=int, default=12)
    ap.add_argument("--n-per-class", type=int, default=200)
    ap.add_argument("--size", type=int, default=48)
    ap.add_argument("--crop", type=int, default=40)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--lr", type=float, default=0.002)
    ap.add_argument("--min-acc", type=float, default=0.9)
    ap.add_argument("--workdir", default=None)
    args = ap.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu import models

    rng = np.random.default_rng(7)
    workdir = args.workdir or tempfile.mkdtemp(prefix="convgate_")
    t0 = time.perf_counter()
    train_rec, val_rec = make_jpeg_dataset(
        workdir, args.n_per_class, args.classes, args.size, rng)
    gen_s = time.perf_counter() - t0

    data_shape = (3, args.crop, args.crop)
    norm = dict(mean_r=128, mean_g=128, mean_b=128,
                std_r=64, std_g=64, std_b=64)
    train = mx.image.ImageRecordIter(
        path_imgrec=train_rec, data_shape=data_shape,
        batch_size=args.batch, shuffle=True, rand_crop=True,
        rand_mirror=True, **norm)
    val = mx.image.ImageRecordIter(
        path_imgrec=val_rec, data_shape=data_shape,
        batch_size=args.batch, **norm)

    sym = models.resnet(num_classes=args.classes, num_layers=18,
                        image_shape="3,%d,%d" % (args.crop, args.crop))
    # multi-epoch LR schedule: drop at 2/3 of training (ref:
    # train_imagenet's --lr-step-epochs over MultiFactorScheduler)
    steps_per_epoch = args.classes * args.n_per_class // args.batch
    sched = mx.lr_scheduler.MultiFactorScheduler(
        step=[steps_per_epoch * args.epochs * 2 // 3], factor=0.1)
    mod = mx.mod.Module(sym)
    t1 = time.perf_counter()
    mod.fit(train, num_epoch=args.epochs,
            optimizer="adam",
            optimizer_params={"learning_rate": args.lr,
                              "lr_scheduler": sched},
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in",
                                              magnitude=2),
            batch_end_callback=mx.callback.Speedometer(args.batch, 20))
    train_s = time.perf_counter() - t1
    acc = mod.score(val, mx.metric.Accuracy())[0][1]
    print(json.dumps({
        "metric": "resnet18_realjpeg%d_holdout_acc" % args.classes,
        "value": round(float(acc), 4),
        "epochs": args.epochs,
        "images": args.classes * args.n_per_class,
        "gen_seconds": round(gen_s, 1),
        "train_seconds": round(train_s, 1),
    }))
    assert acc >= args.min_acc, \
        "real-data convergence gate: %.3f < %.3f" % (acc, args.min_acc)
    print("REALDATA CONVERGENCE PASS")


if __name__ == "__main__":
    main()
