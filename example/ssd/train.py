#!/usr/bin/env python
"""SSD training + evaluation end to end (ref config 4:
example/ssd/train.py + evaluate.py).

With --synthetic (default, no dataset needed) trains on generated
colored-rectangle scenes: each image contains 1-3 axis-aligned colored
boxes whose class is their color; labels are (cls, x1, y1, x2, y2)
normalized, -1-padded — the same array-label layout ImageDetIter produces
from a det .rec (see --data-train). Reports the MultiBox train metrics and
a VOC-style mAP over the detection output.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.models import ssd as ssd_model


COLORS = np.array([[220, 40, 40], [40, 220, 40], [40, 40, 220],
                   [220, 220, 40]], np.float32)


def synth_det_batch(rng, n, size, num_classes, max_obj=3):
    """Images of colored rectangles + (cls,x1,y1,x2,y2) labels."""
    imgs = np.full((n, 3, size, size), 110, np.float32)
    imgs += rng.normal(0, 12, imgs.shape).astype(np.float32)
    labels = -np.ones((n, max_obj, 5), np.float32)
    for i in range(n):
        for o in range(rng.integers(1, max_obj + 1)):
            k = int(rng.integers(0, num_classes))
            w = rng.uniform(0.25, 0.55)
            h = rng.uniform(0.25, 0.55)
            x1 = rng.uniform(0, 1 - w)
            y1 = rng.uniform(0, 1 - h)
            px1, py1 = int(x1 * size), int(y1 * size)
            px2, py2 = int((x1 + w) * size), int((y1 + h) * size)
            imgs[i, :, py1:py2, px1:px2] = COLORS[k][:, None, None]
            labels[i, o] = [k, x1, y1, x1 + w, y1 + h]
    imgs = (imgs - 110.0) / 60.0
    return imgs, labels


class MultiBoxMetric(mx.metric.EvalMetric):
    """Cross-entropy + smooth-L1 training metrics
    (ref: example/ssd/train/metric.py MultiBoxMetric)."""

    def __init__(self):
        super().__init__("MultiBox")
        self.num = 2
        self.reset()

    def reset(self):
        self.num_inst = [0, 0]
        self.sum_metric = [0.0, 0.0]

    def update(self, labels, preds):
        cls_prob = preds[0].asnumpy()       # (n, C, A)
        loc_loss = preds[1].asnumpy()       # (n, A*4) smooth-l1 values
        cls_label = preds[2].asnumpy()      # (n, A)
        valid = cls_label >= 0
        lab = np.maximum(cls_label, 0).astype(int)
        n, C, A = cls_prob.shape
        p = cls_prob[np.arange(n)[:, None], lab, np.arange(A)[None, :]]
        ce = -np.log(np.maximum(p, 1e-10)) * valid
        self.sum_metric[0] += float(ce.sum())
        self.num_inst[0] += int(valid.sum())
        self.sum_metric[1] += float(np.abs(loc_loss).sum())
        self.num_inst[1] += int(valid.sum())

    def get(self):
        return (["CrossEntropy", "SmoothL1"],
                [self.sum_metric[i] / max(self.num_inst[i], 1)
                 for i in range(2)])


def voc_map(dets, gts, num_classes, iou_thresh=0.5):
    """Compact VOC-style AP: dets per image (k, 6) [cls, score, box];
    gts per image (o, 5). Returns mAP over classes present in gt."""
    aps = []
    for c in range(num_classes):
        records = []        # (score, tp)
        npos = 0
        for det, gt in zip(dets, gts):
            g = gt[(gt[:, 0] == c)][:, 1:5]
            npos += len(g)
            d = det[(det[:, 0] == c) & (det[:, 1] > 0.01)]
            used = np.zeros(len(g), bool)
            for row in d[np.argsort(-d[:, 1])]:
                if len(g) == 0:
                    records.append((row[1], 0))
                    continue
                x1 = np.maximum(g[:, 0], row[2]); y1 = np.maximum(g[:, 1], row[3])
                x2 = np.minimum(g[:, 2], row[4]); y2 = np.minimum(g[:, 3], row[5])
                iw = np.maximum(x2 - x1, 0); ih = np.maximum(y2 - y1, 0)
                inter = iw * ih
                ga = (g[:, 2] - g[:, 0]) * (g[:, 3] - g[:, 1])
                da = (row[4] - row[2]) * (row[5] - row[3])
                iou = inter / np.maximum(ga + da - inter, 1e-10)
                j = int(np.argmax(iou))
                if iou[j] >= iou_thresh and not used[j]:
                    used[j] = True
                    records.append((row[1], 1))
                else:
                    records.append((row[1], 0))
        if npos == 0:
            continue
        if not records:
            aps.append(0.0)
            continue
        records.sort(key=lambda r: -r[0])
        tp = np.cumsum([r[1] for r in records])
        fp = np.cumsum([1 - r[1] for r in records])
        rec = tp / npos
        prec = tp / np.maximum(tp + fp, 1e-10)
        ap = 0.0
        for t in np.linspace(0, 1, 11):
            pm = prec[rec >= t]
            ap += (pm.max() if len(pm) else 0.0) / 11
        aps.append(float(ap))
    return float(np.mean(aps)) if aps else 0.0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-train", default=None,
                    help="det .rec (ImageDetIter); default synthetic")
    ap.add_argument("--num-classes", type=int, default=4)
    ap.add_argument("--image-size", type=int, default=128)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--epochs", type=int, default=20)
    ap.add_argument("--epoch-size", type=int, default=8)
    ap.add_argument("--width", type=int, default=16)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adam",
                    help="adam converges in ~200 steps on the synthetic "
                         "task; sgd needs a long schedule")
    ap.add_argument("--min-map", type=float, default=None,
                    help="assert final mAP >= this")
    args = ap.parse_args()
    mx.engine.setup_compile_cache()

    rng = np.random.default_rng(0)
    if args.data_train:
        train = mx.image.ImageDetIter(
            batch_size=args.batch_size,
            data_shape=(3, args.image_size, args.image_size),
            path_imgrec=args.data_train, shuffle=True)
        val_imgs = val_labels = None
    else:
        n = args.batch_size * args.epoch_size
        imgs, labels = synth_det_batch(rng, n, args.image_size,
                                       args.num_classes)
        train = mx.io.NDArrayIter(imgs, labels,
                                  batch_size=args.batch_size, shuffle=True,
                                  label_name="label")
        val_imgs, val_labels = synth_det_batch(rng, args.batch_size * 2,
                                               args.image_size,
                                               args.num_classes)

    net = ssd_model.get_symbol_train(num_classes=args.num_classes,
                                     width=args.width)
    mod = mx.mod.Module(net, data_names=("data",), label_names=("label",))
    mod.fit(train, num_epoch=args.epochs,
            eval_metric=MultiBoxMetric(),
            initializer=mx.initializer.Xavier(),
            optimizer=args.optimizer,
            optimizer_params=({"learning_rate": args.lr, "rescale_grad": 1.0}
                              if args.optimizer == "adam" else
                              {"learning_rate": args.lr, "momentum": 0.9,
                               "wd": 5e-4}),
            batch_end_callback=mx.callback.Speedometer(args.batch_size, 10))

    if val_imgs is None:
        print("training done")
        return

    # evaluation: detection output of the train net (det_out, grad-free)
    mod_det = mx.mod.Module(net, data_names=("data",),
                            label_names=("label",))
    mod_det.bind(data_shapes=[("data", val_imgs.shape)],
                 label_shapes=[("label", val_labels.shape)],
                 for_training=False)
    mod_det.set_params(*mod.get_params())
    b = mx.io.DataBatch(data=[mx.nd.array(val_imgs)],
                        label=[mx.nd.array(val_labels)])
    mod_det.forward(b, is_train=False)
    det = mod_det.get_outputs()[3].asnumpy()    # (n, A, 6)
    dets = [d[d[:, 0] >= 0] for d in det]
    dets = [np.stack([d[:, 0], d[:, 1], d[:, 2], d[:, 3], d[:, 4],
                      d[:, 5]], axis=1) for d in dets]
    m = voc_map(dets, list(val_labels), args.num_classes)
    print("mAP@0.5 = %.3f" % m)
    if args.min_map is not None:
        assert m >= args.min_map, "mAP %.3f < %.3f" % (m, args.min_map)
    print("OK")


if __name__ == "__main__":
    main()
