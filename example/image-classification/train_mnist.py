#!/usr/bin/env python
"""Train LeNet/MLP on MNIST (ref config 1:
example/image-classification/train_mnist.py).

Downloads nothing: pass --data-dir with MNIST idx files
(train-images-idx3-ubyte[.gz] etc.), or use --synthetic for a smoke run.
"""
import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import models


def get_iters(args):
    if args.synthetic:
        rng = np.random.default_rng(0)
        shape = (600, 784) if args.network == "mlp" else (600, 1, 28, 28)
        templates = rng.normal(size=(10,) + shape[1:]).astype(np.float32)
        ys = rng.integers(0, 10, shape[0])
        X = templates[ys] + 0.2 * rng.normal(size=shape).astype(np.float32)
        y = ys.astype(np.float32)
        split = int(0.9 * shape[0])
        train = mx.io.NDArrayIter(X[:split], y[:split], args.batch_size,
                                  shuffle=True)
        val = mx.io.NDArrayIter(X[split:], y[split:], args.batch_size)
        return train, val
    flat = args.network == "mlp"

    def p(name):
        for cand in (name, name + ".gz"):
            full = os.path.join(args.data_dir, cand)
            if os.path.exists(full):
                return full
        raise FileNotFoundError(name)

    train = mx.io.MNISTIter(image=p("train-images-idx3-ubyte"),
                            label=p("train-labels-idx1-ubyte"),
                            batch_size=args.batch_size, flat=flat,
                            shuffle=True)
    val = mx.io.MNISTIter(image=p("t10k-images-idx3-ubyte"),
                          label=p("t10k-labels-idx1-ubyte"),
                          batch_size=args.batch_size, flat=flat,
                          shuffle=False)
    return train, val


def main():
    parser = argparse.ArgumentParser(description="train mnist")
    parser.add_argument("--network", default="lenet",
                        choices=["mlp", "lenet"])
    parser.add_argument("--data-dir", default="mnist/")
    parser.add_argument("--gpus", default=None,
                        help="device ids, e.g. '0' or '0,1' (TPU chips)")
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--lr", type=float, default=0.05)
    parser.add_argument("--num-epochs", type=int, default=10)
    parser.add_argument("--kv-store", default="local")
    parser.add_argument("--model-prefix", default=None)
    parser.add_argument("--synthetic", action="store_true")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    mx.engine.setup_compile_cache()

    net = models.get_symbol(args.network, num_classes=10)
    devs = (mx.current_context() if args.gpus is None
            else [mx.gpu(int(i)) for i in args.gpus.split(",")])
    train, val = get_iters(args)
    mod = mx.mod.Module(net, context=devs)
    cb = []
    if args.model_prefix:
        cb.append(mx.callback.do_checkpoint(args.model_prefix))
    mod.fit(train, eval_data=val, num_epoch=args.num_epochs,
            eval_metric="acc", initializer=mx.initializer.Xavier(),
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9},
            kvstore=args.kv_store,
            batch_end_callback=mx.callback.Speedometer(args.batch_size, 100),
            epoch_end_callback=cb)


if __name__ == "__main__":
    main()
