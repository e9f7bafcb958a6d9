#!/usr/bin/env python
"""CustomOp user story: a softmax loss written in numpy, trained through
Module (ref: example/numpy-ops/numpy_softmax.py — the reference's
demonstration that users can write ops in python/numpy via CustomOp;
the C++ side calls back into python, here operator.py's pure_callback
bridge does the same).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np

import mxnet_tpu as mx
import mxnet_tpu.operator as mxop
from mxnet_tpu import sym


@mxop.register("numpy_softmax")
class NumpySoftmaxProp(mxop.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=False)

    def list_arguments(self):
        return ["data", "label"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        data_shape = in_shape[0]
        label_shape = (in_shape[0][0],)
        return [data_shape, label_shape], [data_shape], []

    def create_operator(self, ctx, shapes, dtypes):
        return NumpySoftmax()


class NumpySoftmax(mxop.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        y = np.exp(x - x.max(axis=1, keepdims=True))
        y /= y.sum(axis=1, keepdims=True)
        self.assign(out_data[0], req[0], y)

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        l = in_data[1].asnumpy().astype(np.int32)
        y = out_data[0].asnumpy().copy()
        y[np.arange(l.shape[0]), l] -= 1.0
        self.assign(in_grad[0], req[0], y / l.shape[0])


def main(num_epoch=10, batch=32):
    rng = np.random.RandomState(0)
    n_class, dim = 6, 20
    templates = rng.randn(n_class, dim).astype(np.float32) * 2
    labels = (np.arange(n_class * 64) % n_class)
    X = templates[labels] + rng.randn(len(labels), dim).astype(np.float32) * .4
    y = labels.astype(np.float32)

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    fc = sym.FullyConnected(data, num_hidden=n_class, name="fc")
    net = sym.Custom(data=fc, label=label, op_type="numpy_softmax",
                     name="softmax")

    mod = mx.mod.Module(net, label_names=("softmax_label",))
    it = mx.io.NDArrayIter(X, y, batch_size=batch, shuffle=True,
                           label_name="softmax_label")
    mod.fit(it, num_epoch=num_epoch, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.initializer.Xavier())
    acc = mod.score(mx.io.NDArrayIter(X, y, batch_size=batch,
                                      label_name="softmax_label"),
                    mx.metric.Accuracy())[0][1]
    print("numpy-softmax train accuracy: %.3f" % acc)
    return acc


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-epoch", type=int, default=10)
    args = ap.parse_args()
    acc = main(args.num_epoch)
    if acc < 0.95:
        raise SystemExit("FAIL: accuracy %.3f < 0.95" % acc)
    print("NUMPY-OPS PASS")
