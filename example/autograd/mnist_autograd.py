#!/usr/bin/env python
"""Imperative training with the autograd API — no Symbol, no Module
(ref: the mx.contrib.autograd story, python/mxnet/contrib/autograd.py;
example/autograd in later reference versions).

An MLP classifier written as plain NDArray ops inside train_section();
gradients land in the marked grad buffers; SGD updates are imperative
in-place ops. Runs on synthetic separable data so it needs no download.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag
from mxnet_tpu import nd


def make_data(rng, n=512, feat=32, classes=4):
    temps = rng.standard_normal((classes, feat)).astype(np.float32) * 2
    X = np.concatenate([t + rng.standard_normal(
        (n // classes, feat)).astype(np.float32) for t in temps])
    Y = np.repeat(np.arange(classes), n // classes)
    perm = rng.permutation(len(X))
    return X[perm], Y[perm].astype(np.int64)


def main():
    rng = np.random.default_rng(0)
    X, Y = make_data(rng)
    feat, hidden, classes = X.shape[1], 64, 4

    params = {
        "w1": nd.array(rng.standard_normal((feat, hidden)).astype(
            np.float32) * 0.1),
        "b1": nd.zeros((hidden,)),
        "w2": nd.array(rng.standard_normal((hidden, classes)).astype(
            np.float32) * 0.1),
        "b2": nd.zeros((classes,)),
    }
    grads = {k: nd.zeros(v.shape) for k, v in params.items()}
    ag.mark_variables(list(params.values()), list(grads.values()))

    def net(x):
        h = nd.dot(x, params["w1"]) + params["b1"]
        h = nd.relu(h)
        return nd.dot(h, params["w2"]) + params["b2"]

    lr, batch = 0.1, 64
    for epoch in range(10):
        total_loss, correct = 0.0, 0
        for i in range(0, len(X), batch):
            xb = nd.array(X[i:i + batch])
            yb = Y[i:i + batch]
            onehot = np.eye(classes, dtype=np.float32)[yb]
            with ag.train_section():
                logits = net(xb)
                logp = nd.log_softmax(logits, axis=1)
                loss = -nd.sum(logp * nd.array(onehot)) / len(yb)
            ag.compute_gradient([loss])
            for k in params:
                params[k][:] = params[k].asnumpy() - lr * grads[k].asnumpy()
            total_loss += float(loss.asnumpy())
            correct += int((logits.asnumpy().argmax(1) == yb).sum())
        print("epoch %d loss %.4f acc %.3f"
              % (epoch, total_loss / (len(X) // batch), correct / len(X)))
    assert correct / len(X) > 0.95, "imperative training failed"
    print("OK")


if __name__ == "__main__":
    main()
