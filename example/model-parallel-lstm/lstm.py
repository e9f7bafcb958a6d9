#!/usr/bin/env python
"""Model-parallel stacked-LSTM language model (ref config 5:
example/model-parallel-lstm/lstm.py — each LSTM layer placed on its own GPU
via AttrScope(ctx_group=...) + bind(group2ctx=...)).

TPU-native lowering: the same ctx_group annotations map to shardings over the
'model' axis of a device mesh (see mxnet_tpu/parallel/placement.py) — each
layer's weights distribute across the mesh and XLA inserts the boundary
collectives that the reference inserted as _CrossDeviceCopy nodes. Numerics
are identical to the single-device run; the memory-capacity win (the reason
the reference pipelined layers across GPUs) is preserved.

Run on the 8-device virtual CPU mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python example/model-parallel-lstm/lstm.py --check
"""
import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def build_symbol(seq_len, num_layers, num_hidden, num_embed, vocab_size,
                 batch_size):
    """Stacked LSTM LM; layer k annotated ctx_group='layer%d', embedding in
    'embed', decoder in 'decode' — the reference's group assignment
    (ref: example/model-parallel-lstm/lstm.py:48-112). Initial states are
    data inputs fed zeros, like the reference's init_states.

    Returns (symbol, state_names)."""
    import mxnet_tpu as mx
    from mxnet_tpu import sym
    from mxnet_tpu.rnn import LSTMCell

    data = sym.Variable("data")
    label = sym.Variable("softmax_label")
    with mx.AttrScope(ctx_group="embed"):
        embed = sym.Embedding(data, name="embed", input_dim=vocab_size,
                              output_dim=num_embed)
    outputs = embed
    state_names = []
    for k in range(num_layers):
        with mx.AttrScope(ctx_group="layer%d" % k):
            cell = LSTMCell(num_hidden, prefix="lstm%d_" % k)
            begin = cell.begin_state(shape=(batch_size, num_hidden))
            state_names += [s.name for s in begin]
            outs, _ = cell.unroll(seq_len, inputs=outputs, begin_state=begin,
                                  layout="NTC", merge_outputs=True)
        outputs = outs
    with mx.AttrScope(ctx_group="decode"):
        flat = sym.Reshape(outputs, shape=(-1, num_hidden))
        pred = sym.FullyConnected(flat, name="pred", num_hidden=vocab_size)
        lab = sym.Reshape(label, shape=(-1,))
        out = sym.SoftmaxOutput(pred, lab, name="softmax")
    return out, state_names


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq-len", type=int, default=16)
    parser.add_argument("--num-layers", type=int, default=4)
    parser.add_argument("--num-hidden", type=int, default=128)
    parser.add_argument("--num-embed", type=int, default=64)
    parser.add_argument("--vocab", type=int, default=64)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument("--lr", type=float, default=0.01)
    parser.add_argument("--check", action="store_true",
                        help="assert loss falls and numerics match the "
                             "single-device run")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    import jax
    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.train_step import TrainStep

    symbol, state_names = build_symbol(args.seq_len, args.num_layers,
                                       args.num_hidden, args.num_embed,
                                       args.vocab, args.batch_size)

    # every group spreads over the full 'model' axis; the reference spread
    # layers over distinct GPUs, which on an SPMD mesh is the degenerate
    # special case of sharding each group across the axis
    ndev = len(jax.devices())
    mesh = make_mesh({"model": ndev})
    group2ctx = {"embed": "model", "decode": "model"}
    for k in range(args.num_layers):
        group2ctx["layer%d" % k] = "model"

    # synthetic next-token-predictable corpus (position-shifted cycle)
    rng = np.random.default_rng(0)
    starts = rng.integers(1, args.vocab - 1, size=(args.batch_size,))
    seq = (starts[:, None] + np.arange(args.seq_len + 1)) % (args.vocab - 1) + 1
    x = seq[:, :-1].astype(np.float32)
    y = seq[:, 1:].astype(np.float32)
    zero_states = {n: np.zeros((args.batch_size, args.num_hidden), np.float32)
                   for n in state_names}
    batch = {"data": x, "softmax_label": y}
    batch.update(zero_states)

    def run(g2c, m):
        step = TrainStep(symbol, data_names=["data"] + state_names,
                         optimizer="adam", learning_rate=args.lr,
                         mesh=m, group2ctx=g2c)
        shapes = {"data": (args.batch_size, args.seq_len)}
        shapes.update({n: (args.batch_size, args.num_hidden)
                       for n in state_names})
        state = step.init(
            shapes, {"softmax_label": (args.batch_size, args.seq_len)},
            seed=42)
        losses = []
        for i in range(args.steps):
            state, outs = step.step(state, batch)
            prob = np.asarray(outs[0]).reshape(-1, args.vocab)
            nll = -np.log(np.maximum(
                prob[np.arange(prob.shape[0]),
                     y.reshape(-1).astype(int)], 1e-8)).mean()
            losses.append(float(nll))
            if (i + 1) % 10 == 0 or i == 0:
                logging.info("step %d nll %.4f", i + 1, nll)
        return losses, state

    losses, state = run(group2ctx, mesh)
    print("model-parallel final nll: %.4f (start %.4f) on %d devices"
          % (losses[-1], losses[0], ndev))

    if args.check:
        w = state["params"]["lstm0_i2h_weight"]
        assert len(w.sharding.device_set) == ndev, \
            "layer weights not distributed: %s" % (w.sharding,)
        assert losses[-1] < losses[0] * 0.5, \
            "loss did not fall: %r" % (losses,)
        ref_losses, _ = run(None, None)
        # sharding preserves values up to reduction order; early steps match
        # tightly, later ones drift as training dynamics amplify the last-bit
        # differences (same behavior across any two XLA partitionings)
        np.testing.assert_allclose(losses[:10], ref_losses[:10],
                                   rtol=1e-4, atol=1e-4)
        print("check ok: loss falls, weights sharded over %d devices, "
              "numerics match single-device" % ndev)


if __name__ == "__main__":
    main()
