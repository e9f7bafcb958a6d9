#!/usr/bin/env python
"""Train ResNet/Inception/etc. on ImageNet (ref config 2:
example/image-classification/train_imagenet.py).

Input: RecordIO shards (see tools/im2rec.py) via mxnet_tpu.image.ImageIter,
or --synthetic for throughput runs. Multi-chip: --gpus 0,1,...  maps to the
SPMD data-parallel mesh.
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


class SyntheticIter(mx.io.DataIter):
    def __init__(self, batch_size, image_shape, num_classes, epoch_size=50):
        super().__init__(batch_size)
        rng = np.random.default_rng(0)
        self._data = rng.normal(size=(batch_size,) + image_shape).astype(
            np.float32)
        self._label = rng.integers(0, num_classes, batch_size).astype(
            np.float32)
        self._i = 0
        self._n = epoch_size
        self.provide_data = [mx.io.DataDesc(
            "data", (batch_size,) + image_shape)]
        self.provide_label = [mx.io.DataDesc("softmax_label", (batch_size,))]

    def reset(self):
        self._i = 0

    def next(self):
        if self._i >= self._n:
            raise StopIteration
        self._i += 1
        return mx.io.DataBatch([mx.nd.array(self._data)],
                               [mx.nd.array(self._label)], pad=0)


def validate_recipe(args):
    """Compile-check the EXACT training computation of the README recipe —
    full ResNet at 3,224,224, SGD momentum + wd + MultiFactor schedule in
    the fused step — on the attached device, run one synthetic step, and
    report parameter count + compiled memory (ref role: the reference's
    recipe is validated by the nightly train jobs; with no dataset on the
    host this validates shapes and the compile instead — README.md §5)."""
    import jax
    from mxnet_tpu.train_step import TrainStep

    image_shape = tuple(int(x) for x in args.image_shape.split(","))
    net = models.get_symbol(args.network, num_classes=args.num_classes,
                            num_layers=args.num_layers,
                            image_shape=args.image_shape)
    steps = [int(e) * args.epoch_size
             for e in args.lr_step_epochs.split(",")]
    sched = mx.lr_scheduler.MultiFactorScheduler(step=steps, factor=0.1)
    opt = mx.optimizer.create("sgd", learning_rate=args.lr, momentum=0.9,
                              wd=args.wd, rescale_grad=1.0 / args.batch_size,
                              lr_scheduler=sched)
    step = TrainStep(net, optimizer=opt, compute_dtype="bfloat16")
    dshape = (args.batch_size,) + image_shape
    state = step.init({"data": dshape},
                      {"softmax_label": (args.batch_size,)})
    n_params = sum(int(np.prod(v.shape)) for v in state["params"].values())
    rng = np.random.default_rng(0)
    batch = {"data": np.asarray(rng.normal(size=dshape), np.float32),
             "softmax_label": np.asarray(
                 rng.integers(0, args.num_classes, args.batch_size),
                 np.float32)}
    state, _ = step.step(state, batch)   # compiles + executes one step
    np.asarray(state["step"])            # wait for the step to finish
    mem_mb = None
    try:
        import jax.numpy as jnp
        lowered = step._jit[args.batch_size].lower(
            state, {k: jnp.asarray(v) for k, v in batch.items()},
            jax.random.key(0), jnp.asarray(args.lr, jnp.float32))
        ma = lowered.compile().memory_analysis()
        mem_mb = round((ma.temp_size_in_bytes
                        + ma.argument_size_in_bytes) / 1e6, 1)
    except Exception:
        pass
    print("RECIPE VALID: %s-%d b%d %s on %s | %.1fM params | "
          "schedule drops at steps %s | peak-mem %s MB"
          % (args.network, args.num_layers, args.batch_size,
             args.image_shape, jax.devices()[0].device_kind,
             n_params / 1e6, steps, mem_mb))
    return 0


def main():
    parser = argparse.ArgumentParser(description="train imagenet")
    parser.add_argument("--network", default="resnet")
    parser.add_argument("--num-layers", type=int, default=50)
    parser.add_argument("--data-train", default=None, help="train .rec path")
    parser.add_argument("--data-val", default=None)
    parser.add_argument("--gpus", default=None)
    parser.add_argument("--batch-size", type=int, default=128)
    parser.add_argument("--image-shape", default="3,224,224")
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--lr-step-epochs", default="30,60,90")
    parser.add_argument("--num-epochs", type=int, default=90)
    parser.add_argument("--wd", type=float, default=1e-4)
    parser.add_argument("--kv-store", default="local")
    parser.add_argument("--model-prefix", default=None)
    parser.add_argument("--load-epoch", type=int, default=None)
    parser.add_argument("--synthetic", action="store_true")
    parser.add_argument("--epoch-size", type=int, default=50)
    parser.add_argument("--validate-recipe", action="store_true",
                        help="shape-validate + compile-check the full "
                             "90-epoch recipe on the attached device and "
                             "exit (no dataset needed)")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)
    mx.engine.setup_compile_cache()

    if args.validate_recipe:
        return validate_recipe(args)

    image_shape = tuple(int(x) for x in args.image_shape.split(","))
    net = models.get_symbol(args.network, num_classes=args.num_classes,
                            num_layers=args.num_layers,
                            image_shape=args.image_shape)
    devs = (mx.current_context() if args.gpus is None
            else [mx.gpu(int(i)) for i in args.gpus.split(",")])

    kvstore = mx.kv.create(args.kv_store)
    if args.synthetic or args.data_train is None:
        train = SyntheticIter(args.batch_size, image_shape, args.num_classes,
                              args.epoch_size)
        val = None
    else:
        # native fused decode/augment engine (src/io/image_decode.cc);
        # part_index/num_parts shard the input across dist_sync workers
        norm = dict(mean_r=123.68, mean_g=116.78, mean_b=103.94,
                    std_r=58.395, std_g=57.12, std_b=57.375)
        train = mx.image.ImageRecordIter(
            path_imgrec=args.data_train, data_shape=image_shape,
            batch_size=args.batch_size, shuffle=True, rand_crop=True,
            rand_mirror=True, resize=256,
            part_index=kvstore.rank, num_parts=kvstore.num_workers, **norm)
        # val sharded like train: each worker scores its slice
        val = None if args.data_val is None else mx.image.ImageRecordIter(
            path_imgrec=args.data_val, data_shape=image_shape,
            batch_size=args.batch_size, resize=256,
            part_index=kvstore.rank, num_parts=kvstore.num_workers, **norm)

    # epoch-boundary lr schedule (ref: fit.py _get_lr_scheduler)
    epoch_size = args.epoch_size
    steps = [int(e) * epoch_size for e in args.lr_step_epochs.split(",")]
    lr_sched = mx.lr_scheduler.MultiFactorScheduler(step=steps, factor=0.1)

    if args.load_epoch is not None and args.model_prefix:
        mod = mx.mod.Module.load(args.model_prefix, args.load_epoch,
                                 context=devs)
        begin_epoch = args.load_epoch
    else:
        mod = mx.mod.Module(net, context=devs)
        begin_epoch = 0

    cb = []
    if args.model_prefix:
        cb.append(mx.callback.do_checkpoint(args.model_prefix))
    mod.fit(train, eval_data=val, num_epoch=args.num_epochs,
            begin_epoch=begin_epoch,
            eval_metric=["acc", mx.metric.TopKAccuracy(top_k=5)],
            initializer=mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in", magnitude=2),
            optimizer="sgd",
            optimizer_params={"learning_rate": args.lr, "momentum": 0.9,
                              "wd": args.wd, "lr_scheduler": lr_sched},
            kvstore=kvstore,
            batch_end_callback=mx.callback.Speedometer(args.batch_size, 20),
            epoch_end_callback=cb)


if __name__ == "__main__":
    main()
