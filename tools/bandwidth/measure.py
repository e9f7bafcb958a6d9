#!/usr/bin/env python
"""Measure device-collective bandwidth (ref: tools/bandwidth/measure.py,
which timed kvstore push/pull over PCIe/network).

TPU-native: times the psum allreduce over the device mesh (ICI) — the
operation that replaced kvstore gradient sync — plus host<->device transfer.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--size-mb", type=float, default=64,
                        help="payload per device, MB")
    parser.add_argument("--iters", type=int, default=10)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()
    n = len(devs)
    elems = int(args.size_mb * 1e6 / 4)
    x = jnp.ones((n, elems), jnp.float32)

    # host -> device
    xh = np.ones((elems,), np.float32)
    t0 = time.perf_counter()
    for _ in range(args.iters):
        jax.block_until_ready(jax.device_put(xh, devs[0]))
    h2d = args.size_mb * args.iters / (time.perf_counter() - t0)
    print("host->device: %.2f MB/s" % h2d)

    if n > 1:
        mesh = Mesh(np.array(devs), ("data",))
        f = jax.shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                      in_specs=P("data"), out_specs=P())

        xs = jax.device_put(x, jax.sharding.NamedSharding(mesh, P("data")))
        jax.block_until_ready(f(xs))  # compile
        t0 = time.perf_counter()
        for _ in range(args.iters):
            jax.block_until_ready(f(xs))
        dt = time.perf_counter() - t0
        # ring allreduce moves 2*(n-1)/n of the payload per device
        algbw = args.size_mb * args.iters / dt
        busbw = algbw * 2 * (n - 1) / n
        print("allreduce (psum) over %d devices: algbw %.2f MB/s, "
              "busbw %.2f MB/s" % (n, algbw, busbw))
    else:
        print("single device: no collective to measure")


if __name__ == "__main__":
    main()
