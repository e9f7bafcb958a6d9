"""Worker program for the multi-process dist_sync tests.

Spawned by tests/test_dist_sync.py through tools/launch.py (the reference's
local tracker path, ref: tools/launch.py:46-78 + tests/nightly/
dist_sync_kvstore.py:30-45 + dist_lenet.py). Runs on the CPU backend with
one device per process; gradient aggregation crosses processes via Gloo.

Modes:
  kvstore — closed-form BSP push/pull assertions (every worker pushes a
            known value; the aggregate is exactly computable)
  lenet   — Module.fit with kvstore='dist_sync' on rank-partitioned
            synthetic data; asserts accuracy, the in-step-psum fused path,
            and cross-worker parameter consistency
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
os.environ.pop("XLA_FLAGS", None)

import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np


def main():
    mode = sys.argv[1]
    import mxnet_tpu as mx
    assert mx.tools_init_distributed(), "MXTPU_* env missing"
    rank = jax.process_index()
    nproc = jax.process_count()
    assert nproc >= 2, "dist test needs >= 2 processes"

    if mode == "kvstore":
        run_kvstore(mx, rank, nproc)
    elif mode == "lenet":
        run_lenet(mx, rank, nproc)
    elif mode == "deadworker":
        run_deadworker(mx, rank, nproc)
        # skip atexit/jax.distributed shutdown: the dead peer would make
        # the orderly shutdown barrier hang (ref: barrier_before_exit,
        # kvstore_dist.h:50-57)
        print("RANK-%d-PASS" % rank, flush=True)
        os._exit(0)
    elif mode == "resume":
        run_resume(mx, rank, nproc)
    elif mode == "elastic":
        run_elastic(mx, rank, nproc)
        # same as deadworker: one peer is gone, the orderly shutdown
        # barrier would hang
        print("RANK-%d-PASS" % rank, flush=True)
        os._exit(0)
    else:
        raise SystemExit("unknown mode %r" % mode)
    print("RANK-%d-PASS" % rank, flush=True)


def _survivor_sync(rank, nproc, victim, tag):
    """Completion sync over the raw coordination KV for tests that lose a
    worker: rank 0 hosts the coordination service, so it must exit LAST —
    otherwise a survivor still polling the plane aborts on
    connection-reset before its PASS line (jax's distributed client
    treats coordination-service loss as fatal). The ring barrier is no
    use here: it would wait on the dead victim."""
    import time

    from jax._src.distributed import global_state
    c = global_state.client
    try:
        # "ok", not "1": sub-2-byte values segfault jaxlib's dir-get
        c.key_value_set("%s_done/%d" % (tag, rank), "ok",
                        allow_overwrite=True)
    except Exception:
        return
    if rank != 0:
        return
    want = ["%s_done/%d" % (tag, r) for r in range(nproc) if r != victim]
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            got = c.key_value_dir_get("%s_done/" % tag)
        except Exception:
            return
        items = dict(got.items() if hasattr(got, "items") else got)
        if all(k in items for k in want):
            return
        time.sleep(0.2)


def run_kvstore(mx, rank, nproc):
    """Closed-form BSP semantics (ref: dist_sync_kvstore.py:30-45)."""
    from mxnet_tpu import nd
    kv = mx.kv.create("dist_sync")
    assert kv.rank == rank and kv.num_workers == nproc
    shape = (3, 4)

    # no-updater push: store <- sum over workers of (rank+1)
    kv.init(3, nd.ones(shape))
    kv.push(3, nd.ones(shape) * (rank + 1))
    out = nd.zeros(shape)
    kv.pull(3, out=out)
    expect = sum(r + 1 for r in range(nproc))
    np.testing.assert_allclose(out.asnumpy(), expect * np.ones(shape))

    # updater path: store += aggregated push, repeated (the reference's
    # accumulation check)
    kv2 = mx.kv.create("dist_sync")
    kv2._set_updater(lambda key, recv, stored: stored.__iadd__(recv))
    kv2.init("acc", nd.zeros(shape))
    nrepeat = 3
    for i in range(nrepeat):
        kv2.push("acc", nd.ones(shape) * (rank + 1))
    o = nd.zeros(shape)
    kv2.pull("acc", out=o)
    np.testing.assert_allclose(o.asnumpy(),
                               nrepeat * expect * np.ones(shape))

    # multi-device local list push combines with cross-worker reduce
    kv3 = mx.kv.create("dist_sync")
    kv3.init(9, nd.zeros(shape))
    kv3.push(9, [nd.ones(shape) * (rank + 1), nd.ones(shape) * (rank + 1)])
    o3 = nd.zeros(shape)
    kv3.pull(9, out=o3)
    np.testing.assert_allclose(o3.asnumpy(), 2 * expect * np.ones(shape))

    # workers whose host values diverged (per-rank seeding) must still
    # start from ONE authoritative copy: init broadcasts rank 0's value
    kv4 = mx.kv.create("dist_sync")
    kv4.init("b", nd.ones(shape) * (rank + 1) * 10)
    o4 = nd.zeros(shape)
    kv4.pull("b", out=o4)
    np.testing.assert_allclose(o4.asnumpy(), 10 * np.ones(shape))

    # liveness: every peer is beating over the coordination service, so
    # no node is dead (ref contract: kvstore_dist.h:159-168 GetDeadNodes)
    kv.barrier()                 # all ranks published their first beat
    assert kv.num_dead_node(0, timeout_sec=60) == 0, \
        "healthy cluster reported dead nodes"
    # a rank that never existed counts dead against a tight horizon —
    # with no startup grace: the phantom never published a beat, so the
    # grace window is the only thing that could excuse it
    hb = kv._heartbeat
    assert hb is not None
    grace = hb.startup_grace
    hb.startup_grace = 0.0
    try:
        assert hb.dead_nodes(nproc + 1, timeout_sec=60) >= 1
    finally:
        hb.startup_grace = grace

    kv.barrier()


def run_deadworker(mx, rank, nproc):
    """Fault injection: the highest rank SIGKILLs itself; survivors must
    see num_dead_node > 0 within the heartbeat timeout (the scenario
    kvstore_dist.h:159-168's GetDeadNodes exists for). Rank 0 hosts the
    coordination service, so the victim is the LAST rank."""
    import signal
    import time

    kv = mx.kv.create("dist_sync")
    assert kv.num_workers == nproc
    kv.barrier()                     # every rank has published its beat
    assert kv.num_dead_node(0, timeout_sec=60) == 0, \
        "cluster reported dead nodes before the kill"

    victim = nproc - 1
    if rank == victim:
        os.kill(os.getpid(), signal.SIGKILL)     # no goodbye, no cleanup
        raise AssertionError("unreachable")

    # survivors: poll until the victim's heartbeat goes stale. Beat
    # interval is 2s; a 4s staleness horizon flags it on the first or
    # second missed beat. NO barriers from here on (the peer is gone).
    deadline = time.time() + 90
    dead = 0
    while time.time() < deadline:
        dead = kv.num_dead_node(0, timeout_sec=4)
        if dead >= 1:
            break
        time.sleep(1)
    assert dead >= 1, "rank %d never detected the killed worker" % rank
    _survivor_sync(rank, nproc, victim, "deadworker")


def run_resume(mx, rank, nproc):
    """Checkpoint mid-training, resume in a FRESH module, finish training
    (ref: Module.save_checkpoint/load + --load-epoch resume,
    example/image-classification/common/fit.py)."""
    from mxnet_tpu.io import NDArrayIter

    n_class, dim, n_per = 8, 32, 256
    rng = np.random.RandomState(7)
    templates = rng.randn(n_class, dim).astype(np.float32) * 3
    labels_all = np.arange(n_class * n_per) % n_class
    x_all = (templates[labels_all]
             + rng.randn(len(labels_all), dim).astype(np.float32) * 0.5)
    x, y = x_all[rank::nproc], labels_all[rank::nproc].astype(np.float32)

    def net():
        data = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(data, name="fc1", num_hidden=64)
        h = mx.sym.Activation(h, name="relu1", act_type="relu")
        h = mx.sym.FullyConnected(h, name="fc2", num_hidden=n_class)
        return mx.sym.SoftmaxOutput(h, name="softmax")

    prefix = os.path.join(os.environ.get("MXTPU_TEST_TMPDIR", "/tmp"),
                          "dist_resume")
    mid_epoch = 3

    mod = mx.mod.Module(net())
    train = NDArrayIter(x, y, batch_size=64, shuffle=False)
    mod.fit(train, num_epoch=mid_epoch, kvstore="dist_sync",
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.initializer.Xavier())
    # replicas are consistent, so every rank saves an identical checkpoint;
    # rank 0's copy is authoritative (ref: per-rank prefixes, fit.py:25-44)
    if rank == 0:
        mod.save_checkpoint(prefix, mid_epoch, save_optimizer_states=True)
    kv0 = mx.kv.create("dist_sync")
    kv0.barrier()                   # checkpoint visible before anyone loads

    # resume in a FRESH module from the saved state (mid-training restart)
    mod2 = mx.mod.Module.load(prefix, mid_epoch,
                              load_optimizer_states=True)
    train.reset()
    mod2.fit(train, num_epoch=8, begin_epoch=mid_epoch,
             kvstore="dist_sync", optimizer="sgd",
             optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    score = mod2.score(NDArrayIter(x, y, batch_size=64), "acc")
    acc = dict(score)["accuracy"]
    assert acc >= 0.95, "rank %d resumed accuracy %.3f < 0.95" % (rank, acc)

    # resumed replicas must agree across workers
    arg_params, _ = mod2.get_params()
    blob = np.concatenate([arg_params[k].asnumpy().ravel()
                           for k in sorted(arg_params)])
    kv = mx.kv.create("dist_sync")
    tot = mx.nd.zeros(blob.shape)
    kv.init("resumecheck", tot)
    kv.push("resumecheck", mx.nd.array(blob))
    kv.pull("resumecheck", out=tot)
    np.testing.assert_allclose(tot.asnumpy(), nproc * blob, rtol=1e-6,
                               err_msg="resumed replicas diverged")


def run_elastic(mx, rank, nproc):
    """Worker-loss survival end to end (docs/robustness.md "Elastic
    distributed training"): the highest rank SIGKILLs itself mid-epoch
    via the kv.worker_die fault site; survivors must take an emergency
    checkpoint, re-form the ring at N-1, re-shard the data, finish
    training to accuracy — and a fresh resume from the same prefix must
    be bitwise-identical to the live post-reform parameters."""
    import glob

    from mxnet_tpu import faults
    from mxnet_tpu.io import NDArrayIter

    n_class, dim, n_per = 8, 32, 192
    num_epoch, batch_size = 8, 64
    rng = np.random.RandomState(7)  # same on all ranks
    templates = rng.randn(n_class, dim).astype(np.float32) * 3
    labels_all = np.arange(n_class * n_per) % n_class
    x_all = (templates[labels_all]
             + rng.randn(len(labels_all), dim).astype(np.float32) * 0.5)

    class ElasticIter(NDArrayIter):
        """fit's re-shard hook: re-cut this worker's shard from the FULL
        dataset at the post-reform (index, size)."""

        def reshard_workers(self, part_index, num_parts):
            ElasticIter.__init__(
                self, x_all[part_index::num_parts],
                labels_all[part_index::num_parts].astype(np.float32),
                batch_size=batch_size, shuffle=False)

    def net():
        data = mx.sym.Variable("data")
        h = mx.sym.FullyConnected(data, name="fc1", num_hidden=64)
        h = mx.sym.Activation(h, name="relu1", act_type="relu")
        h = mx.sym.FullyConnected(h, name="fc2", num_hidden=n_class)
        return mx.sym.SoftmaxOutput(h, name="softmax")

    # per-rank prefix dirs: the leader's checkpoint blob is imported
    # under the LEADER's file names, which must not collide with this
    # rank's own pre-reform saves
    prefix = os.path.join(os.environ.get("MXTPU_TEST_TMPDIR", "/tmp"),
                          "r%d" % rank, "elastic")
    opt_params = {"learning_rate": 0.1, "momentum": 0.9}

    # rank 0 hosts the coordination service, so the victim is the LAST
    # rank. Ring op #30 = 4 init broadcasts + 26 train-step allreduces =
    # mid-epoch 3 (8 steps/epoch on a 512-sample shard) — the kill lands
    # between checkpointable batch boundaries
    victim = nproc - 1
    if rank == victim:
        faults.inject("kv.worker_die", nth=30, kind="die")

    mod = mx.mod.Module(net())
    train = ElasticIter(x_all[rank::nproc],
                        labels_all[rank::nproc].astype(np.float32),
                        batch_size=batch_size, shuffle=False)
    mod.fit(train, num_epoch=num_epoch, kvstore="dist_sync",
            optimizer="sgd", optimizer_params=opt_params,
            initializer=mx.initializer.Xavier(),
            checkpoint_prefix=prefix, checkpoint_keep=50)
    assert rank != victim, "victim outlived its SIGKILL"

    # survivors: exactly one re-form, membership shrank to N-1
    kv = mod._kvstore
    assert kv is not None and kv.reforms == 1, \
        "rank %d: expected 1 ring re-form, saw %r" % (rank, kv.reforms)
    assert kv.num_workers == nproc - 1, \
        "rank %d: ring did not shrink to %d" % (rank, nproc - 1)

    # the mid-kill emergency checkpoint is durably on disk (b > 0: only
    # the emergency path saves mid-epoch in this run)
    mids = [f for f in glob.glob(prefix + "-e*-b*.params")
            if not f.endswith("-b00000000.params")]
    assert mids, "rank %d: no mid-epoch emergency checkpoint" % rank

    # training finished to accuracy despite losing a worker mid-run
    score = mod.score(NDArrayIter(x_all, labels_all.astype(np.float32),
                                  batch_size=batch_size), "acc")
    acc = dict(score)["accuracy"]
    assert acc >= 0.90, "rank %d accuracy %.3f < 0.90" % (rank, acc)

    # survivors' replicas agree bitwise-identically: the fresh store sees
    # the RE-FORMED shared ring, so the sum spans nproc-1 members
    arg_live, _ = mod.get_params()
    blob = np.concatenate([arg_live[k].asnumpy().ravel()
                           for k in sorted(arg_live)])
    kvc = mx.kv.create("dist_sync")
    assert kvc.num_workers == nproc - 1
    tot = mx.nd.zeros(blob.shape)
    kvc.init("elasticcheck", tot)
    kvc.push("elasticcheck", mx.nd.array(blob))
    kvc.pull("elasticcheck", out=tot)
    np.testing.assert_allclose(tot.asnumpy(), (nproc - 1) * blob,
                               rtol=1e-6,
                               err_msg="survivor replicas diverged")

    # a FRESH module resuming from the prefix reproduces the live
    # post-reform state bitwise (resume='auto' lands on the final
    # epoch-end tag, so the epoch loop is already complete)
    mod2 = mx.mod.Module(net())
    train.reset()
    mod2.fit(train, num_epoch=num_epoch, kvstore="dist_sync",
             optimizer="sgd", optimizer_params=opt_params,
             initializer=mx.initializer.Xavier(),
             checkpoint_prefix=prefix, resume="auto")
    arg_res, _ = mod2.get_params()
    for name in sorted(arg_live):
        assert (arg_res[name].asnumpy().tobytes()
                == arg_live[name].asnumpy().tobytes()), \
            "rank %d: resumed %r differs from live state" % (rank, name)

    # machine-readable line for tools/dist_gate.py: the post-reform
    # membership
    print("RANK-%d-ELASTIC-STATS reforms=%d workers=%d"
          % (rank, kv.reforms, kv.num_workers), flush=True)
    _survivor_sync(rank, nproc, victim, "elastic")


def run_lenet(mx, rank, nproc):
    """Distributed training to accuracy (ref: dist_lenet.py / test_mlp)."""
    from mxnet_tpu.io import NDArrayIter

    # rank-partitioned separable data: class templates + noise
    n_class, dim, n_per = 8, 32, 256
    rng = np.random.RandomState(7)  # same on all ranks
    templates = rng.randn(n_class, dim).astype(np.float32) * 3
    labels_all = np.arange(n_class * n_per) % n_class
    x_all = (templates[labels_all]
             + rng.randn(len(labels_all), dim).astype(np.float32) * 0.5)
    # each worker sees ONLY its shard (ref: part_index/num_parts)
    x, y = x_all[rank::nproc], labels_all[rank::nproc].astype(np.float32)

    data = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(data, name="fc1", num_hidden=64)
    h = mx.sym.Activation(h, name="relu1", act_type="relu")
    # dropout exercises RNG threading through the multi-host fused step
    h = mx.sym.Dropout(h, name="drop1", p=0.2)
    h = mx.sym.FullyConnected(h, name="fc2", num_hidden=n_class)
    out = mx.sym.SoftmaxOutput(h, name="softmax")

    mod = mx.mod.Module(out)
    train = NDArrayIter(x, y, batch_size=64, shuffle=False)
    mod.fit(train, num_epoch=8, kvstore="dist_sync",
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.initializer.Xavier())

    # the dist bail-out is gone: fit must have used the fused path with
    # the cross-worker gradient reduction wired into every dispatch
    assert mod._fused is not None, "dist fit fell back to the slow path"
    assert mod._fused.dist_reduce is not None, \
        "fused step not wired to the cross-worker reduction"

    score = mod.score(NDArrayIter(x, y, batch_size=64), "acc")
    acc = dict(score)["accuracy"]
    assert acc >= 0.95, "rank %d accuracy %.3f < 0.95" % (rank, acc)

    # replicas must not diverge: params bitwise identical across workers
    arg_params, _ = mod.get_params()
    blob = np.concatenate([arg_params[k].asnumpy().ravel()
                           for k in sorted(arg_params)])
    kv = mx.kv.create("dist_sync")  # fresh store: no updater installed
    mine = mx.nd.array(blob)
    tot = mx.nd.zeros(blob.shape)
    kv.init("paramcheck", tot)
    kv.push("paramcheck", mine)
    kv.pull("paramcheck", out=tot)
    np.testing.assert_allclose(tot.asnumpy(), nproc * blob, rtol=1e-6,
                               err_msg="worker replicas diverged")


if __name__ == "__main__":
    main()
