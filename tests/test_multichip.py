"""Data-parallel multi-chip training through the fused K-step scan
(docs/perf.md "Data-parallel scaling").

The suite runs on the conftest-provided 8-device virtual CPU mesh: a
Module over N contexts trains the SAME fused ``lax.scan`` dispatch sharded
over an N-way 'data' mesh — superbatches land per-chip sharded off the
producer thread, params/optimizer state replicate, the gradient psum rides
inside the donated body, and the guard + checkpoint/resume stack composes
unchanged.
"""
import os
import signal
import subprocess
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, sym, tracecheck
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel.mesh import (data_parallel_mesh, data_axis_size,
                                     superbatch_sharding)
from mxnet_tpu.train_step import TrainStep

P = jax.sharding.PartitionSpec


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _mlp():
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, num_hidden=16, name="fc1")
    net = sym.Activation(data=net, act_type="relu")
    net = sym.FullyConnected(data=net, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(data=net, name="softmax")


def _fit_data(n=128, batch=32):
    rng = np.random.default_rng(3)
    X = rng.normal(size=(n, 10)).astype(np.float32)
    w = rng.normal(size=(10, 4)).astype(np.float32)
    y = np.argmax(X @ w, axis=1).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=batch), X, y


def _fit(nctx, k=2, num_epoch=2, guard=None, seed=7, **kw):
    mx.random.seed(seed)
    it, X, y = _fit_data()
    ctx = [mx.cpu(i) for i in range(nctx)] if nctx > 1 else mx.cpu()
    mod = mx.mod.Module(_mlp(), context=ctx)
    mod.fit(it, num_epoch=num_epoch, steps_per_dispatch=k, guard=guard,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9}, **kw)
    return mod


def test_mesh_helpers():
    mesh = data_parallel_mesh(8)
    assert data_axis_size(mesh) == 8
    assert data_axis_size(None) == 1
    s = superbatch_sharding(mesh)
    assert s.spec == P(None, "data")
    assert superbatch_sharding(None) is None


def test_sharded_fused_fit_matches_single_device():
    """Same seed, same global batch: the 8-device sharded fused fit must
    match the single-device fused fit numerically — the psum'd gradient is
    the same sum the one-chip backward computes."""
    a = _fit(1).get_params()[0]
    b = _fit(8).get_params()[0]
    for n in a:
        np.testing.assert_allclose(a[n].asnumpy(), b[n].asnumpy(),
                                   rtol=1e-4, atol=1e-5, err_msg=n)


def test_sharded_fit_engages_mesh_and_superbatch_sharding():
    mod = _fit(8)
    assert mod._fused is not None and mod._fused.mesh is not None
    assert data_axis_size(mod._fused.mesh) == 8
    sh = mod._superbatch_sharding()
    assert sh is not None and sh.spec == P(None, "data")
    # single-device module: no sharding handed to the producer
    assert _fit(1)._superbatch_sharding() is None


def test_superbatch_iter_lands_sharded():
    """With ``sharding=``, the producer's H2D IS the scatter: every stacked
    array carries the (None, 'data') NamedSharding, so the dispatch-side
    device_put is a no-op (same committed array, no resharding copy)."""
    mesh = data_parallel_mesh(8)
    sh = superbatch_sharding(mesh)
    it, _, _ = _fit_data()
    sb_it = it.superbatch(2, sharding=sh)
    try:
        batch = next(iter(sb_it))
        for arr in batch.data + batch.label:
            assert arr.data.sharding == sh, arr.data.sharding
        ts = TrainStep(_mlp(), optimizer="sgd", mesh=mesh)
        placed = ts.shard_superbatch(
            {"data": batch.data[0], "softmax_label": batch.label[0]})
        # already-sharded input passes through without a new buffer
        assert placed["data"] is batch.data[0].data
    finally:
        sb_it.close()


def test_sharded_fit_no_retrace_across_dispatches():
    """Epochs of sharded dispatches reuse ONE compiled scan program: the
    producer-landed sharding matches what the jit cache keyed on, so no
    dispatch re-traces (docs/static_analysis.md)."""
    from mxnet_tpu.test_utils import assert_no_retrace
    with assert_no_retrace(msg="8-device sharded fit"):
        mod = _fit(8, num_epoch=3)
    assert mod._fused._jit_scan  # the scan path actually ran


def test_sharded_scan_donation_and_collectives_clean():
    """tracecheck over the SHARDED program set: donation must survive
    sharding (state buffers alias outputs shard-for-shard) and the
    compiled partitioned scan body may sync only by all-reduce — the
    grad/metric psum, nothing gather-shaped (collective-in-scan lint)."""
    mesh = data_parallel_mesh(8)
    ts = TrainStep(_mlp(), optimizer="sgd", learning_rate=0.1, momentum=0.9,
                   mesh=mesh)
    k, bs = 2, 32
    state = ts.init({"data": (bs, 10)}, {"softmax_label": (bs,)})
    rng = np.random.default_rng(0)
    sb = ts.shard_superbatch({
        "data": rng.normal(size=(k, bs, 10)).astype(np.float32),
        "softmax_label": rng.integers(0, 4, (k, bs)).astype(np.float32)})
    fn = ts._build_scan(bs, k)
    lrs = jnp.asarray(np.asarray([0.1] * k, np.float32))
    args = (state, sb, ts._dispatch_key(), lrs)
    findings = tracecheck.check_program(fn, args, donate_argnums=(0,),
                                        name="dp8/mlp-scan")
    findings += tracecheck.check_collectives(fn, args, name="dp8/mlp-scan")
    bad = tracecheck.unsuppressed(findings)
    assert not bad, [f.format() for f in bad]


def test_check_collectives_flags_batch_gather():
    """Regression for the in-scan metric gather: the fancy-index
    ``o[arange(bs), label]`` form loses the batch-dim alignment GSPMD
    needs and lowers to all-gathers INSIDE the scan body — exactly what
    ``check_collectives`` must flag (the shipped ``_metric_step_sums``
    uses take_along_axis and stays clean, previous test)."""
    mesh = data_parallel_mesh(8)
    sh = jax.sharding.NamedSharding(mesh, P(None, "data"))

    def scan_fancy(os_, lis):
        def body(c, xs):
            o, li = xs
            return c + jnp.sum(o[jnp.arange(o.shape[0]), li]), None
        out, _ = jax.lax.scan(body, jnp.float32(0), (os_, lis))
        return out

    rng = np.random.default_rng(0)
    os_ = jax.device_put(rng.normal(size=(2, 32, 4)).astype(np.float32), sh)
    lis = jax.device_put(rng.integers(0, 4, (2, 32)).astype(np.int32), sh)
    findings = tracecheck.check_collectives(jax.jit(scan_fancy), (os_, lis),
                                            name="fancy-gather")
    assert any(f.lint == "collective-in-scan" for f in findings), \
        "fancy-index batch gather must be flagged"


def test_guard_composes_on_mesh():
    """guard.grad_nan at 8 devices: the poisoned step is a GLOBAL no-op
    (every chip takes the same select), the skip rides the packed sentinel
    readback, and params stay finite."""
    mesh = data_parallel_mesh(8)
    ts = TrainStep(_mlp(), optimizer="sgd", learning_rate=0.1, momentum=0.9,
                   mesh=mesh)
    K, bs = 4, 16
    state = ts.init({"data": (bs, 10)}, {"softmax_label": (bs,)})
    rng = np.random.default_rng(0)
    sb = ts.shard_superbatch({
        "data": rng.normal(size=(K, bs, 10)).astype(np.float32),
        "softmax_label": rng.integers(0, 4, (K, bs)).astype(np.float32)})
    faults.inject("guard.grad_nan", nth=2)
    state, m = ts.run_steps(state, sb, guard=True)
    assert m.skipped == 1
    assert m.num_samples == (K - 1) * bs
    assert int(np.asarray(state["step"])) == K - 1
    for n in ts.param_names:
        assert np.isfinite(np.asarray(state["params"][n])).all(), n


def test_sharded_checkpoint_resume_bitwise(tmp_path):
    """The PR 2 stack at 8 devices: fit to an epoch-end checkpoint, resume
    in a FRESH module, finish — final params bitwise-equal to the
    uninterrupted 8-device run (replicated params are identical on every
    chip, so the host snapshot is exact)."""
    full = _fit(8, checkpoint_prefix=str(tmp_path / "a" / "ck"))
    _fit(8, num_epoch=1, checkpoint_prefix=str(tmp_path / "b" / "ck"))
    resumed = _fit(8, checkpoint_prefix=str(tmp_path / "b" / "ck"),
                   resume="auto")
    a, b = full.get_params()[0], resumed.get_params()[0]
    for n in a:
        np.testing.assert_array_equal(a[n].asnumpy(), b[n].asnumpy(),
                                      err_msg=n)


def test_shard_batch_rejects_indivisible_batch():
    mesh = data_parallel_mesh(8)
    ts = TrainStep(_mlp(), optimizer="sgd", mesh=mesh)
    with pytest.raises(MXNetError, match="does not divide"):
        ts.shard_batch({"data": np.zeros((6, 10), np.float32)})
    with pytest.raises(MXNetError, match="does not divide"):
        ts.shard_superbatch({"data": np.zeros((2, 6, 10), np.float32)})


def test_bulk_dispatch_precheck_rejects_indivisible_batch():
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    it = mx.io.NDArrayIter(np.zeros((36, 10), np.float32),
                           np.zeros((36,), np.float32), batch_size=36)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params()
    mod.init_optimizer()
    ok, why = mod._can_bulk_dispatch()
    assert not ok and "does not divide" in why


def test_dp_devices_env(monkeypatch):
    """MXTPU_DP_DEVICES=N spreads a context-less Module over N devices;
    an over-ask fails actionably naming the XLA_FLAGS knob."""
    monkeypatch.setenv("MXTPU_DP_DEVICES", "4")
    mod = mx.mod.Module(_mlp())
    assert len(mod._context) == 4
    assert len({c.to_device() for c in mod._context}) == 4
    monkeypatch.setenv("MXTPU_DP_DEVICES", "4096")
    with pytest.raises(MXNetError, match="xla_force_host_platform"):
        mx.mod.Module(_mlp())
    monkeypatch.setenv("MXTPU_DP_DEVICES", "zoom")
    with pytest.raises(MXNetError, match="MXTPU_DP_DEVICES"):
        mx.mod.Module(_mlp())


class _FakeDistModule(object):
    def _global_batch_scale(self):
        return 4


def test_speedometer_reports_global_img_per_sec(caplog):
    """Under multi-process data parallelism each worker's iterator yields
    its LOCAL shard; the Speedometer line must report GLOBAL img/s —
    per-chip local batch x axis size (here scale 4)."""
    import logging
    from mxnet_tpu.callback import Speedometer
    from mxnet_tpu.module.base_module import BatchEndParam

    def fire(mod):
        spd = Speedometer(batch_size=16, frequent=2)
        t0 = time.time() - 1.0  # ~1s window
        spd(BatchEndParam(epoch=0, nbatch=0, eval_metric=None,
                          locals={"self": mod}))
        spd.tic = t0
        spd(BatchEndParam(epoch=0, nbatch=2, eval_metric=None,
                          locals={"self": mod}))
        for rec in caplog.records:
            if "Speed:" in rec.getMessage():
                return float(rec.getMessage().split("Speed: ")[1]
                             .split(" ")[0])
        raise AssertionError("Speedometer did not fire")

    with caplog.at_level(logging.INFO):
        local = fire(object())            # no scale hook -> per-process
    caplog.clear()
    with caplog.at_level(logging.INFO):
        scaled = fire(_FakeDistModule())  # dist module -> x4
    assert 0.8 * 4 < scaled / local < 1.2 * 4, (local, scaled)


def test_module_global_batch_scale_defaults_to_one():
    mod = _fit(8)
    assert mod._global_batch_scale() == 1


# -- the driver's entry points ----------------------------------------------

def test_graft_entry_traces_and_holds_no_reader_of_a_rate():
    """``entry()`` hands the driver a jittable ResNet-50 forward (traced
    here by shape only: nothing runs), and the module that holds the
    multichip gate measures nothing — a rate comes from ``benchmark/`` on
    the chip, never from forced-host CPU devices."""
    import inspect
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.eval_shape(fn, *args)
    assert out.shape == (8, 1000) and out.dtype == jnp.float32
    src = inspect.getsource(g)
    for gone in ("measure_scan_ips", "MIN_EFF", "img_per_sec",
                 "scaling_efficiency", "perf_counter", "MULTICHIP_r"):
        assert gone not in src, gone
    assert callable(g.dryrun_multichip)


# -- the real thing: SIGKILL an 8-device run and resume it ------------------

@pytest.mark.slow
def test_sharded_sigkill_and_resume_bitwise_identical(tmp_path):
    """SIGKILL a chip-count-8 fused run mid-epoch and re-launch it: the
    resumed run must produce bitwise-identical final params to an
    uninterrupted 8-device run — the PR 2 contract, unchanged by
    sharding."""
    worker = os.path.join(os.path.dirname(__file__), "resume_worker.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RESUME_WORKER_CONTEXTS="8",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8"))

    def launch(prefix, out):
        return subprocess.Popen(
            [sys.executable, worker, prefix, out, "2"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env)

    ref_out = str(tmp_path / "ref.npz")
    p = launch(str(tmp_path / "ref-ck"), ref_out)
    assert p.wait(timeout=600) == 0, p.stdout.read()

    prefix = str(tmp_path / "ck")
    out = str(tmp_path / "resumed.npz")
    p = launch(prefix, out)
    killed = False
    deadline = time.monotonic() + 600
    for line in p.stdout:
        if line.startswith("BATCH 1.") and time.monotonic() < deadline:
            os.kill(p.pid, signal.SIGKILL)
            killed = True
            break
    p.wait(timeout=60)
    assert killed, "worker finished before it could be killed"
    assert not os.path.exists(out)

    p = launch(prefix, out)
    assert p.wait(timeout=600) == 0, p.stdout.read()

    ref = np.load(ref_out)
    got = np.load(out)
    assert sorted(ref.files) == sorted(got.files)
    for name in ref.files:
        np.testing.assert_array_equal(ref[name], got[name], err_msg=name)
