"""Host off the critical path (ISSUE 4): pipelined K-step dispatch and
asynchronous checkpointing.

Pins the two contracts docs/perf.md "Host off the critical path" and
docs/robustness.md "Asynchronous checkpointing" state:

- bitwise parity: pipelined-vs-eager ``fit`` (params, optimizer state,
  metric folds, checkpoint files; guard on and off) and async-vs-sync
  checkpoint files byte-identical;
- guard semantics under lag: divergence still rolls back, a diverged
  state is never sealed, and the host step-clock mirror never drifts from
  the device counter;
- writer failure modes via the ``ckpt.async_write`` / ``ckpt.async_die``
  fault sites: back-pressure sheds-and-counts, a failed/dead writer loses
  only the in-flight save and restarts.

All tier-1, sleep-free (event-paced; the conftest wall-clock cap enforces
it).
"""
import glob
import json
import logging
import os
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, sym
from mxnet_tpu.base import MXNetError
from mxnet_tpu.model import AsyncCheckpointWriter, CheckpointManager

pytestmark = pytest.mark.pipeline


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def _mlp():
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, num_hidden=16, name="fc1")
    net = sym.Activation(data=net, act_type="relu", name="relu1")
    net = sym.FullyConnected(data=net, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(data=net, name="softmax")


def _toy_data(n=128, dim=10, classes=4, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, dim)).astype(np.float32)
    w = rng.normal(size=(dim, classes)).astype(np.float32)
    y = np.argmax(X @ w, axis=1).astype(np.float32)
    return X, y


def _opt_params():
    from mxnet_tpu import lr_scheduler
    return {"learning_rate": 0.1, "momentum": 0.9,
            "lr_scheduler": lr_scheduler.FactorScheduler(step=5,
                                                         factor=0.5)}


def _fit(X, y, depth, k=2, prefix=None, every=4, async_ckpt=False,
         guard=None, num_epoch=2, pace=False, callbacks=None, keep=10):
    """One deterministic fit; returns (module, manager, captured)."""
    mx.random.seed(3)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mgr = CheckpointManager(prefix, keep=keep) if prefix else None
    captured = []

    def cb(p):
        captured.append((p.epoch, p.nbatch,
                         tuple(v for _, v in
                               p.eval_metric.get_name_value())))
        if pace and mgr is not None:
            # parity runs: drain after every callback so back-pressure
            # (timing-dependent on a loaded host) never sheds a save
            mgr.drain()
        if callbacks:
            callbacks(p)

    mod.fit(it, num_epoch=num_epoch, steps_per_dispatch=k,
            optimizer_params=_opt_params(),
            eval_metric=mx.metric.create(["acc", "ce"]),
            dispatch_pipeline=depth,
            checkpoint_prefix=mgr,
            checkpoint_every_n_batches=every if mgr else None,
            checkpoint_async=async_ckpt, guard=guard,
            batch_end_callback=cb)
    return mod, mgr, captured


def _params_np(mod):
    arg, aux = mod.get_params()
    out = {n: v.asnumpy() for n, v in arg.items()}
    out.update({"aux:" + n: v.asnumpy() for n, v in aux.items()})
    return out


def _opt_states_np(mod):
    import pickle
    return pickle.loads(mod._updater.get_states())


def _files(prefix):
    d = os.path.dirname(prefix)
    return sorted(os.path.basename(p) for p in glob.glob(prefix + "*"))


# -- bitwise parity: pipelined vs eager -------------------------------------

@pytest.mark.parametrize("use_guard", [False, True])
def test_pipelined_vs_eager_fit_bitwise(tmp_path, use_guard, caplog):
    X, y = _toy_data()
    pe = str(tmp_path / "eager" / "ck")
    pp = str(tmp_path / "piped" / "ck")
    with caplog.at_level(logging.WARNING):
        a, _, cba = _fit(X, y, depth=0, prefix=pe, guard=use_guard or None)
        b, _, cbb = _fit(X, y, depth=2, prefix=pp, guard=use_guard or None)
    pa, pb = _params_np(a), _params_np(b)
    assert sorted(pa) == sorted(pb)
    for n in pa:
        np.testing.assert_array_equal(pa[n], pb[n], err_msg=n)
    sa, sb = _opt_states_np(a), _opt_states_np(b)
    assert sorted(sa) == sorted(sb)
    for i in sa:
        fa = sa[i][0] if isinstance(sa[i], tuple) else sa[i]
        fb = sb[i][0] if isinstance(sb[i], tuple) else sb[i]
        if fa is not None:
            np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))
    # the callback SEQUENCE (nbatch, metric folds) is identical — only the
    # wall-clock moment of each fire moved
    assert cba == cbb
    # checkpoint FILES byte-identical (cursor, rng, metric sums, params)
    fe, fp = _files(pe), _files(pp)
    assert fe == fp and len(fe) >= 8
    for name in fe:
        be = open(os.path.join(os.path.dirname(pe), name), "rb").read()
        bp = open(os.path.join(os.path.dirname(pp), name), "rb").read()
        assert be == bp, name


def test_pipelined_jit_cache_keys_unchanged():
    """Pipelining defers the readback; it must not touch what gets
    compiled — jit caches stay keyed (batch, k), guard-off caches stay
    guard-free, and the whole pipelined fit (multi-epoch, epoch tails
    included) never retraces a seen program (tracecheck cache-key differ
    names the drifting argument if it ever does)."""
    from mxnet_tpu.test_utils import assert_no_retrace
    X, y = _toy_data()
    a, _, _ = _fit(X, y, depth=0)
    with assert_no_retrace(msg="pipelined fit"):
        b, _, _ = _fit(X, y, depth=2)
    assert sorted(a._fused._jit_scan) == sorted(b._fused._jit_scan)
    assert not a._fused._jit_scan_g and not b._fused._jit_scan_g


def test_epoch_tail_drains_before_per_step(tmp_path):
    """96 samples / batch 16 with k=4: the 2-batch tail trains per-step —
    the pipeline must drain first so metric folds stay in dispatch order
    and every sample is covered."""
    X, y = _toy_data(n=96)
    mx.random.seed(3)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    seen = []
    mod.fit(it, num_epoch=1, steps_per_dispatch=4, dispatch_pipeline=3,
            optimizer_params={"learning_rate": 0.1},
            batch_end_callback=lambda p: seen.append(
                (p.nbatch, p.eval_metric.num_inst)))
    assert mod._fused_step_count() == 6
    assert seen[-1] == (5, 96)
    # callbacks still arrive in nbatch order despite the lag
    assert [s[0] for s in seen] == sorted(s[0] for s in seen)


# -- host step-clock mirror (satellite) -------------------------------------

def test_fused_step_count_matches_device_without_sync():
    X, y = _toy_data()
    mod, _, _ = _fit(X, y, depth=2)
    assert mod._fused_step_count() == int(
        np.asarray(mod._fused_state["step"]))


def test_fused_step_count_tracks_guard_skips():
    """A guard-skipped step is a device no-op: the host mirror must trail
    num_update by exactly the skip count, matching the device counter."""
    X, y = _toy_data()
    faults.inject("guard.grad_nan", nth=3)
    mod, _, _ = _fit(X, y, depth=1, guard=True, num_epoch=1)
    dev = int(np.asarray(mod._fused_state["step"]))
    assert mod._fused_step_count() == dev
    assert dev == 8 - 1  # 8 steps dispatched, 1 skipped


# -- async vs sync checkpoint bytes -----------------------------------------

def test_async_checkpoint_files_byte_identical(tmp_path):
    X, y = _toy_data()
    ps = str(tmp_path / "sync" / "ck")
    pa = str(tmp_path / "async" / "ck")
    _fit(X, y, depth=1, prefix=ps, async_ckpt=False)
    _fit(X, y, depth=1, prefix=pa, async_ckpt=True, pace=True)
    fs, fa = _files(ps), _files(pa)
    assert fs == fa and len(fs) >= 8
    for name in fs:
        bs = open(os.path.join(os.path.dirname(ps), name), "rb").read()
        ba = open(os.path.join(os.path.dirname(pa), name), "rb").read()
        assert bs == ba, name
    # and the resulting run is resumable: latest validates, known-good
    st = CheckpointManager(pa).load_latest()
    assert st is not None and st.known_good is True


def test_async_save_decoupled_from_later_training(tmp_path):
    """The snapshot must capture save-time state even though training (and
    further saves) continue while the writer works: every manifest's
    num_update must be the cursor at ITS submit, strictly increasing."""
    X, y = _toy_data()
    prefix = str(tmp_path / "ck")
    _fit(X, y, depth=1, prefix=prefix, async_ckpt=True, pace=True, every=2)
    mgr = CheckpointManager(prefix)
    upds = []
    for tag in mgr.list_tags():
        man = json.load(open(mgr._file(tag, "manifest.json")))
        upds.append(man["num_update"])
        st = mgr.load(tag)  # validates checksums over the decoupled bytes
        assert st.known_good is True
    # monotone cursor (an epoch-end save legitimately repeats the last
    # cadence save's num_update with a different epoch cursor)
    assert upds == sorted(upds)


# -- writer mechanics: back-pressure, faults, death -------------------------

def test_writer_backpressure_sheds_and_counts():
    gate = threading.Event()
    done = []
    w = AsyncCheckpointWriter(logger=logging)
    try:
        assert w.submit(lambda: (gate.wait(30), done.append(1)))
        # second submit while the first blocks: shed, not queued
        assert not w.submit(lambda: done.append(2))
        w.note_skip("e0000-b00000008")
        assert w.skipped == 1
        gate.set()
        assert w.drain()
        assert done == [1]
        assert w.submitted == 1 and w.written == 1
    finally:
        gate.set()
        w.close()


def test_backpressure_skip_counts_into_training_health():
    from mxnet_tpu import guard as guard_mod
    h = guard_mod.TrainingHealth()
    gate = threading.Event()
    w = AsyncCheckpointWriter(logger=logging, health=h)
    try:
        assert w.submit(lambda: gate.wait(30))
        w.note_skip("tag")
        assert h.ckpt_skipped == 1
        assert h.report()["ckpt_skipped"] == 1
        gate.set()
    finally:
        gate.set()
        w.close()


def test_async_write_fault_drops_save_keeps_previous(tmp_path, caplog):
    """ckpt.async_write raise: the in-flight save is dropped and counted;
    latest keeps pointing at the previous valid generation."""
    X, y = _toy_data()
    prefix = str(tmp_path / "ck")
    mgr = CheckpointManager(prefix, keep=10)
    mod, _, _ = _fit(X, y, depth=0, num_epoch=1)
    assert mgr.save(mod, 1, 0) is not None
    before = mgr.load_latest()
    mgr.async_writer = AsyncCheckpointWriter(logger=logging)
    try:
        faults.inject("ckpt.async_write", nth=1, kind="raise")
        with caplog.at_level(logging.ERROR):
            mgr.save(mod, 1, 4)
            assert mgr.drain()
        assert mgr.async_writer.errors == 1
        assert any("async checkpoint save failed" in r.message
                   for r in caplog.records)
        st = mgr.load_latest()
        assert st is not None and st.tag == before.tag
    finally:
        mgr.async_writer.close()


def test_async_die_reaped_and_writer_restarts(tmp_path, caplog):
    """ckpt.async_die kills the writer thread mid-job: drain must not
    hang, the corpse is counted, and the next save works again."""
    X, y = _toy_data()
    prefix = str(tmp_path / "ck")
    mgr = CheckpointManager(prefix, keep=10)
    mod, _, _ = _fit(X, y, depth=0, num_epoch=1)
    mgr.async_writer = AsyncCheckpointWriter(logger=logging)
    try:
        faults.inject("ckpt.async_die", nth=1, kind="die")
        with caplog.at_level(logging.WARNING):
            assert mgr.save(mod, 1, 0) is not None
            assert mgr.drain() is False       # job lost, not hung
        assert mgr.async_writer.errors == 1
        assert mgr.load_latest() is None      # nothing was written
        # the writer restarts transparently on the next save
        assert mgr.save(mod, 1, 4) is not None
        assert mgr.drain() is True
        assert mgr.async_writer.restarts == 1
        st = mgr.load_latest()
        assert st is not None and st.batches_done == 4
    finally:
        mgr.async_writer.close()


def test_manager_reusable_after_async_fit(tmp_path):
    """fit detaches (not just closes) the writer it created: the same
    manager must drive a second async fit and a manual sync save without
    hitting the closed writer."""
    X, y = _toy_data()
    prefix = str(tmp_path / "ck")
    mgr = CheckpointManager(prefix, keep=10)
    mx.random.seed(3)
    it = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    fit_kw = dict(num_epoch=1, steps_per_dispatch=2,
                  optimizer_params={"learning_rate": 0.1},
                  checkpoint_prefix=mgr, checkpoint_every_n_batches=4,
                  checkpoint_async=True)
    mod.fit(it, **fit_kw)
    assert mgr.async_writer is None            # detached at teardown
    assert mgr.last_async_writer.written >= 1  # counters survive
    it.reset()
    mod.fit(it, **fit_kw)                      # second async fit works
    assert mgr.save(mod, 9, 0) is not None     # manual save falls to sync
    assert mgr.load_latest() is not None


def test_sync_snapshot_skips_decoupled_state_copies(tmp_path):
    """A sync save writes inline before training resumes — it must not pay
    the device-side optimizer-state replica the async writer needs."""
    X, y = _toy_data()
    mod, _, _ = _fit(X, y, depth=0, num_epoch=1)
    calls = []
    orig = mod._snapshot_opt_states
    mod._snapshot_opt_states = lambda: calls.append(1) or orig()
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(mod, 1, 0)
    assert calls == []                         # sync: copy-free path
    mgr.async_writer = AsyncCheckpointWriter(logger=logging)
    try:
        mgr.save(mod, 1, 4)
        mgr.drain()
        assert calls == [1]                    # async: decoupled snapshot
    finally:
        mgr.async_writer.close()


def test_writer_drain_timeout_zero_polls():
    gate = threading.Event()
    w = AsyncCheckpointWriter(logger=logging)
    try:
        assert w.submit(lambda: gate.wait(30))
        assert w.drain(timeout=0) is False     # poll, never block
        gate.set()
        assert w.drain() is True
    finally:
        gate.set()
        w.close()


def test_closed_writer_rejects_submit():
    w = AsyncCheckpointWriter(logger=logging)
    w.close()
    with pytest.raises(MXNetError, match="closed"):
        w.submit(lambda: None)


# -- guard semantics under lag ----------------------------------------------

def test_pipelined_guard_divergence_still_rolls_back(tmp_path, caplog):
    """Divergence detection is allowed a bounded staleness of `depth`
    dispatches — but it must still fire, roll back to a pre-spike
    checkpoint, and never seal a diverged state."""
    from mxnet_tpu.guard import TrainingGuard
    X, y = _toy_data()
    prefix = str(tmp_path / "ck")
    g = TrainingGuard(window=50, spike_factor=4.0, patience=2,
                      max_rollbacks=5, logger=logging)
    faults.inject("guard.loss_spike", nth=5, times=2)
    with caplog.at_level(logging.WARNING):
        mod, mgr, _ = _fit(X, y, depth=2, prefix=prefix, every=2, guard=g)
    assert g.health.rollbacks == 1
    assert any("rolling back" in r.message for r in caplog.records)
    # every surviving checkpoint is known-good (diverged state never sealed)
    mgr2 = CheckpointManager(prefix, keep=10)
    for tag in mgr2.list_tags():
        man = json.load(open(mgr2._file(tag, "manifest.json")))
        assert man["known_good"] is True, tag
    # and training completed bitwise-reproducibly after the rollback
    assert all(np.isfinite(v).all() for v in _params_np(mod).values())


def test_guard_async_ckpt_and_pipeline_compose(tmp_path):
    """All three at once (guard + async ckpt + pipelined dispatch): a NaN
    step is skipped on device, counted, and the run's checkpoints stay
    resumable."""
    X, y = _toy_data()
    prefix = str(tmp_path / "ck")
    faults.inject("guard.grad_nan", nth=4)
    mod, mgr, _ = _fit(X, y, depth=2, prefix=prefix, every=4,
                       async_ckpt=True, pace=True, guard=True)
    st = CheckpointManager(prefix).load_latest()
    assert st is not None and st.known_good is True
    # manifest's fused_step trails num_update by the one skipped step
    assert st.fused_step == st.num_update - 1


# -- Speedometer suffix (satellite) -----------------------------------------

def test_speedometer_appends_pipeline_suffix(caplog):
    from collections import namedtuple
    from mxnet_tpu.callback import Speedometer
    BatchEndParam = namedtuple("BatchEndParams",
                               ["epoch", "nbatch", "eval_metric", "locals"])

    class _P(object):
        depth = 2
        host_stall = 0.0

    p = _P()
    sp = Speedometer(batch_size=16, frequent=4)
    with caplog.at_level(logging.INFO):
        for nbatch in (1, 3, 5, 7, 9):
            p.host_stall += 0.125
            sp(BatchEndParam(epoch=0, nbatch=nbatch, eval_metric=None,
                             locals={"pipeline": p}))
    lines = [r.getMessage() for r in caplog.records]
    piped = [ln for ln in lines if "Pipeline:" in ln]
    assert len(piped) >= 2, lines
    assert "depth=2" in piped[0]
    # per-window stall, not cumulative: the init call (nbatch 1) baselines
    # at 0.125, the first fire (nbatch 5) covers two 0.125 pushes, the
    # second fire (nbatch 9) two more
    assert "host_stall=0.250s" in piped[0]
    assert "host_stall=0.250s" in piped[1]


def test_speedometer_interleaved_stream_keeps_stall_baseline(caplog):
    """A param from another callback stream (no pipeline in locals — e.g.
    score()) must not reset the stall baseline: the next pipelined window
    reports only ITS stall, not the run's whole accumulated total."""
    from collections import namedtuple
    from mxnet_tpu.callback import Speedometer
    BatchEndParam = namedtuple("BatchEndParams",
                               ["epoch", "nbatch", "eval_metric", "locals"])

    class _P(object):
        depth = 2
        host_stall = 0.0

    p = _P()
    sp = Speedometer(batch_size=16, frequent=4)
    with caplog.at_level(logging.INFO):
        for nbatch in (1, 3):
            p.host_stall += 1.0
            sp(BatchEndParam(epoch=0, nbatch=nbatch, eval_metric=None,
                             locals={"pipeline": p}))
        # interleaved pipeline-less stream (fresh count restarts windows)
        sp(BatchEndParam(epoch=0, nbatch=0, eval_metric=None, locals={}))
        for nbatch in (1, 3, 5):
            p.host_stall += 0.125
            sp(BatchEndParam(epoch=0, nbatch=nbatch, eval_metric=None,
                             locals={"pipeline": p}))
    piped = [r.getMessage() for r in caplog.records
             if "Pipeline:" in r.getMessage()]
    assert piped, caplog.records
    # baseline was set at the first init (stall=1.0) and must survive the
    # interleaved call: the fire covers 2.375 - 1.0. A clobbered baseline
    # (the bug) would report the whole 2.375s run total
    assert "host_stall=1.375s" in piped[0], piped


def test_speedometer_no_pipeline_suffix_when_eager(caplog):
    from collections import namedtuple
    from mxnet_tpu.callback import Speedometer
    BatchEndParam = namedtuple("BatchEndParams",
                               ["epoch", "nbatch", "eval_metric", "locals"])
    sp = Speedometer(batch_size=16, frequent=2)
    with caplog.at_level(logging.INFO):
        for nbatch in (1, 3, 5):
            sp(BatchEndParam(epoch=0, nbatch=nbatch, eval_metric=None,
                             locals={}))
    assert not any("Pipeline:" in (r.getMessage())
                   for r in caplog.records)


# -- in-place imperative invoke (satellite, python side) --------------------

def test_imperative_invoke_in_place_updates_existing_handles():
    from mxnet_tpu import c_api
    code, h_in = c_api.MXNDArrayCreate([3], 1, 0)
    assert code == 0
    code, _ = c_api.MXNDArraySyncCopyFromCPU(
        h_in, np.array([1.0, 2.0, 3.0], np.float32))
    assert code == 0
    code, h_out = c_api.MXNDArrayCreate([3], 1, 0)
    assert code == 0
    target_before = c_api._get(h_out)
    code, n = c_api.MXImperativeInvokeInPlace("square", [h_in], {}, [h_out])
    assert code == 0 and n == 1
    # same NDArray object, new data — the handle identity is the contract
    assert c_api._get(h_out) is target_before
    np.testing.assert_array_equal(c_api._get(h_out).asnumpy(),
                                  [1.0, 4.0, 9.0])


def test_imperative_invoke_in_place_count_mismatch_fails():
    from mxnet_tpu import c_api
    code, h_in = c_api.MXNDArrayCreate([3], 1, 0)
    assert code == 0
    code, h1 = c_api.MXNDArrayCreate([3], 1, 0)
    assert code == 0
    code, h2 = c_api.MXNDArrayCreate([3], 1, 0)
    assert code == 0
    code, err = c_api.MXImperativeInvokeInPlace("square", [h_in], {},
                                                [h1, h2])
    assert code != 0
    msg = c_api.MXGetLastError()
    assert "output array" in msg


def test_imperative_invoke_in_place_shape_mismatch_fails():
    from mxnet_tpu import c_api
    code, h_in = c_api.MXNDArrayCreate([3], 1, 0)
    assert code == 0
    code, h_out = c_api.MXNDArrayCreate([2, 3], 1, 0)
    assert code == 0
    before = c_api._get(h_out).asnumpy().copy()
    code, err = c_api.MXImperativeInvokeInPlace("square", [h_in], {},
                                                [h_out])
    assert code != 0
    assert "shape mismatch" in c_api.MXGetLastError()
    # the caller's array must be untouched on a refused write
    np.testing.assert_array_equal(c_api._get(h_out).asnumpy(), before)


def test_imperative_invoke_in_place_records_autograd():
    # the in-place path must record the CALLER's out arrays on the tape
    # (invoke(out=...)), not hidden temporaries — backward through the out
    # handle has to reach the inputs
    from mxnet_tpu import c_api, nd, autograd as ag
    x = nd.array(np.array([1.0, 2.0, 3.0], np.float32))
    out = nd.zeros((3,))
    gx = nd.zeros((3,))
    ag.mark_variables([x], [gx])
    h_in = c_api._new_handle(x)
    h_out = c_api._new_handle(out)
    with ag.train_section():
        code, n = c_api.MXImperativeInvokeInPlace("square", [h_in], {},
                                                  [h_out])
        assert code == 0 and n == 1
    ag.compute_gradient([out])
    np.testing.assert_allclose(gx.asnumpy(), [2.0, 4.0, 6.0])


def test_imperative_invoke_in_place_dtype_mismatch_fails():
    from mxnet_tpu import c_api
    from mxnet_tpu.ndarray import NDArray
    import jax.numpy as jnp
    code, h_in = c_api.MXNDArrayCreate([3], 1, 0)
    assert code == 0
    h_out = c_api._new_handle(NDArray(jnp.zeros((3,), jnp.int32)))
    code, err = c_api.MXImperativeInvokeInPlace("square", [h_in], {},
                                                [h_out])
    assert code != 0
    assert "dtype mismatch" in c_api.MXGetLastError()
    assert c_api._get(h_out).dtype == np.int32
