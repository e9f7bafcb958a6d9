"""Device-fed input tier (mxnet_tpu/data/, docs/perf.md "Device-fed input
pipeline"): shard-aware reader, decode worker pool, prefetch-to-device,
PipelineStats — and the tier's load-bearing contract: worker parallelism
never perturbs the sample stream (bitwise train parity across worker
counts, deterministic shuffle + resume), and failures are prompt and
named, never hangs (fault sites ``data.worker_die``/``data.decode_delay``).
"""
import io as _bio
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import data as mdata
from mxnet_tpu import faults, io as mxio, recordio
from mxnet_tpu.base import MXNetError

PIL = pytest.importorskip("PIL.Image")


# -- dataset helpers --------------------------------------------------------

def _make_rec(path, n=64, h=40, w=40, classes=4, seed=0, quality=92):
    rng = np.random.default_rng(seed)
    colors = np.array([[200, 40, 40], [40, 200, 40], [40, 40, 200],
                       [200, 200, 40]], np.float32)
    idx = os.path.splitext(path)[0] + ".idx"
    rec = recordio.MXIndexedRecordIO(idx, path, "w")
    for i in range(n):
        k = i % classes
        img = (rng.normal(110, 25, (h, w, 3))
               + 0.55 * (colors[k % 4] - 110)).clip(0, 255).astype(np.uint8)
        buf = _bio.BytesIO()
        PIL.fromarray(img).save(buf, format="JPEG", quality=quality)
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(k), i, 0), buf.getvalue()))
    rec.close()
    return path


def _record_iter(rec, num_workers, **kw):
    kw.setdefault("data_shape", (3, 32, 32))
    kw.setdefault("batch_size", 16)
    kw.setdefault("resize", 36)
    return mx.image.ImageRecordIter(path_imgrec=rec,
                                    num_workers=num_workers, **kw)


def _small_convnet(nc=4):
    d = mx.sym.Variable("data")
    n = mx.sym.Convolution(data=d, num_filter=8, kernel=(3, 3),
                           stride=(2, 2), pad=(1, 1), name="c1")
    n = mx.sym.BatchNorm(data=n, fix_gamma=False, name="bn1")
    n = mx.sym.Activation(data=n, act_type="relu")
    n = mx.sym.Pooling(data=n, global_pool=True, kernel=(1, 1),
                       pool_type="avg")
    n = mx.sym.Flatten(data=n)
    n = mx.sym.FullyConnected(data=n, num_hidden=nc, name="fc")
    return mx.sym.SoftmaxOutput(data=n, name="softmax")


# -- PipelineStats ----------------------------------------------------------

def test_pipeline_stats_stages_and_mirror():
    parent = mdata.PipelineStats()
    st = mdata.PipelineStats(parent=parent)
    st.add("read", 0.5, n=10)
    st.add("decode", 1.0, n=10)
    st.add("stall", 0.25)
    st.note_queue_depth(2)
    st.note_queue_depth(4)
    rep = st.report()
    assert rep["read_s"] == 0.5 and rep["read_n"] == 10
    assert rep["decode_s"] == 1.0
    assert rep["stall_s"] == 0.25 and rep["stall_frac"] > 0
    assert rep["queue_depth_avg"] == 3.0 and rep["queue_depth_max"] == 4
    # mirrors into the parent aggregate (the io.DATA_HEALTH convention)
    assert parent.report()["decode_s"] == 1.0
    assert parent.report()["queue_depth_max"] == 4
    st.reset()
    assert "read_s" not in st.report()


def test_pipeline_stats_timed():
    st = mdata.PipelineStats()
    assert st.timed("read", lambda: 7) == 7
    assert st.report()["read_n"] == 1


# -- ShardedRecordReader ----------------------------------------------------

def test_reader_two_level_sharding(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=64)
    full = mdata.ShardedRecordReader(rec)
    assert len(full) == 64
    host0 = mdata.ShardedRecordReader(rec, part_index=0, num_parts=2)
    host1 = mdata.ShardedRecordReader(rec, part_index=1, num_parts=2)
    assert host0.keys == full.keys[:32] and host1.keys == full.keys[32:]
    # per-chip sub-shard within the host shard (the data-mesh feeder)
    sub = mdata.ShardedRecordReader(rec, part_index=1, num_parts=2,
                                    sub_index=1, sub_parts=4)
    assert sub.keys == full.keys[32:][8:16]
    with pytest.raises(MXNetError, match="sub_parts"):
        mdata.ShardedRecordReader(rec, sub_index=0, sub_parts=128)


def test_reader_epoch_order_pure_function(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=32)
    r1 = mdata.ShardedRecordReader(rec, shuffle=True, seed=7)
    r2 = mdata.ShardedRecordReader(rec, shuffle=True, seed=7)
    # pure function of (seed, epoch): no reset-history dependence, and
    # calling epoch 5 before epoch 0 changes nothing
    assert r1.epoch_order(5) == r2.epoch_order(5)
    assert r1.epoch_order(0) == r2.epoch_order(0)
    assert r1.epoch_order(0) != r1.epoch_order(1)
    assert sorted(r1.epoch_order(1)) == sorted(r1.keys)
    r3 = mdata.ShardedRecordReader(rec, shuffle=True, seed=8)
    assert r3.epoch_order(0) != r1.epoch_order(0)
    plain = mdata.ShardedRecordReader(rec, shuffle=False, seed=7)
    assert plain.epoch_order(3) == plain.keys


def test_reader_reads_and_corrupt_classification(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=8)
    r = mdata.ShardedRecordReader(rec)
    hdr, payload = r.read(r.keys[3])
    assert hdr.label == 3.0 and payload[:2] == b"\xff\xd8"
    # truncate the file mid-way: a damaged record classifies as
    # CorruptRecordError (permanent; skip path), not a retried transient
    size = os.path.getsize(rec)
    with open(rec, "r+b") as f:
        f.truncate(size - 10)
    r2 = mdata.ShardedRecordReader(rec)
    with pytest.raises(mxio.CorruptRecordError):
        r2.read(r2.keys[-1])
    assert r2.data_health.report()["retries"] == 0  # permanent: no retry


def test_reader_transient_retry_rides_policy(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=8)
    faults.clear()
    health = mxio.DataHealth()
    r = mdata.ShardedRecordReader(
        rec, retry_policy=mxio.RetryPolicy(max_retries=2, base_delay=0.0),
        data_health=health)
    faults.inject("io.record_read", nth=1, kind="transient")
    hdr, _ = r.read(r.keys[0])
    assert hdr.label == 0.0
    assert health.report()["retries"] == 1
    faults.clear()


def test_reader_thread_safe_concurrent_reads(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=32)
    r = mdata.ShardedRecordReader(rec)
    import threading
    errs = []

    def hammer():
        try:
            for k in r.keys:
                hdr, payload = r.read(k)
                assert hdr.label == float(k % 4)
                assert payload[:2] == b"\xff\xd8"
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=hammer) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs


# -- DecodeWorkerPool -------------------------------------------------------

def _echo_tasks(n):
    return [(list(range(i * 4, (i + 1) * 4)), 100 + i) for i in range(n)]


def test_pool_emits_in_order_any_worker_count():
    def batch_fn(keys, seed):
        time.sleep(0.001 * (seed % 3))  # jitter completion order
        return (list(keys), seed)

    for nw in (1, 3):
        pool = mdata.DecodeWorkerPool(batch_fn, _echo_tasks(9), nw)
        got = []
        while True:
            try:
                got.append(pool.next_batch())
            except StopIteration:
                break
        assert got == [(list(range(i * 4, (i + 1) * 4)), 100 + i)
                       for i in range(9)]
        pool.close()


def test_pool_decode_error_surfaces_at_its_batch_position():
    def batch_fn(keys, seed):
        if seed == 102:
            raise mxio.CorruptRecordError("batch 2 is bad")
        return seed

    pool = mdata.DecodeWorkerPool(batch_fn, _echo_tasks(5), 2)
    assert pool.next_batch() == 100
    assert pool.next_batch() == 101
    with pytest.raises(mxio.CorruptRecordError, match="batch 2"):
        pool.next_batch()
    pool.close()


@pytest.mark.faults
def test_pool_dead_worker_fails_consumer_promptly():
    faults.clear()
    pool = mdata.DecodeWorkerPool(lambda keys, seed: seed,
                                  _echo_tasks(8), 1)
    faults.inject("data.worker_die", nth=3, kind="die")
    assert pool.next_batch() == 100
    assert pool.next_batch() == 101
    t0 = time.monotonic()
    with pytest.raises(MXNetError, match="data.worker_die"):
        for _ in range(6):
            pool.next_batch()
    assert time.monotonic() - t0 < 5.0, "detection must be prompt"
    faults.clear()
    pool.close()


@pytest.mark.faults
def test_pool_slow_worker_stalls_but_never_reorders():
    faults.clear()
    faults.inject("data.decode_delay", nth=2, kind="delay", delay=0.3)
    stats = mdata.PipelineStats()
    pool = mdata.DecodeWorkerPool(lambda keys, seed: seed,
                                  _echo_tasks(6), 2, stats=stats)
    got = []
    while True:
        try:
            got.append(pool.next_batch())
        except StopIteration:
            break
    assert got == [100 + i for i in range(6)], "order must survive a stall"
    rep = stats.report()
    # direct pool consumption charges "wait" (through the prefetcher the
    # same delay surfaces as training-loop "stall" once the queue dries)
    assert rep["wait_s"] >= 0.1, rep
    faults.clear()
    pool.close()


def test_pool_claim_pacing_bounds_decode_ahead():
    """One slow batch must not trigger unbounded decode-ahead: claims are
    paced to a window of queue_depth + workers past the consumer."""
    claimed = []

    def batch_fn(keys, seed):
        claimed.append(seed)
        if seed == 100:
            time.sleep(0.3)
        return seed

    pool = mdata.DecodeWorkerPool(batch_fn, _echo_tasks(40), 2,
                                  queue_depth=2)
    assert pool.next_batch() == 100
    # while batch 0 slept, workers could claim at most the pacing window
    assert len(claimed) <= 2 + 2 + 2 + 1, claimed  # window + in-flight slop
    while True:
        try:
            pool.next_batch()
        except StopIteration:
            break
    assert sorted(claimed) == [100 + i for i in range(40)]
    pool.close()


# -- image iterators through the pool --------------------------------------

def test_record_iter_pool_matches_legacy_no_shuffle(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=64)
    legacy = _record_iter(rec, 0, prefetch=False)
    pooled = _record_iter(rec, 2)
    for _ in range(4):
        a, b = legacy.next_host(), pooled.next_host()
        np.testing.assert_array_equal(a.data[0], b.data[0])
        np.testing.assert_array_equal(a.label[0], b.label[0])
    pooled.close()


def test_record_iter_pool_shuffle_parity_across_worker_counts(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=64)
    kw = dict(shuffle=True, seed=3, rand_crop=True, rand_mirror=True)
    one = _record_iter(rec, 1, **kw)
    four = _record_iter(rec, 4, **kw)
    for _ in range(2):  # two epochs: order differs across, matches within
        for _ in range(4):
            a, b = one.next_host(), four.next_host()
            np.testing.assert_array_equal(a.data[0], b.data[0])
            np.testing.assert_array_equal(a.label[0], b.label[0])
        one.reset()
        four.reset()
    one.close()
    four.close()


def test_record_iter_set_epoch_resumes_mid_schedule(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=64)
    kw = dict(shuffle=True, seed=3, rand_crop=True, rand_mirror=True)
    ref = _record_iter(rec, 1, **kw)
    epochs = []
    for _ in range(3):
        epochs.append([ref.next_host().data[0].copy() for _ in range(4)])
        ref.reset()
    ref.close()
    # a FRESH iterator pinned to epoch 2 reproduces epoch 2 exactly —
    # the property fit's resume fast-forward depends on
    fresh = _record_iter(rec, 2, **kw)
    fresh.set_epoch(2)
    for want in epochs[2]:
        np.testing.assert_array_equal(want, fresh.next_host().data[0])
    fresh.close()


def test_record_iter_pool_round_batch_pad(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=40)  # 2.5 batches of 16
    it = _record_iter(rec, 2)
    pads = []
    while True:
        try:
            pads.append(it.next_host().pad)
        except StopIteration:
            break
    assert pads == [0, 0, 8]  # tail wraps 8 records, reported as pad
    it.close()
    legacy = _record_iter(rec, 0, prefetch=False)
    lpads = []
    while True:
        try:
            lpads.append(legacy.next_host().pad)
        except StopIteration:
            break
    assert lpads == pads


def test_record_iter_pool_sub_sharding(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=64)
    whole = _record_iter(rec, 1, batch_size=8)
    chip1 = _record_iter(rec, 1, batch_size=8, sub_index=1, sub_parts=2)
    whole_labels = []
    for _ in range(8):
        whole_labels.extend(whole.next_host().label[0].tolist())
    chip_labels = []
    for _ in range(4):
        chip_labels.extend(chip1.next_host().label[0].tolist())
    assert chip_labels == whole_labels[32:]
    whole.close()
    chip1.close()


def test_image_iter_pool_parity_and_aug_determinism(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=48)
    aug = mx.image.CreateAugmenter((3, 24, 24), resize=28, rand_crop=True,
                                   rand_mirror=True)
    kw = dict(batch_size=16, data_shape=(3, 24, 24), path_imgrec=rec,
              shuffle=True, seed=9, aug_list=aug)
    a = mx.image.ImageIter(num_workers=1, **kw)
    b = mx.image.ImageIter(num_workers=3, **kw)
    for _ in range(3):
        ba, bb = a.next_host(), b.next_host()
        np.testing.assert_array_equal(ba.data[0], bb.data[0])
        np.testing.assert_array_equal(ba.label[0], bb.label[0])
    a.close()
    b.close()


def test_image_iter_pool_skip_corrupt_backfills_deterministically(tmp_path):
    rec = str(tmp_path / "a.rec")
    idx = str(tmp_path / "a.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    rng = np.random.default_rng(0)
    for i in range(16):
        if i == 5:
            w.write_idx(i, recordio.pack(
                recordio.IRHeader(0, float(i), i, 0), b"not a jpeg"))
            continue
        img = rng.integers(0, 255, (28, 28, 3)).astype(np.uint8)
        buf = _bio.BytesIO()
        PIL.fromarray(img).save(buf, format="JPEG")
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), buf.getvalue()))
    w.close()
    health = mxio.DataHealth()
    kw = dict(batch_size=8, data_shape=(3, 28, 28), path_imgrec=rec,
              skip_corrupt=True, data_health=health)
    it1 = mx.image.ImageIter(num_workers=1, **kw)
    it3 = mx.image.ImageIter(num_workers=3,
                             data_health=mxio.DataHealth(),
                             **{k: v for k, v in kw.items()
                                if k != "data_health"})
    b1, b3 = it1.next_host(), it3.next_host()
    np.testing.assert_array_equal(b1.data[0], b3.data[0])
    # slot 5 backfilled from slot 4 (nearest previous good), counted
    np.testing.assert_array_equal(b1.data[0][5], b1.data[0][4])
    assert b1.label[0][5] == 4.0
    assert health.report()["skipped_records"] == 1
    # without skip_corrupt the pool path raises at the right batch
    strict = mx.image.ImageIter(num_workers=2, batch_size=8,
                                data_shape=(3, 28, 28), path_imgrec=rec)
    with pytest.raises(mxio.CorruptRecordError):
        strict.next_host()
    it1.close()
    it3.close()
    strict.close()


# -- prefetch-to-device -----------------------------------------------------

def test_device_prefetcher_stages_and_superbatch(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=64)
    it = _record_iter(rec, 2)
    pf = mdata.DevicePrefetcher(it, 2, depth=1)
    assert pf.stats is it.data_stats  # ONE stats object for the tier
    sb = pf.next()
    assert sb.data[0].shape == (2, 16, 3, 32, 32)
    assert sb.num_steps == 2
    rep = pf.stats.report()
    for stage in ("read_s", "decode_s", "stack_s", "h2d_s"):
        assert rep.get(stage, 0) > 0, (stage, rep)
    assert "stall_frac" in rep and "queue_depth_avg" in rep
    pf.close()
    it.close()


def test_device_prefetcher_set_epoch_delegates(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=64)
    kw = dict(shuffle=True, seed=3)
    ref = _record_iter(rec, 1, **kw)
    ref.reset()  # epoch 1
    want = ref.next_host().data[0].copy()
    ref.close()
    it = _record_iter(rec, 2, **kw)
    pf = mdata.DevicePrefetcher(it, 2, depth=1)
    pf.set_epoch(1)
    sb = pf.next()
    np.testing.assert_array_equal(np.asarray(sb.data[0].data)[0], want)
    pf.close()
    it.close()


# -- fit through the tier: the bitwise contracts ---------------------------

def _fit_params(rec, num_workers, k=2, epochs=2, ckpt=None, resume=None,
                num_epoch_override=None):
    mx.random.seed(0)
    it = _record_iter(rec, num_workers, shuffle=True, seed=5)
    mod = mx.mod.Module(_small_convnet())
    mod.fit(it, num_epoch=num_epoch_override or epochs,
            steps_per_dispatch=k,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            checkpoint_prefix=ckpt, resume=resume,
            checkpoint_every_n_batches=4 if ckpt else None)
    it.close()
    arg, aux = mod.get_params()
    out = {n: v.asnumpy() for n, v in arg.items()}
    out.update({n: v.asnumpy() for n, v in aux.items()})
    return out


def test_fit_bitwise_parity_across_worker_counts(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=128)
    p1 = _fit_params(rec, 1)
    p4 = _fit_params(rec, 4)
    assert sorted(p1) == sorted(p4)
    for n in p1:
        np.testing.assert_array_equal(p1[n], p4[n], err_msg=n)


def test_fit_through_tier_programs_lint_clean_and_health_reported(tmp_path):
    """A real-JPEG epoch through reader -> 2 decode workers -> superbatch
    stack -> prefetch-to-device -> fused K-step scan: the programs the
    fit registered audit at zero unsuppressed tracecheck findings, the
    iterator's own DataHealth reports its counters (all zero: nothing
    went wrong), and PipelineStats saw every stage."""
    from mxnet_tpu import tracecheck
    rec = _make_rec(str(tmp_path / "a.rec"), n=64)
    mx.random.seed(0)
    it = _record_iter(rec, 2, shuffle=True, seed=5)
    mod = mx.mod.Module(_small_convnet())
    mod.fit(it, num_epoch=1, steps_per_dispatch=2,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    assert mod._fused._jit_scan               # the scan path took the data
    health = it.data_health.report()
    assert {k: health[k] for k in ("retries", "skipped_records",
                                   "failures")} == {
        "retries": 0, "skipped_records": 0, "failures": 0}
    stats = it.data_stats.report()
    for stage in ("read_s", "decode_s", "stack_s", "h2d_s"):
        assert stats.get(stage, 0) > 0, (stage, stats)
    it.close()
    prefix = mod._fused._watcher.name + "/"
    names = [r.name for r in tracecheck.registered_programs()
             if r.name.startswith(prefix)]
    assert any("scan[" in n for n in names), names
    findings = tracecheck.unsuppressed(
        tracecheck.check_registered(match=prefix))
    assert [f.format() for f in findings] == []


def test_fit_resume_through_pool_bitwise(tmp_path):
    """Kill-free resume equivalence: train epoch 0 with checkpoints, then
    a FRESH process-state (new module + new iterator) resumes at epoch 1
    via set_epoch fast-forward — final params bitwise-match the
    uninterrupted 2-epoch run. This is the tier-1 stand-in for the slow
    SIGKILL test, exercising the same epoch-pinning path."""
    rec = _make_rec(str(tmp_path / "a.rec"), n=128)
    ref = _fit_params(rec, 2)
    ck = str(tmp_path / "ck")
    _fit_params(rec, 2, ckpt=ck, resume="auto", num_epoch_override=1)
    got = _fit_params(rec, 2, ckpt=ck, resume="auto")
    for n in ref:
        np.testing.assert_array_equal(ref[n], got[n], err_msg=n)


@pytest.mark.faults
def test_fit_dead_worker_surfaces_not_hangs(tmp_path):
    rec = _make_rec(str(tmp_path / "a.rec"), n=128)
    faults.clear()
    faults.inject("data.worker_die", nth=3, kind="die")
    it = _record_iter(rec, 2, shuffle=True, seed=5)
    mod = mx.mod.Module(_small_convnet())
    with pytest.raises(MXNetError, match="data.worker_die"):
        mod.fit(it, num_epoch=1, steps_per_dispatch=2,
                optimizer_params={"learning_rate": 0.1})
    faults.clear()
    it.close()


# -- MXTPU_BF16_STATS (perf.md next-steps item 2) --------------------------

def test_bf16_stats_storage_dtypes_and_sync(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_BF16_STATS", "all")
    rec = _make_rec(str(tmp_path / "a.rec"), n=64)
    mx.random.seed(0)
    it = _record_iter(rec, 1, shuffle=True, seed=5)
    mod = mx.mod.Module(_small_convnet())
    mod.fit(it, num_epoch=1, steps_per_dispatch=2,
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    it.close()
    st = mod._fused_state
    assert str(st["aux"]["bn1_moving_mean"].dtype) == "bfloat16"
    mom = st["opt"]["c1_weight"]
    leaf = mom[0] if isinstance(mom, tuple) else mom
    assert str(leaf.dtype) == "bfloat16"
    # executor arrays and checkpoints stay f32 (exact widen-back)
    _, aux = mod.get_params()
    assert aux["bn1_moving_mean"].asnumpy().dtype == np.float32
    assert np.isfinite(aux["bn1_moving_mean"].asnumpy()).all()
    # serialized optimizer state stays f32 too
    states = str(tmp_path / "opt.states")
    mod.save_optimizer_states(states)
    mod.load_optimizer_states(states)


def test_bf16_stats_run_to_run_deterministic(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_BF16_STATS", "all")
    rec = _make_rec(str(tmp_path / "a.rec"), n=64)
    a = _fit_params(rec, 2, epochs=1)
    b = _fit_params(rec, 2, epochs=1)
    for n in a:
        np.testing.assert_array_equal(a[n], b[n], err_msg=n)


# -- SIGKILL through the worker pool (slow tier) ---------------------------

@pytest.mark.slow
def test_sigkill_and_resume_through_worker_pool(tmp_path):
    """The PR 2 SIGKILL contract THROUGH the device-fed tier: a killed run
    re-launched with the same command line — shuffling ImageRecordIter,
    2 decode workers, superbatch dispatch — lands bitwise-identical final
    params (deterministic epoch order + set_epoch fast-forward)."""
    rec = _make_rec(str(tmp_path / "train.rec"), n=256, h=32, w=32)
    worker = os.path.join(os.path.dirname(__file__), "resume_worker.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               RESUME_WORKER_IMAGE_REC=rec,
               RESUME_WORKER_DATA_WORKERS="2")

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
    for line in p.stdout:
        if line.startswith("BATCH 1."):
            os.kill(p.pid, signal.SIGKILL)
            killed = True
            break
    p.wait(timeout=60)
    assert killed, "worker finished before it could be killed"
    assert not os.path.exists(out)

    p = launch(prefix, out)
    assert p.wait(timeout=600) == 0, p.stdout.read()
    ref, got = np.load(ref_out), np.load(out)
    assert sorted(ref.files) == sorted(got.files)
    for name in ref.files:
        np.testing.assert_array_equal(ref[name], got[name], err_msg=name)
