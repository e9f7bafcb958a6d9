"""Fleet-tier tests (docs/serving.md "Fleet tier"): model-axis-sharded
engines, the priority-aware FleetRouter, elastic drain/join, replica-death
re-queue, and the batcher race/deadline fixes that ride this PR.

The load-bearing assertions:

* a model-axis-sharded ``ServingEngine.infer`` is BITWISE identical to the
  single-chip engine on the same checkpoint, and its per-bucket programs
  pass memcheck + commscheck with zero findings;
* a dead replica's queued-but-undispatched requests are RE-QUEUED onto
  surviving replicas — no hang, no silent shed;
* priority classes keep their own deadlines under mixed load: an expired
  batch request never poisons an interactive co-rider, and the per-class
  ``ServingHealth`` counters attribute to the right class;
* ``Batcher.submit``/``close`` can no longer race a request into a
  just-shed queue, and ``wait()`` tracks the request's actual deadline
  instead of a 50 ms poll quantum.
"""
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import faults, serving  # noqa: E402
from mxnet_tpu.base import MXNetError, env_int  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

def _mlp_sym():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                                name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _mlp_params(seed=0):
    rs = np.random.RandomState(seed)
    return {
        "arg:fc1_weight": rs.randn(8, 6).astype(np.float32) * 0.5,
        "arg:fc1_bias": rs.randn(8).astype(np.float32) * 0.1,
        "arg:fc2_weight": rs.randn(4, 8).astype(np.float32) * 0.5,
        "arg:fc2_bias": rs.randn(4).astype(np.float32) * 0.1,
    }


def _engine(buckets=(4, 8), **kw):
    return serving.ServingEngine(_mlp_sym(), _mlp_params(), {"data": (6,)},
                                 buckets=buckets, **kw)


def _batcher(**kw):
    kw.setdefault("max_latency_ms", 1.0)
    return serving.Batcher(_engine(), **kw)


def _x(n, seed=1):
    return np.random.RandomState(seed).rand(n, 6).astype(np.float32)


class _GatedEngine(object):
    """Engine proxy whose dispatches block until ``gate`` is set — lets a
    test hold a replica busy without sleeps."""

    def __init__(self, engine):
        self._engine = engine
        self.gate = threading.Event()

    def infer(self, inputs):
        self.gate.wait(10.0)
        return self._engine.infer(inputs)

    def __getattr__(self, name):
        return getattr(self._engine, name)


# ---------------------------------------------------------------------------
# satellites: env_int, close/submit race, wait() deadline fidelity
# ---------------------------------------------------------------------------

def test_env_int_rejects_non_integer_spellings(monkeypatch):
    monkeypatch.setenv("MXTPU_SERVE_QUEUE", "64")
    assert env_int("MXTPU_SERVE_QUEUE", 256) == 64
    for bad in ("256.5", "junk", "1e3"):
        monkeypatch.setenv("MXTPU_SERVE_QUEUE", bad)
        with pytest.raises(MXNetError, match="MXTPU_SERVE_QUEUE"):
            env_int("MXTPU_SERVE_QUEUE", 256)
    monkeypatch.setenv("MXTPU_SERVE_QUEUE", "")
    assert env_int("MXTPU_SERVE_QUEUE", 256) == 256


def test_batcher_rejects_non_integer_queue_env(monkeypatch):
    monkeypatch.setenv("MXTPU_SERVE_QUEUE", "12.7")
    with pytest.raises(MXNetError, match="MXTPU_SERVE_QUEUE"):
        _batcher(start=False)


def test_fleet_rejects_non_integer_queue_env(monkeypatch):
    monkeypatch.setenv("MXTPU_FLEET_QUEUE", "big")
    with pytest.raises(MXNetError, match="MXTPU_FLEET_QUEUE"):
        serving.FleetRouter([_batcher(start=False)])


def test_batcher_close_submit_race_never_orphans_a_request():
    """Regression for the close()/submit() race: a submit that passed the
    _closed check can no longer enqueue AFTER close()'s final shed — every
    accepted request must settle (shed or served), and post-close submits
    fail fast. Hammered across interleavings; with the old unlocked
    enqueue an orphaned request's event stays unset forever."""
    for _ in range(30):
        b = _batcher(start=False)
        accepted = []
        errors = []

        def submitter():
            for _ in range(4):
                try:
                    accepted.append(b.submit({"data": _x(1)}))
                except serving.ServingClosedError:
                    errors.append("closed")

        t1 = threading.Thread(target=submitter)
        t2 = threading.Thread(target=b.close)
        t1.start(); t2.start()
        t1.join(5.0); t2.join(5.0)
        deadline = time.monotonic() + 2.0
        for req in accepted:
            assert req.event.wait(max(0.0, deadline - time.monotonic())), \
                "request accepted by submit() was never settled"
        with pytest.raises(serving.ServingClosedError):
            b.submit({"data": _x(1)})


def test_batcher_wait_tracks_actual_deadline_not_poll_quantum():
    """wait() sleeps toward the request's real remaining deadline: a
    120 ms deadline resolves at ~120 ms, not rounded up to a 50 ms poll
    grid (the old loop woke 20x/s and quantized every deadline)."""
    b = _batcher(start=False)     # parked: nothing will serve it
    req = b.submit({"data": _x(1)}, deadline_ms=120.0)
    t0 = time.monotonic()
    with pytest.raises(serving.ServingDeadlineError):
        b.wait(req)
    elapsed = time.monotonic() - t0
    assert 0.10 <= elapsed < 0.17, elapsed
    b.close()


def test_batcher_on_done_fires_exactly_once():
    calls = []
    b = _batcher(start=False)
    req = b.submit({"data": _x(1)}, on_done=calls.append)
    b.close()                     # settles it (shed)
    assert calls == [req]
    assert req.error is not None
    # double-settle attempts are no-ops
    assert not req.fail(RuntimeError("late"))
    assert calls == [req]

    done = []
    b2 = _batcher()
    r2 = b2.submit({"data": _x(2)}, on_done=done.append)
    out = b2.wait(r2)
    assert out[0].shape == (2, 4)
    assert done == [r2]
    b2.close()


def test_batcher_take_queued_returns_without_failing():
    b = _batcher(start=False)
    r1 = b.submit({"data": _x(1)})
    r2 = b.submit({"data": _x(1)})
    taken = b.take_queued()
    assert taken == [r1, r2]
    assert not r1.event.is_set() and not r2.event.is_set()
    assert b.backlog() == 0
    b.close()


# ---------------------------------------------------------------------------
# model-axis-sharded engine (acceptance: bitwise + analyzer-clean)
# ---------------------------------------------------------------------------

def test_sharded_engine_bitwise_and_analyzer_clean():
    """ACCEPTANCE: a model-axis-sharded ServingEngine.infer equals the
    single-chip engine on the same checkpoint to float32 rounding, and
    every bucket program passes memcheck + commscheck with zero findings.
    No reduction spans shards, but XLA:CPU (jaxlib 0.9) picks its dot
    kernel by operand shape, so a per-shard (4,6)x(6,4) product differs
    from the same columns of the full (4,6)x(6,8) one in the last ulp —
    bitwise is the backend's to give, not the partitioning's."""
    x = _x(3)
    ref = _engine().infer({"data": x})
    for nctx in (2, 4):
        eng = _engine(contexts=[mx.cpu(i) for i in range(nctx)])
        assert eng.model_devices == nctx
        out = eng.infer({"data": x})
        for o, r in zip(out, ref):
            np.testing.assert_array_max_ulp(o, r, maxulp=4)
        findings = [f for f in eng.check(memory=True, comms=True)
                    if not f.suppressed]
        assert findings == [], [f.format() for f in findings]


def test_sharded_engine_params_actually_sharded():
    """The capacity win is real: a sharded engine's weights live split
    over the model mesh (each device holds 1/N of the rows), and its
    compiled programs really contain collectives."""
    eng = _engine(contexts=2)
    w = eng._params["fc1_weight"]           # (8, 6), first-dim rule
    shard_shapes = {tuple(s.data.shape) for s in w.addressable_shards}
    assert shard_shapes == {(4, 6)}
    reports = eng.comms_report()
    assert reports and all(r.collective_count > 0
                           for r in reports.values())


def test_sharded_engine_int_contexts_and_batcher_compose():
    eng = _engine(contexts=2)
    b = serving.Batcher(eng, max_latency_ms=1.0)
    out = b.infer({"data": _x(2)})
    assert np.array_equal(out[0], _engine().infer({"data": _x(2)})[0])
    b.close()


def test_single_chip_engine_reports_no_collectives():
    eng = _engine()
    assert eng.model_devices == 1
    reports = eng.comms_report()
    assert reports and all(r.collective_count == 0
                           for r in reports.values())


# ---------------------------------------------------------------------------
# FleetRouter: routing, priority, drain/join, death
# ---------------------------------------------------------------------------

def test_fleet_routes_and_matches_engine_output():
    router = serving.FleetRouter([_batcher(), _batcher()])
    try:
        x = _x(2)
        out = router.infer({"data": x})
        assert np.array_equal(out[0], _engine().infer({"data": x})[0])
        rep = router.report()
        assert rep["fleet"]["requests"] == 1
        assert rep["classes"]["interactive"]["requests"] == 1
        assert rep["classes"]["batch"]["requests"] == 0
    finally:
        router.close()


def test_fleet_validates_at_submit():
    router = serving.FleetRouter([_batcher()])
    try:
        with pytest.raises(MXNetError, match="per-example shape"):
            router.submit({"data": np.zeros((1, 7), np.float32)})
        with pytest.raises(MXNetError, match="priority"):
            router.submit({"data": _x(1)}, priority="bulk")
        with pytest.raises(MXNetError, match="empty"):
            router.submit({"data": _x(0)})
    finally:
        router.close()


def test_fleet_least_loaded_dispatch_balances():
    """With both replicas parked, assignments alternate by in-flight
    depth — queue-depth-aware dispatch, not round-robin by accident."""
    b1, b2 = _batcher(start=False), _batcher(start=False)
    router = serving.FleetRouter({"a": b1, "b": b2})
    try:
        reqs = [router.submit({"data": _x(1)}, deadline_ms=5000)
                for _ in range(6)]
        t0 = time.monotonic()
        while time.monotonic() - t0 < 2.0:
            rep = router.replica_report()
            if (rep["a"]["assigned"] + rep["b"]["assigned"]) == 6:
                break
            time.sleep(0.01)
        rep = router.replica_report()
        assert rep["a"]["assigned"] == 3
        assert rep["b"]["assigned"] == 3
        b1.start(); b2.start()
        for r in reqs:
            assert len(r.result(timeout=10.0)) > 0
    finally:
        router.close()


def test_fleet_strict_priority_and_expired_batch_never_poisons():
    """Mixed-load per-class semantics (the satellite): with the single
    replica saturated, a later interactive request dispatches BEFORE an
    earlier batch request (strict priority), an expired batch request is
    failed at pop without occupying a dispatch, its expiry is attributed
    to the batch class, and the interactive co-riders all complete."""
    gated = _GatedEngine(_engine())
    b = serving.Batcher(gated, max_latency_ms=1.0, queue_size=1,
                        max_batch=4)
    router = serving.FleetRouter([b], tick_ms=5.0)
    order = []
    try:
        # A occupies the replica queue (gate closed, queue_size=1)
        ra = router.submit({"data": _x(1)}, deadline_ms=8000,
                           on_done=lambda r: order.append("A"))
        t0 = time.monotonic()
        while b.backlog() == 0 and time.monotonic() - t0 < 2.0:
            time.sleep(0.005)
        # B (batch, will expire) and C (batch) queue at the ROUTER;
        # D (interactive) arrives LAST but must dispatch before C
        rb = router.submit({"data": _x(1)}, priority="batch",
                           deadline_ms=30.0,
                           on_done=lambda r: order.append("B"))
        rc = router.submit({"data": _x(1)}, priority="batch",
                           deadline_ms=8000,
                           on_done=lambda r: order.append("C"))
        rd = router.submit({"data": _x(1)}, deadline_ms=8000,
                           on_done=lambda r: order.append("D"))
        time.sleep(0.06)          # let B's deadline lapse in the queue
        gated.gate.set()
        assert len(ra.result(timeout=10.0)) > 0
        assert len(rc.result(timeout=10.0)) > 0
        assert len(rd.result(timeout=10.0)) > 0
        with pytest.raises(serving.ServingDeadlineError):
            rb.result(timeout=10.0)
        assert order.index("D") < order.index("C")
        ch = router.class_health
        assert ch["batch"].expired == 1
        assert ch["interactive"].expired == 0
        assert ch["interactive"].errors == 0
    finally:
        gated.gate.set()
        router.close()


def test_fleet_class_default_deadlines(monkeypatch):
    monkeypatch.setenv("MXTPU_FLEET_INTERACTIVE_DEADLINE_MS", "750")
    monkeypatch.setenv("MXTPU_FLEET_BATCH_DEADLINE_MS", "9000")
    router = serving.FleetRouter([_batcher()])
    try:
        now = time.monotonic()
        ri = router.submit({"data": _x(1)})
        rb = router.submit({"data": _x(1)}, priority="batch")
        assert 0.4 < ri.deadline - now < 0.80
        assert 8.0 < rb.deadline - now < 9.05
        ri.result(timeout=10.0)
        rb.result(timeout=10.0)
    finally:
        router.close()


def test_fleet_backpressure_bounded_per_class(monkeypatch):
    gated = _GatedEngine(_engine())
    b = serving.Batcher(gated, queue_size=1, max_latency_ms=1.0)
    router = serving.FleetRouter([b], queue_size=2)
    try:
        for _ in range(4):   # 1 in replica queue + 2 router + in-flight
            try:
                router.submit({"data": _x(1)}, priority="batch",
                              deadline_ms=5000)
            except serving.ServingOverloadedError:
                break
        with pytest.raises(serving.ServingOverloadedError):
            for _ in range(4):
                router.submit({"data": _x(1)}, priority="batch",
                              deadline_ms=5000)
        assert router.class_health["batch"].dropped >= 1
        assert router.class_health["interactive"].dropped == 0
    finally:
        gated.gate.set()
        router.close()


def test_fleet_drain_flushes_then_retires():
    """Drain under load: stop assigning, flush what the replica owns,
    retire — zero requests shed."""
    gated = _GatedEngine(_engine())
    b0 = serving.Batcher(gated, max_latency_ms=1.0)
    router = serving.FleetRouter({"r0": b0, "r1": _batcher()})
    try:
        reqs = [router.submit({"data": _x(1)}, deadline_ms=10000)
                for _ in range(8)]
        res = {}

        def do_drain():
            res["report"] = router.drain("r0", timeout=15.0)

        t = threading.Thread(target=do_drain)
        t.start()
        time.sleep(0.03)
        gated.gate.set()
        t.join(20.0)
        assert res["report"]["state"] == serving.fleet.RETIRED
        for r in reqs:
            assert len(r.result(timeout=10.0)) > 0
        assert router.health.shed == 0
        assert "r0" not in router.replica_names()
        # a retired replica takes no further work but the fleet serves on
        out = router.infer({"data": _x(1)}, deadline_ms=5000)
        assert out[0].shape == (1, 4)
    finally:
        gated.gate.set()
        router.close()


def test_fleet_join_warms_and_enters_rotation():
    router = serving.FleetRouter([_batcher()])
    try:
        router.join("fresh", _batcher)
        assert "fresh" in router.replica_names()
        # warm-up ran one request per bucket through the new engine
        rep = router.replica_report()["fresh"]
        assert rep["engine_health"]["batches"] >= 2
        out = router.infer({"data": _x(2)})
        assert out[0].shape == (2, 4)
    finally:
        router.close()


def test_fleet_drain_and_warm_rejoin_under_load_sheds_nothing():
    """Two replicas take mixed-class requests from three clients while
    r0 is drained and its engine rejoins warm as r0b: every request
    completes equal to the engine's own answer, nothing fails or is shed
    in either class, and ``router.check`` finds the in-rotation replicas'
    program sets (tracecheck + memory + comms lints) clean, the shared
    engine audited once."""
    b0, b1 = _batcher(), _batcher()
    r0_engine = b0.engine
    router = serving.FleetRouter({"r0": b0, "r1": b1})
    x = _x(1)
    ref = _engine().infer({"data": x})[0]
    outs, errs = [], []
    begun = threading.Event()

    def client(cid):
        try:
            for i in range(20):
                cls = "batch" if (cid + i) % 4 == 0 else "interactive"
                outs.append(router.infer({"data": x}, priority=cls,
                                         deadline_ms=20000)[0])
                begun.set()
        except Exception as e:   # surface in the main thread
            errs.append(e)

    try:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(3)]
        for t in threads:
            t.start()
        assert begun.wait(20.0)
        assert len(outs) < 60              # the drain lands mid-load
        rep = router.drain("r0", timeout=30.0)
        assert rep["state"] == serving.fleet.RETIRED
        router.join("r0b", lambda: serving.Batcher(r0_engine,
                                                   max_latency_ms=1.0))
        for t in threads:
            t.join(60.0)
        assert not any(t.is_alive() for t in threads)
        assert not errs, errs
        assert len(outs) == 60
        for o in outs:
            assert np.array_equal(o, ref)
        fleet = router.report()["fleet"]
        assert fleet["shed"] == 0 and fleet["errors"] == 0
        assert fleet["expired"] == 0 and fleet["dropped"] == 0
        assert sum(router.class_health[c].requests
                   for c in serving.FLEET_CLASSES) == 60
        assert sorted(router.replica_names()) == ["r0b", "r1"]
        findings = [f for f in router.check(memory=True, comms=True)
                    if not f.suppressed]
        assert findings == [], [f.format() for f in findings]
    finally:
        router.close()


def test_fleet_join_rejects_mismatched_signature():
    router = serving.FleetRouter([_batcher()])
    try:
        def bad():
            rs = np.random.RandomState(0)
            params = {
                "arg:fc1_weight": rs.randn(8, 7).astype(np.float32),
                "arg:fc1_bias": rs.randn(8).astype(np.float32),
                "arg:fc2_weight": rs.randn(4, 8).astype(np.float32),
                "arg:fc2_bias": rs.randn(4).astype(np.float32),
            }
            return serving.ServingEngine(_mlp_sym(), params,
                                         {"data": (7,)}, buckets=(4,))
        with pytest.raises(MXNetError, match="signature"):
            router.join("bad", bad)
        assert "bad" not in router.replica_names()
    finally:
        router.close()


@pytest.mark.faults
def test_fleet_replica_die_requeues_undispatched_onto_survivors():
    """ACCEPTANCE: a dead replica's queued-but-undispatched requests are
    re-queued onto survivors — every request completes, nothing hangs,
    nothing is silently shed."""
    router = serving.FleetRouter([_batcher(), _batcher()], tick_ms=5.0)
    try:
        faults.inject("fleet.replica_die", nth=1, kind="die")
        x = _x(1)
        ref = _engine().infer({"data": x})[0]
        reqs = [router.submit({"data": x}, deadline_ms=15000)
                for _ in range(16)]
        for r in reqs:
            out = r.result(timeout=20.0)
            assert np.array_equal(out[0], ref)
        rep = router.report()
        assert rep["fleet"]["requeued"] >= 1
        assert rep["fleet"]["shed"] == 0
        states = sorted(r["state"] for r in rep["replicas"].values())
        assert states == [serving.fleet.ACTIVE, serving.fleet.DEAD]
        dead = [r for r in rep["replicas"].values()
                if r["state"] == serving.fleet.DEAD][0]
        assert "replica death" in dead["died"]
    finally:
        router.close()


@pytest.mark.faults
def test_fleet_single_replica_death_requeues_then_join_recovers():
    """With NO survivor, re-queued requests wait in the router (deadline-
    aware, not shed); a joining replica then serves them."""
    router = serving.FleetRouter([_batcher()], tick_ms=5.0)
    try:
        faults.inject("fleet.replica_die", nth=1, kind="die")
        reqs = [router.submit({"data": _x(1)}, deadline_ms=15000)
                for _ in range(6)]
        t0 = time.monotonic()
        while not router.replica_names(states=(serving.fleet.DEAD,)) \
                and time.monotonic() - t0 < 5.0:
            time.sleep(0.01)
        router.join("rescue", _batcher)
        for r in reqs:
            assert len(r.result(timeout=20.0)) > 0
        assert router.health.shed == 0
    finally:
        router.close()


def test_fleet_close_sheds_queued_with_clear_error():
    gated = _GatedEngine(_engine())
    b = serving.Batcher(gated, queue_size=1, max_latency_ms=1.0)
    router = serving.FleetRouter([b], queue_size=8)
    reqs = [router.submit({"data": _x(1)}, priority="batch",
                          deadline_ms=30000) for _ in range(5)]
    router.close()
    gated.gate.set()
    failed = 0
    for r in reqs:
        try:
            r.result(timeout=10.0)
        except serving.ServingClosedError:
            failed += 1
        except serving.ServingDeadlineError:
            pytest.fail("close must shed promptly, not leak to deadline")
    assert failed >= 1            # everything unserved failed with Closed
    with pytest.raises(serving.ServingClosedError):
        router.submit({"data": _x(1)})


def test_fleet_health_rollup_mirrors_to_process_global():
    base = serving.SERVING_HEALTH.report()["requests"]
    router = serving.FleetRouter([_batcher()])
    try:
        router.infer({"data": _x(1)})
        router.infer({"data": _x(1)}, priority="batch")
        assert serving.SERVING_HEALTH.report()["requests"] >= base + 2
        assert router.health.requests == 2
        assert router.class_health["interactive"].requests == 1
        assert router.class_health["batch"].requests == 1
    finally:
        router.close()


# ---------------------------------------------------------------------------
# model-axis-sharded decode loop
# ---------------------------------------------------------------------------

def _lm_params(num_layers=2, num_heads=4, embed=16, vocab=32, max_len=24,
               seed=3):
    rs = np.random.RandomState(seed)
    p = {"tok_embed_weight": rs.randn(vocab, embed) * 0.3,
         "pos_embed_weight": rs.randn(max_len, embed) * 0.1,
         "final_ln_gamma": np.ones(embed), "final_ln_beta": np.zeros(embed),
         "lm_head_weight": rs.randn(vocab, embed) * 0.3,
         "lm_head_bias": np.zeros(vocab)}
    for i in range(num_layers):
        pre = "layer%d" % i
        p[pre + "_ln1_gamma"] = np.ones(embed)
        p[pre + "_ln1_beta"] = np.zeros(embed)
        p[pre + "_ln2_gamma"] = np.ones(embed)
        p[pre + "_ln2_beta"] = np.zeros(embed)
        p[pre + "_attn_qkv_weight"] = rs.randn(3 * embed, embed) * 0.2
        p[pre + "_attn_qkv_bias"] = np.zeros(3 * embed)
        p[pre + "_attn_out_weight"] = rs.randn(embed, embed) * 0.2
        p[pre + "_attn_out_bias"] = np.zeros(embed)
        p[pre + "_ffn_fc1_weight"] = rs.randn(4 * embed, embed) * 0.2
        p[pre + "_ffn_fc1_bias"] = np.zeros(4 * embed)
        p[pre + "_ffn_fc2_weight"] = rs.randn(embed, 4 * embed) * 0.2
        p[pre + "_ffn_fc2_bias"] = np.zeros(embed)
    return {k: np.asarray(v, np.float32) for k, v in p.items()}


def test_sharded_decode_greedy_token_parity():
    """Sharded decode (KV cache over heads) emits the same greedy tokens
    as the single-chip loop, with the cache genuinely distributed and the
    program set analyzer-clean (donation of the sharded cache included)."""
    params = _lm_params()
    l1 = serving.DecodeLoop(params, 2, 4, 24, slots=2)
    t1 = l1.generate([3, 5, 7], 8).result(timeout=30.0)
    l1.close()
    l2 = serving.DecodeLoop(params, 2, 4, 24, slots=2, contexts=2)
    try:
        t2 = l2.generate([3, 5, 7], 8).result(timeout=30.0)
        assert t1 == t2
        shard_shapes = {tuple(s.data.shape)
                        for s in l2._state["k"].addressable_shards}
        # (layers, slots, rows, heads * head_dim): 4 heads of 4 lanes, a
        # group of 2 heads per device
        assert shard_shapes == {(2, 2, 24, 8)}
        bad = [f for f in l2.check(memory=True, comms=True)
               if not f.suppressed]
        assert bad == [], [f.format() for f in bad]
    finally:
        l2.close()


def test_sharded_decode_rejects_indivisible_heads():
    with pytest.raises(MXNetError, match="heads"):
        serving.DecodeLoop(_lm_params(num_heads=4), 2, 3, 24, contexts=2)
