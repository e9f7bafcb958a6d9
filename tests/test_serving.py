"""Serving-tier tests (docs/serving.md): AOT shape-bucketed engine,
dynamic batcher, continuous-batching decode loop, fault shedding.

The load-bearing assertions:

* batched ``serving.infer()`` output is BITWISE equal to unbatched
  ``Predictor.forward`` on the same rows — padding to a bucket never leaks
  into real examples;
* the serving program set (every AOT bucket + the decode body) audits
  clean under tracecheck, donation of the KV cache included;
* greedy decode through the slot loop is token-for-token identical to
  full re-forward decoding, across sequences joining and leaving
  mid-stream;
* a killed decode loop / closed batcher sheds in-flight requests with a
  clear error instead of hanging callers.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import faults, models, serving  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402


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


def _x(n, seed=1):
    return np.random.RandomState(seed).rand(n, 6).astype(np.float32)


# ---------------------------------------------------------------------------
# engine: buckets, padding parity, chunking, export
# ---------------------------------------------------------------------------

def test_engine_bucket_selection():
    eng = _engine(buckets=(2, 4, 16))
    assert eng.bucket_for(1) == 2
    assert eng.bucket_for(2) == 2
    assert eng.bucket_for(3) == 4
    assert eng.bucket_for(16) == 16
    with pytest.raises(MXNetError):
        eng.bucket_for(17)
    assert eng.max_batch == 16


def test_engine_pad_parity_bitwise_vs_predictor():
    """Acceptance: batched serving.infer == unbatched Predictor.forward,
    bitwise — the pad rows added to reach the bucket never leak."""
    eng = _engine(buckets=(4, 8))
    x = _x(3)
    out = eng.infer({"data": x})[0]           # padded 3 -> bucket 4
    params = {k: mx.nd.array(v) for k, v in _mlp_params().items()}
    pred = mx.Predictor(_mlp_sym(), params, {"data": (3, 6)})
    ref = pred.forward(data=x).get_output(0).asnumpy()
    assert out.shape == (3, 4)
    assert np.array_equal(out, ref)


def test_engine_pad_content_never_leaks():
    """Same rows, different co-riders/padding -> bitwise-identical rows."""
    eng = _engine(buckets=(4,))
    x = _x(3)
    a = eng.infer({"data": x})[0]             # zero-padded internally
    junk = np.full((1, 6), 1e6, np.float32)   # hostile 4th row
    b = eng.infer({"data": np.concatenate([x, junk])})[0][:3]
    assert np.array_equal(a, b)


def test_engine_chunks_requests_larger_than_max_bucket():
    eng = _engine(buckets=(4, 8))
    x = _x(19)
    out = eng.infer({"data": x})[0]
    assert out.shape == (19, 4)
    ref = eng.infer({"data": x[:4]})[0]
    assert np.array_equal(out[:4], ref)


def test_engine_input_validation():
    eng = _engine(buckets=(4,))
    with pytest.raises(MXNetError):
        eng.infer({})                          # missing input
    with pytest.raises(MXNetError):
        eng.infer({"data": np.zeros((2, 7), np.float32)})  # bad shape
    with pytest.raises(MXNetError):
        eng.infer({"data": np.zeros((0, 6), np.float32)})  # empty


def test_engine_missing_param_raises_by_name():
    params = _mlp_params()
    del params["arg:fc2_bias"]
    with pytest.raises(MXNetError, match="fc2_bias"):
        serving.ServingEngine(_mlp_sym(), params, {"data": (6,)},
                              buckets=(4,))
    # deliberate zero-fill still available
    eng = serving.ServingEngine(_mlp_sym(), params, {"data": (6,)},
                                buckets=(4,), allow_missing=True)
    out = eng.infer({"data": _x(2)})[0]
    assert np.all(np.isfinite(out))


def test_engine_export_import_cold_start(tmp_path):
    eng = _engine(buckets=(4, 8))
    x = _x(5)
    ref = eng.infer({"data": x})[0]
    path = str(tmp_path / "exe.bin")
    try:
        eng.export_compiled(path)
    except MXNetError:
        pytest.skip("backend cannot serialize executables")
    eng2 = serving.ServingEngine(_mlp_sym(), _mlp_params(), {"data": (6,)},
                                 buckets=(4, 8), executables=path)
    assert np.array_equal(eng2.infer({"data": x})[0], ref)


def test_engine_stale_executables_fall_back(tmp_path):
    eng = _engine(buckets=(4,))
    path = str(tmp_path / "exe.bin")
    try:
        eng.export_compiled(path)
    except MXNetError:
        pytest.skip("backend cannot serialize executables")
    # different bucket set: must warn + recompile, not serve stale programs
    eng2 = serving.ServingEngine(_mlp_sym(), _mlp_params(), {"data": (6,)},
                                 buckets=(2,), executables=path)
    out = eng2.infer({"data": _x(2)})[0]
    assert out.shape == (2, 4)


def test_engine_tracecheck_clean():
    """The serving bucket programs gate at zero findings, like the train
    step programs."""
    eng = _engine(buckets=(2, 4))
    findings = eng.check()
    assert [f.format() for f in findings] == []


@pytest.mark.parametrize("model,quantize", [("mlp", "none"),
                                            ("lenet", "none"),
                                            ("mlp", "int8")])
def test_served_program_set_stays_lint_clean_under_load(model, quantize):
    """A zoo model (dense and conv; float32 and int8 weights) behind the
    batcher with concurrent clients: every request completes, and the
    programs the engine REGISTERED while serving audit at zero
    unsuppressed findings."""
    import threading
    from mxnet_tpu import tracecheck
    from mxnet_tpu.autotune.harness import serve_model
    _name, sym, params, shape = serve_model(model)
    eng = serving.ServingEngine(sym, params, {"data": shape},
                                buckets=(1, 8), quantize=quantize)
    b = serving.Batcher(eng, max_latency_ms=5.0)
    x1 = np.random.RandomState(1).rand(1, *shape).astype(np.float32)
    ref = eng.infer({"data": x1})[0]
    outs, errs = [], []

    def client():
        try:
            for _ in range(8):
                outs.append(b.infer({"data": x1})[0])
        except Exception as e:   # surface in the main thread
            errs.append(e)

    threads = [threading.Thread(target=client) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60.0)
    b.close()
    assert not errs and len(outs) == 24
    assert b.health.requests == 24 and b.health.errors == 0
    # co-riders share a larger bucket's program: XLA:CPU picks its dot
    # kernel by shape, so equal to rounding, not bitwise
    for o in outs:
        np.testing.assert_allclose(o, ref, rtol=1e-5, atol=1e-6)
    assert eng.quant_mode == quantize
    served = [r.name for r in tracecheck.registered_programs()
              if r.name.startswith(eng.name + "/")]
    assert len(served) == 2, served            # one program a bucket
    findings = tracecheck.unsuppressed(
        tracecheck.check_registered(match=eng.name + "/"))
    assert [f.format() for f in findings] == []


# ---------------------------------------------------------------------------
# batcher
# ---------------------------------------------------------------------------

def test_batcher_coalesces_backlog_into_one_bucket():
    eng = _engine(buckets=(4, 8))
    b = serving.Batcher(eng, max_latency_ms=50.0, start=False)
    x = _x(3)
    reqs = [b.submit({"data": x[i:i + 1]}) for i in range(3)]
    before = eng.health.batches
    b.start()
    outs = [b.wait(r) for r in reqs]
    got = np.concatenate([o[0] for o in outs])
    params = {k: mx.nd.array(v) for k, v in _mlp_params().items()}
    pred = mx.Predictor(_mlp_sym(), params, {"data": (3, 6)})
    ref = pred.forward(data=x).get_output(0).asnumpy()
    assert np.array_equal(got, ref)
    # the backlog coalesced: one dispatch for all three requests
    assert eng.health.batches == before + 1
    assert b.health.requests == 3
    b.close()


def test_batcher_concurrent_callers_bitwise():
    import threading
    eng = _engine(buckets=(4, 8))
    b = serving.Batcher(eng, max_latency_ms=20.0)
    x = _x(8)
    results = [None] * 8
    errs = []

    def call(i):
        try:
            results[i] = b.infer({"data": x[i:i + 1]})[0]
        except Exception as e:   # surface in the main thread
            errs.append(e)

    threads = [threading.Thread(target=call, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    got = np.concatenate(results)
    ref = eng.infer({"data": x})[0]
    assert np.array_equal(got, ref)
    b.close()


def test_batcher_request_deadline_expires():
    eng = _engine(buckets=(4,))
    b = serving.Batcher(eng, start=False)
    req = b.submit({"data": _x(1)}, deadline_ms=0.0)
    b.start()
    with pytest.raises(serving.ServingDeadlineError):
        b.wait(req)
    assert b.health.expired >= 1
    b.close()


def test_batcher_backpressure_bounded_queue():
    eng = _engine(buckets=(4,))
    b = serving.Batcher(eng, queue_size=1, start=False)
    b.submit({"data": _x(1)})
    with pytest.raises(serving.ServingOverloadedError):
        b.submit({"data": _x(1)})
    assert b.health.dropped == 1
    b.close()


def test_batcher_oversized_request_rejected():
    eng = _engine(buckets=(4,))
    b = serving.Batcher(eng, start=False)
    with pytest.raises(MXNetError, match="max_batch"):
        b.submit({"data": _x(5)})
    b.close()


def test_batcher_rejects_malformed_shape_at_submit():
    """A bad per-example shape is rejected ALONE at submit — once
    coalesced it would fail every innocent co-rider in its batch."""
    eng = _engine(buckets=(4,))
    b = serving.Batcher(eng, max_latency_ms=50.0, start=False)
    good = b.submit({"data": _x(1)})
    with pytest.raises(MXNetError, match="per-example shape"):
        b.submit({"data": np.zeros((1, 7), np.float32)})
    b.start()
    out = b.wait(good)[0]          # the valid request is unaffected
    assert out.shape == (1, 4)
    b.close()


def test_batcher_close_sheds_queued_requests():
    eng = _engine(buckets=(4,))
    b = serving.Batcher(eng, start=False)
    r1 = b.submit({"data": _x(1)})
    r2 = b.submit({"data": _x(1)})
    b.close()
    for r in (r1, r2):
        with pytest.raises(serving.ServingClosedError):
            b.wait(r)
    assert b.health.shed == 2
    with pytest.raises(serving.ServingClosedError):
        b.submit({"data": _x(1)})


@pytest.mark.faults
def test_fault_enqueue_drop_rejects_with_clear_error():
    eng = _engine(buckets=(4,))
    b = serving.Batcher(eng, start=False)
    with faults.scoped("serve.enqueue_drop", nth=2, kind="drop"):
        b.submit({"data": _x(1)})              # call 1: clean
        with pytest.raises(serving.ServingOverloadedError,
                           match="enqueue"):
            b.submit({"data": _x(1)})          # call 2: dropped
    assert b.health.dropped == 1
    b.close()


# ---------------------------------------------------------------------------
# continuous-batching decode loop
# ---------------------------------------------------------------------------

_LM = dict(vocab_size=17, embed=16, num_heads=2, num_layers=2, seq_len=12)


def _lm_setup(seed=3):
    sym = models.transformer(**_LM)
    s = _LM["seq_len"]
    arg_shapes, _, _ = sym.infer_shape(data=(1, s), softmax_label=(1, s))
    rs = np.random.RandomState(seed)
    params = {}
    for n, shp in zip(sym.list_arguments(), arg_shapes):
        if n in ("data", "softmax_label"):
            continue
        params[n] = (rs.randn(*shp) * 0.3).astype(np.float32)
    eng = serving.ServingEngine(sym, params, {"data": (s,)}, buckets=(1,))
    return params, eng


def _ref_greedy(eng, prompt, max_new):
    """Greedy decode by full re-forward through the AOT engine."""
    s = _LM["seq_len"]
    seq = list(prompt)
    out = []
    for _ in range(max_new):
        x = np.zeros((1, s), np.float32)
        x[0, :len(seq)] = seq
        probs = eng.infer({"data": x})[0]      # (seq, vocab)
        tok = int(np.argmax(probs[len(seq) - 1]))
        out.append(tok)
        seq.append(tok)
    return out


def test_decode_greedy_parity_with_slot_join_leave():
    """Acceptance: the decode loop demonstrates slot join/leave mid-stream
    with the KV cache donated across steps, and greedy decode matches full
    re-forward token-for-token (cache numerics are right)."""
    params, eng = _lm_setup()
    loop = serving.DecodeLoop(params, num_layers=_LM["num_layers"],
                              num_heads=_LM["num_heads"],
                              max_len=_LM["seq_len"], slots=2)
    try:
        prompts = [[1, 2, 3], [4, 5], [6]]
        news = [5, 4, 6]
        # three sequences through two slots: the third must JOIN after an
        # earlier one retires, mid-stream
        futs = [loop.generate(p, n) for p, n in zip(prompts, news)]
        got = [f.result(timeout=120) for f in futs]
        ref = [_ref_greedy(eng, p, n) for p, n in zip(prompts, news)]
        assert got == ref
        assert [len(g) for g in got] == news
        assert loop.health.joined == 3
        assert loop.health.retired == 3
    finally:
        loop.close()


def test_decode_tracecheck_clean_including_donation():
    """The decode body's KV cache donation must actually alias (a copy
    would double serving memory) and the program must carry no host syncs
    or f64 leaks: zero findings."""
    params, _eng = _lm_setup()
    loop = serving.DecodeLoop(params, num_layers=_LM["num_layers"],
                              num_heads=_LM["num_heads"],
                              max_len=_LM["seq_len"], slots=2)
    try:
        findings = loop.check()
        assert [f.format() for f in findings] == []
    finally:
        loop.close()


def test_decode_validation():
    params, _eng = _lm_setup()
    loop = serving.DecodeLoop(params, num_layers=_LM["num_layers"],
                              num_heads=_LM["num_heads"],
                              max_len=_LM["seq_len"], slots=1)
    try:
        with pytest.raises(MXNetError):
            loop.generate([], 3)
        with pytest.raises(MXNetError, match="cache length"):
            loop.generate(list(range(10)), 10)
    finally:
        loop.close()
    bad = dict(params)
    del bad["lm_head_bias"]
    with pytest.raises(MXNetError, match="lm_head_bias"):
        serving.DecodeLoop(bad, num_layers=_LM["num_layers"],
                           num_heads=_LM["num_heads"],
                           max_len=_LM["seq_len"])


def test_decode_rejects_silent_gather_clamps():
    """jit-mode gather CLAMPS out-of-range indices — a max_len past the
    positional table or an out-of-vocab prompt id would produce silently
    wrong tokens; both must raise up front."""
    params, _eng = _lm_setup()
    with pytest.raises(MXNetError, match="positional embedding"):
        serving.DecodeLoop(params, num_layers=_LM["num_layers"],
                           num_heads=_LM["num_heads"],
                           max_len=_LM["seq_len"] + 1)
    loop = serving.DecodeLoop(params, num_layers=_LM["num_layers"],
                              num_heads=_LM["num_heads"],
                              max_len=_LM["seq_len"], slots=1)
    try:
        with pytest.raises(MXNetError, match="vocabulary"):
            loop.generate([_LM["vocab_size"]], 1)
        with pytest.raises(MXNetError, match="vocabulary"):
            loop.generate([-1], 1)
    finally:
        loop.close()


def test_decode_result_never_hangs_after_close():
    """result() on a future that raced close() must resolve — served or
    shed with ServingClosedError — never spin forever."""
    params, _eng = _lm_setup()
    loop = serving.DecodeLoop(params, num_layers=_LM["num_layers"],
                              num_heads=_LM["num_heads"],
                              max_len=_LM["seq_len"], slots=1)
    fut = loop.generate([1, 2], 10)
    loop.close()
    try:
        toks = fut.result(timeout=30)     # either fully served pre-close…
        assert len(toks) == 10
    except serving.ServingClosedError:
        pass                              # …or shed with a clear error


@pytest.mark.faults
def test_fault_decode_die_sheds_in_flight_requests():
    """A killed decode loop must fail waiting callers with a clear error
    — never hang them — and refuse new work."""
    params, _eng = _lm_setup()
    loop = serving.DecodeLoop(params, num_layers=_LM["num_layers"],
                              num_heads=_LM["num_heads"],
                              max_len=_LM["seq_len"], slots=2)
    try:
        faults.inject("serve.decode_die", nth=3, kind="die")
        fut = loop.generate([1, 2, 3], 8)
        with pytest.raises(serving.ServingClosedError, match="died"):
            fut.result(timeout=60)
        assert loop.health.shed >= 1
        assert loop.dead is not None
        with pytest.raises(serving.ServingClosedError):
            loop.generate([1], 1)
    finally:
        faults.clear("serve.decode_die")
        loop.close()


# ---------------------------------------------------------------------------
# health plumbing
# ---------------------------------------------------------------------------

def test_serving_health_mirrors_process_global():
    base = serving.SERVING_HEALTH.report()
    eng = _engine(buckets=(4,))
    eng.infer({"data": _x(3)})
    after = serving.SERVING_HEALTH.report()
    assert after["batches"] == base["batches"] + 1
    assert after["examples"] == base["examples"] + 3
    assert after["padded"] == base["padded"] + 1
    assert eng.health.report()["batches"] == 1


# ---------------------------------------------------------------------------
# retrace pins (docs/static_analysis.md): the serving tier is AOT — the
# jit entries behind the compiled executables must NEVER grow a cache
# ---------------------------------------------------------------------------

def test_engine_bucket_switching_never_retraces():
    """Alternating request sizes across every bucket — padding, exact fit,
    chunking past the max — is pure executable reuse: the engine's
    underlying jit entry must not trace once (a trace here means the AOT
    path silently fell back to jit dispatch)."""
    from mxnet_tpu.test_utils import assert_no_retrace
    eng = _engine(buckets=(2, 4, 8))
    with assert_no_retrace(eng._jfn):
        for n in (1, 4, 2, 8, 3, 20, 1, 8):
            outs = eng.infer({"data": _x(n)})
            assert outs[0].shape[0] == n


def test_decode_join_retire_cycles_never_retrace():
    """Sequences joining free slots mid-stream, retiring at different
    lengths, and fresh rounds re-filling the slots all ride ONE compiled
    decode body — no retrace across the whole churn."""
    from mxnet_tpu.test_utils import assert_no_retrace
    params, _eng = _lm_setup()
    loop = serving.DecodeLoop(params, num_layers=_LM["num_layers"],
                              num_heads=_LM["num_heads"],
                              max_len=_LM["seq_len"], slots=2)
    try:
        with assert_no_retrace(loop._jfn):
            for _round in range(2):
                futs = [loop.generate(p, n)
                        for p, n in zip([[1, 2], [3], [4, 5, 6]],
                                        [3, 2, 2])]
                for f in futs:
                    f.result(timeout=120)
        assert loop.health.retired == 6
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# memory audit (docs/static_analysis.md "Memory lints")
# ---------------------------------------------------------------------------

def test_engine_memory_report_and_check_clean():
    """Every compiled bucket reports a static memory profile (no
    recompile, nothing executes) and the default budget audits clean."""
    eng = _engine(buckets=(2, 4))
    reps = eng.memory_report()
    assert sorted(reps) == [2, 4]
    for rep in reps.values():
        assert rep.peak_bytes > 0
        assert rep.argument_bytes > 0
        assert rep.platform
    assert [f.format() for f in eng.check(memory=True)] == []


def test_engine_memory_budget_findings():
    """An absurd budget turns every bucket into an hbm-budget finding plus
    one resident-set finding over the co-resident bucket set."""
    eng = _engine(buckets=(2, 4))
    fs = eng.check(memory=True, budget=256)
    lints = [f.lint for f in fs]
    assert lints.count("hbm-budget") == 2
    assert lints.count("resident-set") == 1
    rs = [f for f in fs if f.lint == "resident-set"][0]
    assert "bucket[b=2]" in rs.message and "bucket[b=4]" in rs.message


def test_engine_load_audit_error_mode():
    """MXTPU_MEMCHECK=error: a deploy whose bucket set cannot fit the
    budget fails at LOAD, naming the findings — not at the first
    full-batch request."""
    from mxnet_tpu import engine as _engmod
    prev = _engmod.set_memcheck("error")
    os.environ["MXTPU_MEMCHECK_BUDGET"] = "256"
    try:
        with pytest.raises(MXNetError, match="memory audit"):
            _engine(buckets=(2,))
    finally:
        del os.environ["MXTPU_MEMCHECK_BUDGET"]
        _engmod.set_memcheck(prev)
    # warn mode constructs fine and logs instead
    prev = _engmod.set_memcheck("warn")
    os.environ["MXTPU_MEMCHECK_BUDGET"] = "256"
    try:
        eng = _engine(buckets=(2,))
        assert eng.infer({"data": _x(2)})[0].shape[0] == 2
    finally:
        del os.environ["MXTPU_MEMCHECK_BUDGET"]
        _engmod.set_memcheck(prev)
    # a MALFORMED budget is an operator error, not an analyzer failure:
    # it must propagate even in warn mode rather than silently disarm
    # the gate the operator just configured
    prev = _engmod.set_memcheck("warn")
    os.environ["MXTPU_MEMCHECK_BUDGET"] = "16gigs"
    try:
        with pytest.raises(MXNetError, match="MXTPU_MEMCHECK_BUDGET"):
            _engine(buckets=(2,))
    finally:
        del os.environ["MXTPU_MEMCHECK_BUDGET"]
        _engmod.set_memcheck(prev)


def test_decode_memory_report_cache_aliased():
    """The decode body's dominant buffer is the donated KV cache — the
    memory report must show it fully aliased (a copy would double serving
    memory per step) and the memory lints stay clean."""
    params, _eng = _lm_setup()
    loop = serving.DecodeLoop(params, num_layers=_LM["num_layers"],
                              num_heads=_LM["num_heads"],
                              max_len=_LM["seq_len"], slots=2)
    try:
        # the program set now includes the prefix-cache get/put helpers;
        # the decode body is the one named "step[...]"
        reports = loop.memory_report()
        (name, rep), = [(n, r) for n, r in reports.items()
                        if "step[" in n]
        embed = params["tok_embed_weight"].shape[1]
        head_dim = embed // _LM["num_heads"]
        cache_bytes = 2 * (_LM["num_layers"] * 2 * _LM["num_heads"]
                           * _LM["seq_len"] * head_dim) * 4
        assert rep.alias_bytes >= cache_bytes
        assert rep.unaliased_donated == []
        assert [f.format() for f in loop.check(memory=True)] == []
    finally:
        loop.close()
