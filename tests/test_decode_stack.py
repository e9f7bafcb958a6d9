"""Production decode path tests (docs/serving.md "Production decode
path"): in-graph sampling, quantized weights, prefix-cache reuse,
speculative decoding.

The load-bearing assertions:

* ``temperature=0`` through the sampled body is BITWISE the greedy path
  (token-for-token against full re-forward through the AOT engine);
* a fixed seed reproduces the exact token stream regardless of which
  co-riders share the batch or how slots churn — per-(seed, position)
  randomness, not per-dispatch;
* int8 quantization cuts resident weight bytes by >= 40% with the
  quality gate green, and a sharded quantized engine holds 1/N of the
  quantized bytes per chip (scale sharded beside its weight);
* prefix-cache hits produce the IDENTICAL stream a cold prefill would
  (reuse changes where decoding starts, never what it computes);
* speculative decode output is token-identical to target-only sampling
  under the same seeds — with a perfect draft (100%-ish acceptance) AND
  with a deliberately weak one;
* the ``serve.sample`` / ``serve.spec_verify`` fault sites shed every
  in-flight sequence with a clear error, never a hang.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mxnet_tpu as mx  # noqa: E402,F401
from mxnet_tpu import faults, models, serving  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.serving.quantize import check_quality  # noqa: E402


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

_LM = dict(vocab_size=17, embed=16, num_heads=2, num_layers=2, seq_len=12)


def _lm_params(seed=3, num_layers=None):
    cfg = dict(_LM)
    if num_layers is not None:
        cfg["num_layers"] = num_layers
    sym = models.transformer(**cfg)
    s = cfg["seq_len"]
    arg_shapes, _, _ = sym.infer_shape(data=(1, s), softmax_label=(1, s))
    rs = np.random.RandomState(seed)
    return {n: (rs.randn(*shp) * 0.3).astype(np.float32)
            for n, shp in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}


def _loop(params=None, **kw):
    kw.setdefault("slots", 2)
    return serving.DecodeLoop(params if params is not None else _lm_params(),
                              num_layers=_LM["num_layers"],
                              num_heads=_LM["num_heads"],
                              max_len=_LM["seq_len"], **kw)


def _gen(loop, prompt, n, **kw):
    return loop.generate(prompt, n, **kw).result(timeout=120.0)


# ---------------------------------------------------------------------------
# in-graph sampling
# ---------------------------------------------------------------------------

def test_temperature_zero_is_bitwise_greedy():
    """temp=0 rows must take the argmax value chain (no scaling, no
    sort): identical tokens to the default-greedy generate call."""
    params = _lm_params()
    loop = _loop(params, prefix_cache=False)
    try:
        prompts = [[1, 2, 3], [4, 5], [6, 7, 1]]
        greedy = [_gen(loop, p, 5) for p in prompts]
        explicit = [_gen(loop, p, 5, temperature=0.0, top_k=3, top_p=0.5,
                         seed=99) for p in prompts]
        assert greedy == explicit
    finally:
        loop.close()


def test_fixed_seed_reproduces_stream_across_loops():
    params = _lm_params()
    outs = []
    for _ in range(2):
        loop = _loop(params, prefix_cache=False)
        try:
            outs.append(_gen(loop, [1, 2, 3], 6, temperature=0.9,
                             top_k=8, top_p=0.9, seed=42))
        finally:
            loop.close()
    assert outs[0] == outs[1]
    assert len(outs[0]) == 6


def test_sampled_stream_immune_to_corider_churn():
    """Per-(seed, position) randomness: the SAME request draws the SAME
    tokens whether it runs alone or with co-riders joining and retiring
    around it mid-stream."""
    params = _lm_params()
    loop = _loop(params, prefix_cache=False)
    try:
        alone = _gen(loop, [1, 2, 3], 8, temperature=0.8, seed=7)
        # now the same request with churn: short co-riders retire and new
        # ones join while it decodes
        fut = loop.generate([1, 2, 3], 8, temperature=0.8, seed=7)
        riders = [loop.generate([i + 1], 2, temperature=1.2, seed=i)
                  for i in range(4)]
        crowded = fut.result(timeout=120.0)
        for r in riders:
            r.result(timeout=120.0)
        assert crowded == alone
    finally:
        loop.close()


def test_sampling_validation_rejects_nonsense():
    loop = _loop(prefix_cache=False)
    try:
        with pytest.raises(MXNetError, match="temperature"):
            loop.generate([1], 1, temperature=-0.5)
        with pytest.raises(MXNetError, match="top_k"):
            loop.generate([1], 1, top_k=-1)
        with pytest.raises(MXNetError, match="top_p"):
            loop.generate([1], 1, top_p=0.0)
        with pytest.raises(MXNetError, match="prefix_len"):
            loop.generate([1, 2], 1, prefix_len=2)
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# quantized weights
# ---------------------------------------------------------------------------

def test_int8_weight_bytes_reduction_and_quality_gate():
    params = _lm_params()
    f32 = _loop(params, quantize="none", prefix_cache=False)
    q8 = _loop(params, quantize="int8", prefix_cache=False)
    try:
        reduction = 1.0 - q8.weight_bytes() / f32.weight_bytes()
        assert reduction >= 0.40, reduction
        # the loop still decodes sensibly: greedy streams agree with the
        # f32 loop on this tiny model (the engine-level gate below is the
        # deploy workflow)
        a = _gen(f32, [1, 2, 3], 5)
        b = _gen(q8, [1, 2, 3], 5)
        assert len(b) == 5
        agree = np.mean([x == y for x, y in zip(a, b)])
        assert agree >= 0.6, (a, b)
    finally:
        f32.close()
        q8.close()


@pytest.mark.slow
def test_bf16_mode_halves_weight_bytes():
    params = _lm_params()
    f32 = _loop(params, quantize="none", prefix_cache=False)
    bf = _loop(params, quantize="bf16", prefix_cache=False)
    try:
        assert bf.weight_bytes() == f32.weight_bytes() // 2
        assert len(_gen(bf, [1, 2], 4)) == 4
    finally:
        f32.close()
        bf.close()


def test_engine_quality_gate_workflow():
    """The documented deploy gate: probe the f32 and quantized engines
    with the same batch; check_quality passes at high agreement and
    raises below the floor."""
    sym = models.transformer(**_LM)
    params = _lm_params()
    s = _LM["seq_len"]
    ref = serving.ServingEngine(sym, params, {"data": (s,)}, buckets=(2,))
    q = serving.ServingEngine(sym, params, {"data": (s,)}, buckets=(2,),
                              quantize="int8")
    probe = np.zeros((2, s), np.float32)
    probe[:, :3] = [[1, 2, 3], [4, 5, 6]]
    rep = q.quality_report(ref, {"data": probe})
    # the transformer engine emits per-position logits, so a (2, seq)
    # probe compares 2*seq rows, not 2
    assert rep["probe_rows"] == 2 * _LM["seq_len"]
    check_quality(rep, min_agree=0.9, who="test")
    # an engine that disagrees must fail loudly, naming the numbers
    bad = {"top1_agreement": 0.5, "max_abs_err": 3.0, "probe_rows": 2}
    with pytest.raises(MXNetError, match="quality gate FAILED"):
        check_quality(bad, min_agree=0.98, who="test")
    assert ref.quant_mode == "none" and q.quant_mode == "int8"
    assert q.weight_bytes() < ref.weight_bytes()


def test_sharded_quantized_engine_holds_one_nth_per_chip():
    """int8 payloads shard along axis 0 (auto_spec's first choice) with
    the per-channel scale pinned to the SAME split: each chip holds 1/N
    of the quantized bytes, not a replicated copy."""
    import jax
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs the forced multi-device host")
    rs = np.random.RandomState(0)
    net = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                num_hidden=len(devs), name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    params = {"arg:fc1_weight":
              rs.randn(len(devs), 6).astype(np.float32),
              "arg:fc1_bias": rs.randn(len(devs)).astype(np.float32)}
    eng = serving.ServingEngine(net, params, {"data": (6,)}, buckets=(2,),
                                contexts=devs, quantize="int8")
    leaf = eng._params["fc1_weight"]
    assert set(leaf) == {"q", "s"}
    qshards = leaf["q"].addressable_shards
    assert len(qshards) == len(devs)
    assert qshards[0].data.shape[0] == 1          # 1/N of axis 0
    sshards = leaf["s"].addressable_shards
    assert sshards[0].data.shape[0] == 1          # scale rides the split
    out = eng.infer({"data": np.zeros((2, 6), np.float32)})[0]
    assert out.shape == (2, len(devs))


@pytest.mark.slow
def test_update_params_requantizes_in_place():
    params = _lm_params()
    loop = _loop(params, quantize="int8", prefix_cache=False)
    try:
        before = _gen(loop, [1, 2, 3], 5)
        bytes_before = loop.weight_bytes()
        loop.update_params(_lm_params(seed=11))
        after = _gen(loop, [1, 2, 3], 5)
        assert loop.weight_bytes() == bytes_before   # still int8-resident
        assert after != before                       # new weights serve
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# prefix cache
# ---------------------------------------------------------------------------

def test_prefix_hit_stream_identical_to_cold():
    params = _lm_params()
    shared = [1, 2, 3, 4]
    cold = _loop(params, prefix_cache=False)
    warm = _loop(params, prefix_cache=True)
    try:
        ref = [_gen(cold, shared + t, 5, temperature=0.7, seed=9)
               for t in ([5], [6, 7])]
        got = [_gen(warm, shared + t, 5, temperature=0.7, seed=9,
                    prefix_len=len(shared)) for t in ([5], [6, 7])]
        assert got == ref
        assert warm.health.prefix_prefills == 1      # first request fills
        assert warm.health.prefix_hits == 1          # second implants
    finally:
        cold.close()
        warm.close()


@pytest.mark.slow
def test_prefix_lru_evicts_at_capacity(monkeypatch):
    monkeypatch.setenv("MXTPU_SERVE_PREFIX_MAX", "1")
    params = _lm_params()
    loop = _loop(params, prefix_cache=True)
    try:
        a, b = [1, 2, 3], [4, 5, 6]
        _gen(loop, a + [7], 2, prefix_len=3)    # prefill A
        _gen(loop, a + [8], 2, prefix_len=3)    # hit A
        _gen(loop, b + [7], 2, prefix_len=3)    # prefill B, evict A
        _gen(loop, a + [9], 2, prefix_len=3)    # A again: re-prefill
        assert loop.health.prefix_prefills == 3
        assert loop.health.prefix_hits == 1
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------

def test_spec_decode_token_identical_perfect_draft():
    """draft == target: every proposal must verify, and the output is
    token-identical to target-only sampling under the same seeds."""
    params = _lm_params()
    plain = _loop(params, prefix_cache=False)
    spec = _loop(params, prefix_cache=False, spec_k=2,
                 draft_params=params,
                 draft_num_layers=_LM["num_layers"])
    try:
        prompts = [[1, 2, 3], [4, 5]]
        ref = [_gen(plain, p, 6, temperature=0.8, seed=10 + i)
               for i, p in enumerate(prompts)]
        got = [_gen(spec, p, 6, temperature=0.8, seed=10 + i)
               for i, p in enumerate(prompts)]
        assert got == ref
        h = spec.health
        assert h.spec_rounds > 0
        # drafted counts only proposals the target ruled on, so a perfect
        # draft earns exactly 100% acceptance
        assert h.spec_drafted > 0
        assert h.spec_accepted == h.spec_drafted, h.report()
    finally:
        plain.close()
        spec.close()


def test_spec_decode_token_identical_weak_draft():
    """A deliberately useless draft (different random weights) costs
    acceptance, never correctness: the emitted stream is still identical
    to target-only decoding — greedy AND sampled."""
    params = _lm_params()
    plain = _loop(params, prefix_cache=False)
    spec = _loop(params, prefix_cache=False, spec_k=2,
                 draft_params=_lm_params(seed=77, num_layers=1),
                 draft_num_layers=1)
    try:
        for kw in (dict(), dict(temperature=1.1, top_k=6, seed=5)):
            ref = _gen(plain, [2, 4, 6], 7, **kw)
            got = _gen(spec, [2, 4, 6], 7, **kw)
            assert got == ref, kw
    finally:
        plain.close()
        spec.close()


@pytest.mark.parametrize("leg", ["int8", "spec"])
def test_decode_program_set_audits_clean(leg):
    """Every leg of the production decode path keeps its whole program
    set lint-clean (tracecheck + the memory lints): the quantized loop's
    step, and the speculative loop's verify and draft bodies beside the
    prefix helpers. The default loop's set is held by
    tests/test_serving.py::test_decode_memory_report_cache_aliased."""
    params = _lm_params()
    if leg == "int8":
        loop = _loop(params, quantize="int8", prefix_cache=False)
        want = ("step[",)
    else:
        loop = _loop(params, prefix_cache=True, spec_k=2,
                     draft_params=_lm_params(seed=8, num_layers=1),
                     draft_num_layers=1)
        want = ("verify[", "draft[")
    try:
        names = sorted(loop.memory_report())
        for w in want:
            assert any(w in n for n in names), (w, names)
        assert [f.format() for f in loop.check(memory=True)] == []
    finally:
        loop.close()


def test_spec_k_without_draft_raises():
    with pytest.raises(MXNetError, match="draft_params"):
        _loop(spec_k=2)


# ---------------------------------------------------------------------------
# knob resolution
# ---------------------------------------------------------------------------

def test_decode_knobs_resolve_from_tuning_db(monkeypatch, tmp_path):
    """DB knobs apply when arg and env are silent; a DB spec_k without a
    draft model falls back with a warning (never breaks a deploy); env
    beats DB."""
    from mxnet_tpu.autotune import db as _adb
    params = _lm_params()
    tdb = _adb.TuningDB(str(tmp_path / "tune.json"))
    tdb.put("lm", "decode_tokens_per_sec", 0,
            {"spec_k": 2, "prefix_cache": 0}, 100.0, "tokens/sec",
            kind="decode", symbol_sig=_adb.param_signature(params))
    tdb.save()
    monkeypatch.setenv("MXTPU_AUTOTUNE_DB", str(tmp_path / "tune.json"))
    loop = _loop(params)
    try:
        assert loop.prefix_enabled is False          # db applied
        assert loop.spec_k == 0                      # no draft: warned off
    finally:
        loop.close()
    monkeypatch.setenv("MXTPU_SERVE_PREFIX_CACHE", "1")
    loop = _loop(params)
    try:
        assert loop.prefix_enabled is True           # env beats db
    finally:
        loop.close()


# ---------------------------------------------------------------------------
# fault sites
# ---------------------------------------------------------------------------

@pytest.mark.faults
def test_fault_sample_sheds_in_flight():
    loop = _loop(prefix_cache=False)
    try:
        faults.inject("serve.sample", nth=2, kind="raise")
        fut = loop.generate([1, 2, 3], 8, temperature=0.8, seed=3)
        with pytest.raises(serving.ServingClosedError):
            fut.result(timeout=60.0)
        assert loop.health.shed >= 1
        assert loop.dead is not None
    finally:
        faults.clear("serve.sample")
        loop.close()


@pytest.mark.faults
def test_fault_spec_verify_sheds_without_emitting_drafts():
    params = _lm_params()
    loop = _loop(params, prefix_cache=False, spec_k=2,
                 draft_params=params,
                 draft_num_layers=_LM["num_layers"])
    try:
        faults.inject("serve.spec_verify", nth=1, kind="raise")
        fut = loop.generate([1, 2, 3], 6)
        with pytest.raises(serving.ServingClosedError):
            fut.result(timeout=60.0)
        # the round died between draft and verify: nothing was committed
        assert loop.health.spec_accepted == 0
        assert loop.dead is not None
    finally:
        faults.clear("serve.spec_verify")
        loop.close()
