"""mxnet_tpu.autotune (docs/perf.md "Autotuning").

Pins the contract: deterministic bounded search with crash/timeout
isolation, the memcheck pruner rejecting over-budget candidates WITHOUT
executing them, the tuning-DB schema/platform fallback rules, and the
knob-resolution precedence **explicit arg > env > tuning DB > built-in
default** across ``Module.fit`` and ``ServingEngine``.
"""
import json
import logging
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autotune, models
from mxnet_tpu.autotune.db import SCHEMA_VERSION, TuningDB
from mxnet_tpu.autotune.harness import TrainHarness
from mxnet_tpu.autotune.search import NEG_INF, Knob, SearchDriver
from mxnet_tpu.tracecheck import ZOO


@pytest.fixture(autouse=True)
def _isolated_db(tmp_path, monkeypatch):
    """Every test runs against its own tuning DB: the committed repo DB
    must never leak knobs into unrelated tests, and tests must never
    write the committed file."""
    monkeypatch.setenv("MXTPU_AUTOTUNE_DB", str(tmp_path / "tune_db.json"))
    yield


def _zoo_mlp():
    return models.get_symbol("mlp", **ZOO["mlp"]["kwargs"])


def _write_train_entry(path, sym, batch, knobs, model="mlp",
                       objective="img_per_sec", schema=SCHEMA_VERSION,
                       device_kind=None):
    from mxnet_tpu.autotune.db import _device_kind
    entry = {
        "model": model, "objective": objective, "kind": "train",
        "global_batch": int(batch),
        "device_kind": device_kind or _device_kind(),
        "platform": "cpu", "symbol": sym.name,
        "symbol_sig": autotune.symbol_signature(sym),
        "knobs": dict(knobs), "score": 1.0, "unit": "images/sec",
    }
    key = "%s|%s|b%d|%s" % (model, entry["device_kind"], batch, objective)
    with open(path, "w") as f:
        json.dump({"schema": schema, "entries": {key: entry}}, f)
    return key


# -- search driver ----------------------------------------------------------

def test_grid_is_exhaustive_and_deterministic():
    seen = []

    def ev(kn):
        seen.append((kn["a"], kn["b"]))
        return kn["a"] * 10 + kn["b"]

    d = SearchDriver([Knob("a", (1, 2)), Knob("b", (0, 1, 2))], ev,
                     budget=10)
    best, trials = d.run()
    # itertools.product order in declared knob order; trial #0 = defaults
    assert seen == [(1, 0), (1, 1), (1, 2), (2, 0), (2, 1), (2, 2)]
    assert d.default_trial.knobs == {"a": 1, "b": 0}
    assert best.knobs == {"a": 2, "b": 2}
    # same space, same budget -> identical trial sequence
    seen2 = []
    d2 = SearchDriver([Knob("a", (1, 2)), Knob("b", (0, 1, 2))],
                      lambda kn: seen2.append((kn["a"], kn["b"])) or 0.0,
                      budget=10)
    d2.run()
    assert seen2 == seen


def test_hill_climb_bounded_and_greedy():
    calls = []

    def ev(kn):
        calls.append(dict(kn))
        return kn["a"] + kn["b"] + kn["c"]

    space = [Knob("a", (0, 1, 2)), Knob("b", (0, 1, 2)),
             Knob("c", (0, 1, 2))]  # 27 candidates > budget
    d = SearchDriver(space, ev, budget=7)
    best, trials = d.run()
    assert len(trials) == 7
    assert trials[0].knobs == {"a": 0, "b": 0, "c": 0}
    # greedy: after sweeping knob a it holds the best (a=2) while
    # sweeping b
    assert best.score == max(t.score for t in trials if t.ok)
    assert best.knobs["a"] == 2


def test_crashing_candidate_scores_neg_inf_and_sweep_survives():
    def ev(kn):
        if kn["a"] == 2:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
        return float(kn["a"])

    d = SearchDriver([Knob("a", (1, 2, 3))], ev, budget=5)
    best, trials = d.run()
    assert [t.status for t in trials] == ["ok", "error", "ok"]
    assert trials[1].score == NEG_INF
    assert "RESOURCE_EXHAUSTED" in trials[1].detail
    assert best.knobs == {"a": 3}


def test_wedged_candidate_times_out_and_stops_sweep():
    """A timed-out trial's abandoned thread may still hold the shared
    harness: the sweep must stop there (later measurements would be
    contaminated by the zombie's contention) and report only the clean
    prefix."""
    def ev(kn):
        if kn["a"] == 2:
            time.sleep(30)
        return float(kn["a"])

    d = SearchDriver([Knob("a", (1, 2, 3))], ev, budget=5,
                     trial_timeout=0.2)
    best, trials = d.run()
    assert [t.status for t in trials] == ["ok", "timeout"]
    assert trials[1].score == NEG_INF
    assert d.timed_out
    assert best.knobs == {"a": 1}  # a=3 was never (mis)measured


def test_pruned_candidate_never_executes():
    executed = []

    def ev(kn):
        executed.append(kn["a"])
        return float(kn["a"])

    def prune(kn):
        if kn["a"] == 2:
            return ["peak HBM over budget"]
        return []

    d = SearchDriver([Knob("a", (1, 2, 3))], ev, prune=prune,
                     program_knobs=("a",), budget=5)
    best, trials = d.run()
    assert [t.status for t in trials] == ["ok", "pruned", "ok"]
    assert executed == [1, 3]  # the pruned candidate never ran
    assert best.knobs == {"a": 3}


# -- static pruner over a real program set ----------------------------------

def test_memcheck_pruner_rejects_over_budget_scan(monkeypatch):
    """A tiny MXTPU_AUTOTUNE_BUDGET makes the mlp scan over-budget: the
    pruner reports hbm-budget findings from ONE compile, and a driver
    using it records the candidate as pruned without evaluating."""
    h = TrainHarness(model="mlp", batch=8)
    assert h.prune({"steps_per_dispatch": 2}) == []  # sane budget: admits
    monkeypatch.setenv("MXTPU_AUTOTUNE_BUDGET", "4K")
    findings = h.prune({"steps_per_dispatch": 2})
    assert findings and all(f.lint in ("hbm-budget", "resident-set")
                            for f in findings)


# -- tuning DB --------------------------------------------------------------

def test_db_roundtrip_atomic_and_lookup(tmp_path):
    sym = _zoo_mlp()
    path = str(tmp_path / "db.json")
    db = TuningDB(path)
    db.put("mlp", "img_per_sec", 16, {"steps_per_dispatch": 2}, 123.0,
           "images/sec", symbol=sym.name,
           symbol_sig=autotune.symbol_signature(sym))
    db.save()
    db2 = TuningDB.load(path)
    assert not db2.stale
    key, entry, note = db2.lookup(
        "train", symbol_sig=autotune.symbol_signature(sym),
        global_batch=16)
    assert entry is not None and note is None
    assert entry["knobs"] == {"steps_per_dispatch": 2}
    # batch mismatch: no entry
    _, miss, _ = db2.lookup(
        "train", symbol_sig=autotune.symbol_signature(sym),
        global_batch=32)
    assert miss is None


def test_db_schema_mismatch_is_stale_with_warning(tmp_path, caplog):
    sym = _zoo_mlp()
    path = str(tmp_path / "db.json")
    _write_train_entry(path, sym, 16, {"steps_per_dispatch": 2},
                       schema=SCHEMA_VERSION + 99)
    with caplog.at_level(logging.WARNING):
        db = TuningDB.load(path)
    assert db.stale
    assert any("schema" in r.message for r in caplog.records)
    _, entry, _ = db.lookup("train",
                            symbol_sig=autotune.symbol_signature(sym),
                            global_batch=16)
    assert entry is None


def test_db_device_kind_mismatch_is_note_not_error(tmp_path):
    sym = _zoo_mlp()
    path = str(tmp_path / "db.json")
    _write_train_entry(path, sym, 16, {"steps_per_dispatch": 2},
                       device_kind="TPU v5e")
    db = TuningDB.load(path)
    key, entry, note = db.lookup(
        "train", symbol_sig=autotune.symbol_signature(sym),
        global_batch=16)
    assert entry is None
    assert note is not None and "TPU v5e" in note


def test_db_foreign_sibling_entry_does_not_note_when_match_found(tmp_path):
    """A multi-device DB (the intended layout) holds one entry per device
    kind: scanning past a foreign-device sibling must NOT report a
    mismatch when a same-device entry is then found and applied."""
    from mxnet_tpu.autotune.db import _device_kind
    sym = _zoo_mlp()
    path = str(tmp_path / "db.json")
    db = TuningDB(path)
    for dk, k in (("TPU v5e", 8), (_device_kind(), 2)):
        sig = autotune.symbol_signature(sym)
        db.entries["mlp|%s|b16|img_per_sec" % dk] = {
            "model": "mlp", "objective": "img_per_sec", "kind": "train",
            "global_batch": 16, "device_kind": dk, "platform": "cpu",
            "symbol": sym.name, "symbol_sig": sig,
            "knobs": {"steps_per_dispatch": k}, "score": 1.0,
            "unit": "images/sec"}
    key, entry, note = db.lookup(
        "train", symbol_sig=autotune.symbol_signature(sym),
        global_batch=16)
    assert entry is not None and note is None
    assert entry["knobs"]["steps_per_dispatch"] == 2


def test_mismatch_note_survives_objective_preference_loop(tmp_path,
                                                          monkeypatch):
    """A device-kind mismatch found under the FIRST preferred objective
    must still be reported when later objectives simply have no entries
    (the note accumulates across the preference loop)."""
    from mxnet_tpu.obs import REGISTRY
    sym = _zoo_mlp()
    path = str(tmp_path / "tune_db.json")
    monkeypatch.setenv("MXTPU_AUTOTUNE_DB", path)
    _write_train_entry(path, sym, 16, {"steps_per_dispatch": 8},
                       device_kind="TPU v5e")
    before = REGISTRY.snapshot().get("autotune.db_mismatches", 0)
    key, knobs = autotune.resolve_train_knobs(sym, 16)
    assert knobs is None
    assert REGISTRY.snapshot()["autotune.db_mismatches"] == before + 1


def test_img_per_sec_score_not_inflated_by_label_tokens():
    """An img_per_sec sweep over a multi-dim-label model must report
    samples/sec, not samples*tokens/sec — one unit, one meaning; the token
    multiplier is the tokens_per_sec objective's alone."""
    h_img = TrainHarness(model="transformer", batch=4,
                         objective="img_per_sec")
    h_tok = TrainHarness(model="transformer", batch=4,
                         objective="tokens_per_sec")
    assert h_tok.tokens_per_sample == 16  # ZOO transformer seq_len
    # monkey-free check: evaluate() on the same knobs — tokens objective
    # reports ~seq_len x the img objective's rate (same measurement)
    import mxnet_tpu.autotune.harness as _h
    calls = {}

    def fake_measure(step, state, sb, batch, k, depth, ns, nl, rounds=2,
                     warmup=2):
        calls["hit"] = calls.get("hit", 0) + 1
        return 100.0

    real = _h.measure_pipelined_ips
    _h.measure_pipelined_ips = fake_measure
    try:
        s_img = h_img.evaluate({"steps_per_dispatch": 1,
                                "dispatch_pipeline": 0})
        s_tok = h_tok.evaluate({"steps_per_dispatch": 1,
                                "dispatch_pipeline": 0})
    finally:
        _h.measure_pipelined_ips = real
    assert s_img == 100.0
    assert s_tok == 1600.0


def test_train_resolution_prefers_img_per_sec_objective(tmp_path,
                                                        monkeypatch):
    """Two training objectives tuned for one symbol/batch/device: the
    documented preference order (img_per_sec first) picks the entry,
    never key-sort accident."""
    from mxnet_tpu.autotune.db import _device_kind
    sym = _zoo_mlp()
    path = str(tmp_path / "tune_db.json")
    monkeypatch.setenv("MXTPU_AUTOTUNE_DB", path)
    sig = autotune.symbol_signature(sym)
    entries = {}
    # 'a_weird_objective'-style sort traps: img_per_sec sorts AFTER
    # "aaa" and BEFORE "tokens"; insert both real objectives
    for objective, k in (("tokens_per_sec", 8), ("img_per_sec", 2)):
        entries["mlp|%s|b16|%s" % (_device_kind(), objective)] = {
            "model": "mlp", "objective": objective, "kind": "train",
            "global_batch": 16, "device_kind": _device_kind(),
            "platform": "cpu", "symbol": sym.name, "symbol_sig": sig,
            "knobs": {"steps_per_dispatch": k}, "score": 1.0,
            "unit": "x"}
    with open(path, "w") as f:
        json.dump({"schema": SCHEMA_VERSION, "entries": entries}, f)
    key, knobs = autotune.resolve_train_knobs(sym, 16)
    assert knobs["steps_per_dispatch"] == 2
    assert "img_per_sec" in key


def test_corrupt_db_bucket_spec_falls_back_at_serving_load(tmp_path,
                                                           monkeypatch,
                                                           caplog):
    """A hand-edited/corrupt knob value in the DB must never break the
    deploy it configures: the engine warns and uses built-in buckets."""
    from mxnet_tpu import serving
    path = str(tmp_path / "tune_db.json")
    monkeypatch.setenv("MXTPU_AUTOTUNE_DB", path)
    sym, params, shape = _serve_entry(
        path, {"buckets": "0,garbage", "max_latency_ms": "wat"})
    with caplog.at_level(logging.WARNING):
        eng = serving.ServingEngine(sym, params, {"data": shape},
                                    buckets=None)
    assert eng.buckets == (1, 8, 32)  # built-in default
    assert eng._autotuned is None
    assert any("unusable" in r.message for r in caplog.records)


def test_symbol_signature_stable_across_rebuilds_and_discriminating():
    s1 = _zoo_mlp()
    s2 = _zoo_mlp()  # same process, fresh auto-name counters
    assert autotune.symbol_signature(s1) == autotune.symbol_signature(s2)
    other = models.get_symbol("mlp", num_classes=7, hidden=(32,))
    assert autotune.symbol_signature(s1) != autotune.symbol_signature(other)


# -- knob-resolution precedence across Module.fit ---------------------------

def _fit_data(batch=16, n=64):
    rng = np.random.default_rng(0)
    X = rng.normal(size=(n, 64)).astype(np.float32)
    y = rng.integers(0, 4, n).astype(np.float32)
    return mx.io.NDArrayIter(X, y, batch_size=batch)


def _bound_module(sym, batch=16):
    it = _fit_data(batch)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    return mod, it


def test_fit_resolution_precedence(tmp_path, monkeypatch):
    """explicit arg > env > tuning DB > built-in default, per knob."""
    sym = _zoo_mlp()
    path = str(tmp_path / "tune_db.json")
    monkeypatch.setenv("MXTPU_AUTOTUNE_DB", path)
    _write_train_entry(path, sym, 16, {"steps_per_dispatch": 2,
                                       "dispatch_pipeline": 0})
    mod, it = _bound_module(sym)
    # DB wins when nothing else is set
    k, depth, src = autotune.resolve_fit_knobs(mod, it, None, None)
    assert (k, depth) == (2, 0)
    assert src == {"steps_per_dispatch": "db", "dispatch_pipeline": "db"}
    # explicit args beat the DB
    k, depth, src = autotune.resolve_fit_knobs(mod, it, 4, 2)
    assert (k, depth) == (4, 2)
    assert src == {"steps_per_dispatch": "arg", "dispatch_pipeline": "arg"}
    # env beats the DB (pipeline via env var; K via an engine bulk scope)
    monkeypatch.setenv("MXTPU_DISPATCH_PIPELINE", "3")
    with mx.engine.bulk(8):
        k, depth, src = autotune.resolve_fit_knobs(mod, it, None, None)
    assert (k, depth) == (8, 3)
    assert src == {"steps_per_dispatch": "env", "dispatch_pipeline": "env"}
    monkeypatch.delenv("MXTPU_DISPATCH_PIPELINE")
    # an EXPLICIT bulk(1) means "the operator asked for 1" — the DB must
    # not re-enable bulking over it
    with mx.engine.bulk(1):
        k, depth, src = autotune.resolve_fit_knobs(mod, it, None, None)
    assert k == 1 and src["steps_per_dispatch"] == "env"
    # ...and the scope's exit restores "unset": DB resolution is back
    k, _, src = autotune.resolve_fit_knobs(mod, it, None, None)
    assert k == 2 and src["steps_per_dispatch"] == "db"
    # MXTPU_AUTOTUNE=0 disarms the DB: built-in defaults
    monkeypatch.setenv("MXTPU_AUTOTUNE", "0")
    k, depth, src = autotune.resolve_fit_knobs(mod, it, None, None)
    assert (k, depth) == (1, 1)
    assert src == {"steps_per_dispatch": "default",
                   "dispatch_pipeline": "default"}


def test_fit_resolves_db_knobs_end_to_end(tmp_path, monkeypatch, caplog):
    """A fresh Module.fit with NO knob args trains at the DB's K (the
    compiled scan cache keys on it) and logs the resolution once via the
    obs registry."""
    from mxnet_tpu.obs import REGISTRY
    sym = _zoo_mlp()
    path = str(tmp_path / "tune_db.json")
    monkeypatch.setenv("MXTPU_AUTOTUNE_DB", path)
    _write_train_entry(path, sym, 16, {"steps_per_dispatch": 2,
                                       "dispatch_pipeline": 1})
    before = REGISTRY.snapshot().get("autotune.db_resolutions", 0)
    it = _fit_data()
    mod = mx.mod.Module(sym, context=mx.cpu())
    with caplog.at_level(logging.INFO):
        mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1})
    assert mod._fused is not None
    assert any(key[1] == 2 for key in mod._fused._jit_scan)
    assert REGISTRY.snapshot()["autotune.db_resolutions"] == before + 1
    assert any("tuning DB" in r.message for r in caplog.records)


def test_fit_stale_db_warns_and_uses_defaults(tmp_path, monkeypatch,
                                              caplog):
    sym = _zoo_mlp()
    path = str(tmp_path / "tune_db.json")
    monkeypatch.setenv("MXTPU_AUTOTUNE_DB", path)
    _write_train_entry(path, sym, 16, {"steps_per_dispatch": 2},
                       schema=SCHEMA_VERSION + 1)
    mod, it = _bound_module(sym)
    with caplog.at_level(logging.WARNING):
        k, depth, src = autotune.resolve_fit_knobs(mod, it, None, None)
    assert (k, depth) == (1, 1)
    assert src["steps_per_dispatch"] == "default"
    assert any("schema" in r.message for r in caplog.records)


# -- knob-resolution precedence across ServingEngine ------------------------

def _serve_entry(path, knobs):
    from mxnet_tpu.autotune.db import _device_kind
    from mxnet_tpu.autotune.harness import serve_model
    from mxnet_tpu.predictor import _strip_loss_heads
    name, sym, params, shape = serve_model("mlp")
    sig = autotune.symbol_signature(_strip_loss_heads(sym))
    key = "mlp|%s|b0|serve_p99" % _device_kind()
    entry = {"model": "mlp", "objective": "serve_p99", "kind": "serve",
             "global_batch": 0, "device_kind": _device_kind(),
             "platform": "cpu", "symbol": sym.name, "symbol_sig": sig,
             "knobs": dict(knobs), "score": -5.0, "unit": "ms_p99_neg"}
    with open(path, "w") as f:
        json.dump({"schema": SCHEMA_VERSION, "entries": {key: entry}}, f)
    return sym, params, shape


def test_serving_engine_bucket_precedence(tmp_path, monkeypatch):
    from mxnet_tpu import serving
    path = str(tmp_path / "tune_db.json")
    monkeypatch.setenv("MXTPU_AUTOTUNE_DB", path)
    sym, params, shape = _serve_entry(
        path, {"buckets": "1,4", "max_latency_ms": 2.0})
    # DB wins when neither ctor arg nor env is set — and the Batcher
    # resolves its own knobs from the engine's stashed entry
    eng = serving.ServingEngine(sym, params, {"data": shape})
    assert eng.buckets == (1, 4)
    assert eng._autotuned["max_latency_ms"] == 2.0
    b = serving.Batcher(eng, start=False)
    assert abs(b.max_latency - 0.002) < 1e-12
    # env beats the DB
    monkeypatch.setenv("MXTPU_SERVE_BUCKETS", "1,2")
    eng_env = serving.ServingEngine(sym, params, {"data": shape})
    assert eng_env.buckets == (1, 2)
    assert eng_env._autotuned is None
    monkeypatch.delenv("MXTPU_SERVE_BUCKETS")
    # explicit ctor arg beats everything
    eng_arg = serving.ServingEngine(sym, params, {"data": shape},
                                    buckets=(1, 3))
    assert eng_arg.buckets == (1, 3)
    assert eng_arg._autotuned is None


# -- end-to-end sweep (tiny) ------------------------------------------------

def test_tune_writes_db_and_winner_beats_nothing(tmp_path, monkeypatch):
    """A 2-trial sweep over mlp: the default config is trial #0, the
    winner's measured score >= the default's (it IS the max), the DB
    entry round-trips, and resolution finds it."""
    monkeypatch.setenv("MXTPU_AUTOTUNE_MEASURE", "2,5")
    path = str(tmp_path / "tune_db.json")
    res = autotune.tune(
        model="mlp", objective="img_per_sec", budget=2, batch=8,
        db_path=path, write_db=True, rounds=1,
        space=[autotune.Knob("steps_per_dispatch", (1, 2)),
               autotune.Knob("dispatch_pipeline", (1,))])
    assert res["best"] is not None
    assert res["default"]["knobs"]["steps_per_dispatch"] == 1
    assert res["best"]["score"] >= res["default"]["score"]
    db = TuningDB.load(path)
    key, entry, _ = db.lookup("train", symbol_sig=res["symbol_sig"],
                              global_batch=8)
    assert entry is not None
    assert entry["knobs"] == res["best"]["knobs"]
    assert entry["unit"] == "images/sec"
