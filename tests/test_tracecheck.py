"""tracecheck — static analyzer for compiled step programs
(docs/static_analysis.md).

Pins the lint catalog with a SEEDED violation of every class — an injected
host callback inside a scan body, a shape-perturbed retrace, an un-donatable
donated argument, an f64 literal, a weak-typed input, an oversized
closure-captured constant — each detected with op path + source provenance.
The retrace explainer's negative controls check the cache-key differ names
the offending argument AND property (shape / dtype / weak-type / static
value). Plus: inline + programmatic suppressions, the TrainStep runtime
hooks (program registry, watcher, MXTPU_TRACECHECK=error), the
``assert_no_retrace`` helper, bitwise parity for the satellite dtype pins,
and the tier-1 CLI smoke over a zoo subset.
"""
import logging

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import engine, guard as guard_mod, metric as metric_mod
from mxnet_tpu import sym, tracecheck as tc
from mxnet_tpu.base import MXNetError
from mxnet_tpu.test_utils import assert_no_retrace
from mxnet_tpu.train_step import StepMetrics, TrainStep

# NOTE: only the end-to-end TrainStep tests carry the ``tracecheck``
# marker (transfer_guard("disallow") via conftest): the lint/differ unit
# tests SEED violations — building arrays from Python scalars is their job.


@pytest.fixture(autouse=True)
def _clean_slate():
    tc.clear_suppressions()
    tc.RETRACE_EVENTS.clear()
    tc.PROGRAMS.clear()
    guard_mod.TRAINING_HEALTH.reset()
    engine.set_tracecheck(None)
    yield
    tc.clear_suppressions()
    tc.RETRACE_EVENTS.clear()
    guard_mod.TRAINING_HEALTH.reset()
    engine.set_tracecheck(None)


def _mlp():
    data = sym.Variable("data")
    net = sym.FullyConnected(data=data, num_hidden=16, name="fc1")
    net = sym.Activation(data=net, act_type="tanh")
    net = sym.FullyConnected(data=net, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(data=net, name="softmax")


def _sds(shape, dtype=np.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


# ---------------------------------------------------------------------------
# seeded violations: one per lint class, op path + provenance asserted
# ---------------------------------------------------------------------------

def test_host_sync_lint_callback_in_scan_body():
    """An injected jax.debug.print inside the scan body — the single worst
    regression for the bulked dispatch (a host round-trip K times per
    dispatch) — is caught with an op path rooted in the scan."""
    def step_with_logging(x):
        def body(c, _):
            jax.debug.print("loss={}", c.sum())
            return c + 1.0, None
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out

    findings = tc.check_program(step_with_logging, (_sds((4,)),),
                                name="seeded-cb")
    hits = [f for f in findings if f.lint == "host-sync"]
    assert len(hits) == 1
    f = hits[0]
    assert f.op_path.startswith("scan/")
    assert "INSIDE the scan body" in f.message
    assert f.provenance and "test_tracecheck" in f.provenance
    assert not f.suppressed


def test_host_sync_lint_clean_program_silent():
    findings = tc.check_program(lambda x: x * 2.0, (_sds((4,)),),
                                name="clean")
    assert not [f for f in findings if f.lint == "host-sync"]


def test_donation_lint_undonatable_argument():
    """A donated argument the lowering copies anyway (its shape matches no
    output) is named by flat path."""
    def shrink(x):
        return x[::2]

    findings = tc.check_program(shrink, (_sds((8,)),), donate_argnums=(0,),
                                name="seeded-don")
    hits = [f for f in findings if f.lint == "donation"]
    assert len(hits) == 1
    assert "args[0]" in hits[0].message
    assert "NOT aliased" in hits[0].message


def test_donation_lint_honored_donation_silent():
    findings = tc.check_program(lambda x: x + 1.0, (_sds((8,)),),
                                donate_argnums=(0,), name="don-ok")
    assert not [f for f in findings if f.lint == "donation"]


def test_dtype_lint_f64_literal():
    """An f64 literal leaking into the step program (only reachable with
    x64 enabled — exactly the config drift the lint is for) is reported
    with the producing op and provenance."""
    from jax import enable_x64
    with enable_x64():
        def f64_math(x):
            return x * np.float64(2.0)

        findings = tc.check_program(f64_math, (_sds((4,)),),
                                    name="seeded-f64")
    hits = [f for f in findings if f.lint == "dtype-f64"]
    assert hits, "f64 promotion not detected"
    assert any("float64" in f.message for f in hits)
    assert any(f.provenance and "test_tracecheck" in f.provenance
               for f in hits)
    assert any(f.op_path for f in hits)


def test_dtype_lint_weak_typed_input():
    """A bare Python scalar reaching the trace is flagged as a weak-typed
    program input, by argument path."""
    findings = tc.check_program(lambda x, s: x * s, (_sds((4,)), 2.5),
                                name="seeded-weak")
    hits = [f for f in findings if f.lint == "dtype-weak"]
    assert len(hits) == 1
    assert "[0][1]" in hits[0].message
    assert "weak-typed" in hits[0].message


def test_const_capture_lint_oversized_closure():
    big = jnp.ones((1024, 300), jnp.float32)  # 1.2 MB

    def with_baked_const(x):
        return x + jnp.sum(big, axis=1)[:4]

    findings = tc.check_program(with_baked_const, (_sds((4,)),),
                                name="seeded-const", const_bytes=100_000)
    hits = [f for f in findings if f.lint == "const-capture"]
    assert len(hits) == 1
    assert "1228800 bytes" in hits[0].message
    assert "consts[0]" == hits[0].op_path
    # the finding names the CAPTURED CLOSURE VARIABLE and its dtype/shape,
    # and carries the provenance of the constant's first use
    assert "variable 'big'" in hits[0].message
    assert "float32[1024, 300]" in hits[0].message
    assert hits[0].provenance and "test_tracecheck" in hits[0].provenance
    # above the default 1 MiB threshold too; a higher explicit one passes
    assert not [f for f in tc.check_program(
        with_baked_const, (_sds((4,)),), name="seeded-const",
        const_bytes=2_000_000) if f.lint == "const-capture"]


# ---------------------------------------------------------------------------
# the retrace explainer (cache-key differ)
# ---------------------------------------------------------------------------

def test_explain_diff_names_argument_and_property():
    """Negative controls: for each cache-key-relevant property — shape,
    dtype, weak type, static value — the differ names the argument and the
    property that changed."""
    x32 = jnp.ones((4, 3), jnp.float32)

    base = tc.signature((x32, 5), {"mode": "fast"})
    # shape
    d = tc.explain_diff(base, tc.signature((jnp.ones((5, 3)), 5),
                                           {"mode": "fast"}))
    assert d == ["argument [0][0]: shape (4, 3) -> (5, 3)"]
    # dtype
    d = tc.explain_diff(base, tc.signature(
        (x32.astype(jnp.float16), 5), {"mode": "fast"}))
    assert d == ["argument [0][0]: dtype float32 -> float16"]
    # weak type (a weak scalar array where a strong one used to be)
    weak = jnp.asarray(2.0)          # weak f32
    strong = jnp.float32(2.0)        # strong f32
    if weak.weak_type and not strong.weak_type:
        d = tc.explain_diff(tc.signature((strong,)),
                            tc.signature((weak,)))
        assert d == ["argument [0][0]: weak_type False -> True"]
    # static value (a non-scalar static leaf is keyed by VALUE)
    d = tc.explain_diff(base, tc.signature((x32, 5), {"mode": "slow"}))
    assert d == ["argument [1]['mode']: static value 'fast' -> 'slow'"]
    # python scalar type flip (int 5 -> float 5.0 retraces; the VALUE of a
    # traced scalar never keys the cache, so only the type is compared)
    d = tc.explain_diff(base, tc.signature((x32, 5.0), {"mode": "fast"}))
    assert d == ["argument [0][1]: Python scalar type int -> float"]
    assert tc.explain_diff(base,
                           tc.signature((x32, 7), {"mode": "fast"})) == []
    # unchanged signature -> empty diff
    assert tc.explain_diff(base, tc.signature((x32, 5),
                                              {"mode": "fast"})) == []


def test_explain_diff_committedness_is_benign():
    """The first dispatch after seeding flips donated state leaves
    uncommitted -> committed; that re-keys only jit's C++ fast path, never
    the trace — the differ must stay silent and benign_diff must name it."""
    x = jnp.ones((4,), jnp.float32)
    committed = jax.device_put(x, jax.devices()[0])
    a, b = tc.signature((x,)), tc.signature((committed,))
    if a != b:  # committedness differs on this backend
        assert tc.explain_diff(a, b) == []
        assert any("committed" in ln for ln in tc.benign_diff(a, b))


def test_trace_watcher_detects_shape_perturbed_retrace(caplog):
    """A watched jit entry re-traced by a shape change logs the diff naming
    the argument + property and lands in RETRACE_EVENTS + health."""
    f = jax.jit(lambda x: x * 2.0)
    w = tc.TraceWatcher("toy")
    x1, x2 = jnp.ones((4, 3)), jnp.ones((5, 3))
    f(x1)
    assert w.after_call("k", f, tc.signature((x1,))) is None
    f(x2)  # same watch key, perturbed shape -> cache grows
    with caplog.at_level(logging.WARNING):
        ev = w.after_call("k", f, tc.signature((x2,)))
    assert ev is not None
    assert ev.site == "toy/k"
    assert ev.diff == ("argument [0][0]: shape (4, 3) -> (5, 3)",)
    assert any("unexpected retrace at toy/k" in r.message
               for r in caplog.records)
    assert tc.retrace_count() == 1
    assert guard_mod.TRAINING_HEALTH.report()["retraces"] == 1


def test_trace_watcher_error_mode_raises():
    engine.set_tracecheck("error")
    f = jax.jit(lambda x: x + 1.0)
    w = tc.TraceWatcher("toy")
    x1, x2 = jnp.ones((4,)), jnp.ones((4,), jnp.float16)
    f(x1)
    w.after_call("k", f, tc.signature((x1,)))
    f(x2)
    with pytest.raises(MXNetError, match=r"dtype float32 -> float16"):
        w.after_call("k", f, tc.signature((x2,)))


@pytest.mark.tracecheck
def test_train_step_runtime_hook_catches_dtype_retrace(caplog):
    """End to end through the wired hooks: a batch dtype flip on an
    already-compiled TrainStep program is an unexpected retrace — the log
    names the batch argument and the dtype change."""
    B = 8
    ts = TrainStep(_mlp(), optimizer="sgd", learning_rate=0.05)
    state = ts.init({"data": (B, 10)}, {"softmax_label": (B,)}, seed=0)
    rng = np.random.default_rng(3)
    X = rng.normal(size=(B, 10)).astype(np.float32)
    y = rng.integers(0, 4, (B,)).astype(np.float32)
    batch = {"data": jnp.asarray(X), "softmax_label": jnp.asarray(y)}
    state, _ = ts.step(state, batch)
    assert tc.retrace_count() == 0
    bad = dict(batch, data=jnp.asarray(X.astype(np.float16)))
    with caplog.at_level(logging.WARNING):
        state, _ = ts.step(state, bad)
    assert tc.retrace_count() == 1
    ev = tc.RETRACE_EVENTS[-1]
    assert "step[bs=%d]" % B in ev.site
    assert any("data" in ln and "float32 -> float16" in ln
               for ln in ev.diff)


@pytest.mark.tracecheck
def test_train_step_registers_programs_cleanly():
    """The wired jit caches (step + scan) land in the program registry and
    the registered set audits clean — the guard-on/guard-off program set
    as a unit."""
    B, K = 8, 2
    ts = TrainStep(_mlp(), optimizer="sgd", learning_rate=0.05)
    state = ts.init({"data": (B, 10)}, {"softmax_label": (B,)}, seed=0)
    rng = np.random.default_rng(5)
    Xs = rng.normal(size=(K, B, 10)).astype(np.float32)
    ys = rng.integers(0, 4, (K, B)).astype(np.float32)
    sb = {"data": jnp.asarray(Xs), "softmax_label": jnp.asarray(ys)}
    state, _ = ts.run_steps(state, dict(sb))
    state, _ = ts.run_steps(state, dict(sb), guard=True)
    names = [r.name for r in tc.registered_programs()]
    assert any("scan[bs=%d,k=%d]" % (B, K) in n for n in names)
    assert any("guard-scan[bs=%d,k=%d]" % (B, K) in n for n in names)
    findings = tc.check_registered(match="scan")
    assert tc.unsuppressed(findings) == []


def test_error_mode_retrace_carries_dispatch_result():
    """MXTPU_TRACECHECK=error raises AFTER the dispatch has donated the
    old state — the RetraceError must carry the new state so the caller
    (Module._adopt_retrace_result) never dangles on deleted buffers."""
    engine.set_tracecheck("error")
    B = 8
    ts = TrainStep(_mlp(), optimizer="sgd", learning_rate=0.05)
    state = ts.init({"data": (B, 10)}, {"softmax_label": (B,)}, seed=0)
    X = np.zeros((B, 10), np.float32)
    y = np.zeros((B,), np.float32)
    batch = {"data": jnp.asarray(X), "softmax_label": jnp.asarray(y)}
    state, _ = ts.step(state, batch)
    bad = dict(batch, data=jnp.asarray(X.astype(np.float16)))
    with pytest.raises(tc.RetraceError,
                       match="float32 -> float16") as ei:
        ts.step(state, bad)
    assert ei.value.result is not None
    new_state, outs = ei.value.result
    assert int(np.asarray(new_state["step"])) == 2  # the dispatch DID run


@pytest.mark.tracecheck
def test_two_train_steps_same_symbol_name_both_register():
    """Registry names are process-unique: a second TrainStep over a
    same-named symbol (the default 'softmax' head) must register its OWN
    programs, not be shadowed by the first instance's entries."""
    B = 8
    batch = {"data": jnp.asarray(np.zeros((B, 10), np.float32)),
             "softmax_label": jnp.asarray(np.zeros((B,), np.float32))}
    steps = []
    for seed in (0, 1):
        ts = TrainStep(_mlp(), optimizer="sgd", learning_rate=0.05)
        state = ts.init({"data": (B, 10)}, {"softmax_label": (B,)},
                        seed=seed)
        ts.step(state, dict(batch))
        steps.append(ts)
    assert steps[0]._watcher.name != steps[1]._watcher.name
    regs = [r for r in tc.registered_programs()
            if "step[bs=%d]" % B in r.name]
    assert len(regs) == 2
    assert {r.fn_ref() for r in regs} == \
        {steps[0]._jit[B], steps[1]._jit[B]}


def test_tracecheck_off_mode_skips_capture():
    engine.set_tracecheck("off")
    B = 8
    ts = TrainStep(_mlp(), optimizer="sgd", learning_rate=0.05)
    state = ts.init({"data": (B, 10)}, {"softmax_label": (B,)}, seed=0)
    batch = {"data": jnp.zeros((B, 10), jnp.float32),
             "softmax_label": jnp.zeros((B,), jnp.float32)}
    ts.step(state, batch)
    assert ts._watcher is None
    assert tc.PROGRAMS == {} or not any(
        "TrainStep" in n for n in tc.PROGRAMS)


def test_engine_mode_parsing(monkeypatch):
    for raw, want in [("", "warn"), ("warn", "warn"), ("1", "warn"),
                      ("error", "error"), ("raise", "error"),
                      ("off", "off"), ("0", "off")]:
        monkeypatch.setenv("MXTPU_TRACECHECK", raw)
        assert engine.tracecheck_mode() == want
    monkeypatch.setenv("MXTPU_TRACECHECK", "bogus")
    with pytest.raises(MXNetError, match="MXTPU_TRACECHECK"):
        engine.tracecheck_mode()
    monkeypatch.delenv("MXTPU_TRACECHECK")
    with pytest.raises(MXNetError, match="set_tracecheck"):
        engine.set_tracecheck("loud")


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_programmatic_suppression():
    tok = tc.add_suppression("dtype-weak", program="seeded")
    findings = tc.check_program(lambda x, s: x * s, (_sds((4,)), 2.5),
                                name="seeded-weak")
    hit = [f for f in findings if f.lint == "dtype-weak"][0]
    assert hit.suppressed
    tc.remove_suppression(tok)
    findings = tc.check_program(lambda x, s: x * s, (_sds((4,)), 2.5),
                                name="seeded-weak")
    assert not [f for f in findings if f.lint == "dtype-weak"][0].suppressed
    with pytest.raises(MXNetError, match="unknown lint"):
        tc.add_suppression("not-a-lint")


def test_inline_suppression_on_provenance_line():
    """`# tracecheck: ignore[host-sync]` on the source line a finding
    points at marks it suppressed (reported, but not gate-failing)."""
    def quiet(x):
        jax.debug.print("x={}", x.sum())  # tracecheck: ignore[host-sync]
        return x + 1.0

    findings = tc.check_program(quiet, (_sds((4,)),), name="inline-ok")
    hits = [f for f in findings if f.lint == "host-sync"]
    assert len(hits) == 1 and hits[0].suppressed
    assert tc.unsuppressed(findings) == []


def test_inline_suppression_wrong_lint_does_not_match():
    def noisy(x):
        jax.debug.print("x={}", x.sum())  # tracecheck: ignore[dtype-f64]
        return x + 1.0

    findings = tc.check_program(noisy, (_sds((4,)),), name="inline-no")
    hits = [f for f in findings if f.lint == "host-sync"]
    assert len(hits) == 1 and not hits[0].suppressed


# ---------------------------------------------------------------------------
# assert_no_retrace helper
# ---------------------------------------------------------------------------

def test_assert_no_retrace_passes_on_stable_cache():
    f = jax.jit(lambda x: x * 3.0)
    x = jnp.ones((4,))
    f(x)
    with assert_no_retrace(f):
        for _ in range(3):
            f(x)


def test_assert_no_retrace_fails_naming_growth():
    f = jax.jit(lambda x: x * 3.0)
    f(jnp.ones((4,)))
    with pytest.raises(AssertionError, match="re-traced"):
        with assert_no_retrace(f, msg="toy"):
            f(jnp.ones((5,)))  # new shape -> new trace


def test_assert_no_retrace_reports_watcher_events():
    """Events recorded by any runtime watcher inside the block fail the
    assertion with the differ's argument/property line."""
    f = jax.jit(lambda x: x + 1.0)
    w = tc.TraceWatcher("toy")
    x1, x2 = jnp.ones((4,)), jnp.ones((7,))
    f(x1)
    w.after_call("k", f, tc.signature((x1,)))
    with pytest.raises(AssertionError, match=r"shape \(4,\) -> \(7,\)"):
        with assert_no_retrace():
            f(x2)
            w.after_call("k", f, tc.signature((x2,)))


# ---------------------------------------------------------------------------
# satellite dtype pins: bitwise parity on the default (x64-off) config
# ---------------------------------------------------------------------------

def test_eps_pin_bitwise_parity():
    """`-log(p + jnp.float32(1e-8))` == `-log(p + 1e-8)` bitwise on the
    default config — the pin only matters under x64, where the unpinned
    form promotes."""
    p = jnp.asarray(np.random.default_rng(0).uniform(
        1e-6, 1.0, (64,)).astype(np.float32))
    a = np.asarray(jnp.sum(-jnp.log(p + 1e-8)))
    b = np.asarray(jnp.sum(-jnp.log(p + jnp.float32(1e-8))))
    assert a.tobytes() == b.tobytes()


def test_lr_vector_pin_bitwise_parity():
    lrs = [0.05, 0.049, 0.0485]
    a = np.asarray(jnp.asarray(lrs, jnp.float32))
    b = np.asarray(jnp.asarray(np.asarray(lrs, np.float32)))
    assert a.dtype == b.dtype == np.float32
    assert a.tobytes() == b.tobytes()
    assert not jnp.asarray(np.asarray(lrs, np.float32)).weak_type


def test_metric_fold_pins_accumulator_to_python_float():
    """update_from_device_sums keeps the host accumulator a Python
    float/int even when the sums object yields np.float32 scalars — under
    NEP 50 an np.float32 fold would demote sum_metric to f32 for the rest
    of the run (increments stop landing past 2**24)."""
    class _F32Sums(object):
        loss_sum = np.float32(2.5)
        top1_correct = np.float32(6.0)
        num_samples = np.float32(8.0)

    acc = metric_mod.Accuracy()
    metric_mod.update_from_device_sums(acc, _F32Sums())
    assert type(acc.sum_metric) is float and type(acc.num_inst) is int
    ce = metric_mod.CrossEntropy()
    metric_mod.update_from_device_sums(ce, _F32Sums())
    assert type(ce.sum_metric) is float
    assert ce.get()[1] == pytest.approx(2.5 / 8.0)
    # parity: the f64 fold equals the float32 values exactly at small counts
    assert acc.sum_metric == 6.0 and acc.num_inst == 8


def test_step_metrics_fold_parity():
    packed = jnp.asarray(np.asarray([2.5, 6.0, 8.0], np.float32))
    sums = StepMetrics(packed)
    acc = metric_mod.Accuracy()
    metric_mod.update_from_device_sums(acc, sums)
    assert acc.sum_metric == 6.0 and acc.num_inst == 8


def test_speedometer_surfaces_retrace_count():
    """`Retraces: N` appears in Speedometer lines once a watched jit entry
    re-traces during the run — and is baselined at the init fire, so an
    earlier run's misses never leak into this run's lines."""
    import logging as _logging
    from mxnet_tpu.callback import Speedometer
    from mxnet_tpu.module.base_module import BatchEndParam

    tc.RETRACE_EVENTS.append(tc.RetraceEvent("stale/run", ("old",)))
    sp = Speedometer(batch_size=16, frequent=10)
    fired = []
    orig = _logging.info
    _logging.info = lambda *a: fired.append(a)
    try:
        sp(BatchEndParam(epoch=0, nbatch=5, eval_metric=None, locals=None))
        tc.RETRACE_EVENTS.append(tc.RetraceEvent(
            "TrainStep(softmax)/scan[bs=8,k=2]",
            ("argument data: dtype float32 -> float16",)))
        sp(BatchEndParam(epoch=0, nbatch=15, eval_metric=None, locals=None))
    finally:
        _logging.info = orig
    joined = " ".join(str(x) for call in fired for x in call)
    assert "Retraces: 1" in joined

    # a REUSED Speedometer re-baselines: a miss between runs (score(), a
    # different Module) must not leak into run 2's lines — and a clean
    # window stays quiet (no "Retraces: 0" noise)
    tc.RETRACE_EVENTS.append(tc.RetraceEvent("between/runs", ("x",)))
    fired2 = []
    _logging.info = lambda *a: fired2.append(a)
    try:
        sp(BatchEndParam(epoch=0, nbatch=5, eval_metric=None, locals=None))
        sp(BatchEndParam(epoch=0, nbatch=15, eval_metric=None, locals=None))
    finally:
        _logging.info = orig
    assert "Retraces" not in " ".join(str(x) for call in fired2
                                      for x in call)


# ---------------------------------------------------------------------------
# zoo audit + CLI (tier-1 smoke)
# ---------------------------------------------------------------------------

def test_check_zoo_subset_clean():
    findings, nprog = tc.check_zoo(names=["mlp"], k=2)
    assert nprog == 4  # step / scan / guarded-step / guarded-scan
    assert tc.unsuppressed(findings) == []


def test_cli_smoke_exits_zero_on_zoo_subset(capsys):
    """The CI gate's tier-1 smoke: the CLI audits shipped models and exits
    0 (zero unsuppressed findings on the seed zoo)."""
    rc = tc.main(["--models", "mlp,lenet", "--quiet"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 finding(s)" in out or "finding(s)" in out


def test_cli_list_and_bad_model():
    assert tc.main(["--list"]) == 0
    with pytest.raises(MXNetError, match="unknown zoo model"):
        tc.main(["--models", "nope"])


def test_cli_json_output(capsys):
    """--json emits an object: the findings list plus the suppressed and
    program counts (machine-readable gate summary)."""
    import json
    rc = tc.main(["--models", "mlp", "--json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert isinstance(data["findings"], list)
    assert data["suppressed"] == 0
    assert data["total"] == len(data["findings"])
    assert data["programs"] == 4  # step / scan / guarded-step / guarded-scan


def test_cli_json_counts_suppressed_findings(capsys, monkeypatch):
    """A suppressed finding still reports and is COUNTED in the json
    summary's suppressed field; the unsuppressed one still fails the
    gate."""
    import json
    seeded = [
        tc.Finding("host-sync", "fake/step", "seeded-suppressed",
                   suppressed=True),
        tc.Finding("dtype-weak", "fake/step", "seeded-live"),
    ]
    monkeypatch.setattr(tc, "check_zoo", lambda **kw: (list(seeded), 4))
    rc = tc.main(["--models", "mlp", "--json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert data["total"] == 2
    assert data["suppressed"] == 1
    assert [f["suppressed"] for f in data["findings"]] == [True, False]


# ---------------------------------------------------------------------------
# collective-in-scan (docs/perf.md "Data-parallel scaling")
# ---------------------------------------------------------------------------

def _dp_mesh(n=8):
    import jax
    import numpy as np
    return jax.sharding.Mesh(np.array(jax.devices()[:n]), ("data",))


def test_collective_lint_flags_explicit_allgather_in_scan():
    """Jaxpr half: an explicit shard_map all_gather inside a scan body is
    a finding with the scan-rooted op path and the seeding line's
    provenance."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    P = jax.sharding.PartitionSpec
    mesh = _dp_mesh()

    def bad(xs):
        def body(c, x):
            g = jax.lax.all_gather(x, "data")
            return c + jnp.sum(g), None
        out, _ = jax.lax.scan(body, jnp.float32(0), xs)
        return out

    sm = shard_map(bad, mesh=mesh, in_specs=P(None, "data"), out_specs=P(),
                   check_vma=False)
    xs = jax.device_put(np.ones((4, 8), np.float32),
                        jax.sharding.NamedSharding(mesh, P(None, "data")))
    findings = [f for f in tc.check_program(jax.jit(sm), (xs,),
                                            name="seeded-allgather")
                if f.lint == "collective-in-scan"]
    assert findings, "all_gather in scan body must be flagged"
    assert "scan" in findings[0].op_path
    assert findings[0].provenance and "test_tracecheck" in \
        findings[0].provenance


def test_collective_lint_allows_psum_in_scan():
    """psum IS the expected grad/metric sync — a psum-only shard_map scan
    stays clean on both the jaxpr pass and the compiled-HLO audit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    P = jax.sharding.PartitionSpec
    mesh = _dp_mesh()

    def good(xs):
        def body(c, x):
            return c + jax.lax.psum(jnp.sum(x), "data"), None
        out, _ = jax.lax.scan(body, jnp.float32(0), xs)
        return out

    sm = shard_map(good, mesh=mesh, in_specs=P(None, "data"), out_specs=P(),
                   check_vma=False)
    xs = jax.device_put(np.ones((4, 8), np.float32),
                        jax.sharding.NamedSharding(mesh, P(None, "data")))
    assert [f for f in tc.check_program(jax.jit(sm), (xs,), name="psum-scan")
            if f.lint == "collective-in-scan"] == []
    assert tc.check_collectives(jax.jit(sm), (xs,), name="psum-scan") == []


def test_collective_lint_suppressible():
    tok = tc.add_suppression("collective-in-scan", program="seeded")
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax import shard_map
        P = jax.sharding.PartitionSpec
        mesh = _dp_mesh()

        def bad(xs):
            def body(c, x):
                return c + jnp.sum(jax.lax.all_gather(x, "data")), None
            return jax.lax.scan(body, jnp.float32(0), xs)[0]

        sm = shard_map(bad, mesh=mesh, in_specs=P(None, "data"),
                       out_specs=P(), check_vma=False)
        xs = jax.device_put(np.ones((4, 8), np.float32),
                            jax.sharding.NamedSharding(mesh, P(None, "data")))
        fs = [f for f in tc.check_program(jax.jit(sm), (xs,),
                                          name="seeded-suppressed")
              if f.lint == "collective-in-scan"]
        assert fs and all(f.suppressed for f in fs)
    finally:
        tc.remove_suppression(tok)
