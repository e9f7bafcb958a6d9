"""memcheck tests (docs/static_analysis.md "Memory lints"): the static
HBM analyzer over compiled step programs.

The load-bearing assertions:

* a TrainStep's full program set reports peak/argument/temp/alias bytes
  with the donated state's alias savings realized (alias > 0, no waste);
* one SEEDED violation per memory lint class — ``hbm-budget``,
  ``donation-waste``, ``temp-blowup``, ``resident-set`` — is caught with
  the op path (and source provenance where the HLO carries it) asserted;
* the baseline regression gate fails on an injected temp-bytes
  regression and passes on the honest baseline (the ci/memcheck.sh
  contract);
* the CLI smoke (mlp + lenet, json mode) exits 0 with zero findings —
  the tier-1 mirror of the full-zoo CI gate.
"""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu import memcheck as mc  # noqa: E402
from mxnet_tpu import tracecheck as tc  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402


def _sds(shape, dtype=np.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


@pytest.fixture(scope="module")
def mlp_audit():
    """One compile of the mlp program set shared by the report/baseline
    tests (4 programs — the expensive part of this suite)."""
    from mxnet_tpu import models
    from mxnet_tpu.train_step import TrainStep
    cfg = tc.ZOO["mlp"]
    sym = models.get_symbol("mlp", **cfg["kwargs"])
    ts = TrainStep(sym, optimizer="sgd", learning_rate=0.1)
    return mc.check_train_step(ts, {"data": cfg["data"]},
                               {"softmax_label": cfg["label"]}, k=2,
                               name="mlp")


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------

def test_train_step_reports_all_programs(mlp_audit):
    findings, reports = mlp_audit
    assert sorted(reports) == ["mlp/guarded-scan[k=2]", "mlp/guarded-step",
                               "mlp/scan[k=2]", "mlp/step"]
    for rep in reports.values():
        assert rep.peak_bytes > 0
        assert rep.argument_bytes > 0
        assert rep.output_bytes > 0
        assert rep.temp_bytes > 0
        # the donated state aliased: donation is realized as savings
        assert rep.alias_bytes > 0
        assert rep.donated_bytes >= rep.alias_bytes // 2
        assert rep.top_buffers and rep.top_buffers[0]["bytes"] > 0
    # the default budget audits the zoo clean (the acceptance bar)
    assert [f.format() for f in findings] == []


def test_report_peak_formula_and_dict(mlp_audit):
    _, reports = mlp_audit
    rep = reports["mlp/step"]
    assert rep.peak_bytes == (rep.argument_bytes + rep.output_bytes
                              + rep.temp_bytes - rep.alias_bytes)
    d = rep.as_dict()
    assert d["peak_bytes"] == rep.peak_bytes
    assert d["program"] == "mlp/step"
    assert isinstance(d["top_buffers"], list)
    assert "MemoryReport" in repr(rep)


def test_hlo_buffer_parse_shapes():
    """The HLO shape parser handles every dtype width the step programs
    use (and sub-byte types), and skips view ops."""
    txt = """HloModule t, is_scheduled=true, input_output_alias={ {0}: (1, {}, may-alias) }, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

FileNames
1 "x.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=7 end_line=7 column=1 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}


%fused_computation (p: f32[8,8]) -> f32[8,8] {
  %inner.1 = f32[8,8]{1,0} multiply(f32[8,8]{1,0} %p, f32[8,8]{1,0} %p)
}

ENTRY %main.1 (Arg_0.1: f32[4]) -> f32[4] {
  %Arg_0.1 = f32[4]{0} parameter(0), metadata={op_name="state[\\'p\\']"}
  %Arg_1.2 = bf16[2,3]{1,0} parameter(1), metadata={op_name="batch"}
  %big.1 = f32[128,2]{1,0} broadcast(f32[4]{0} %Arg_0.1), metadata={op_name="jit(f)/bcast" stack_frame_id=1}
  %gte.1 = f32[4]{0} get-tuple-element(%big.1), index=0
  %pred.1 = pred[16]{0} compare(f32[4]{0} %Arg_0.1, f32[4]{0} %Arg_0.1)
}
"""
    buffers, params, aliased = mc.parse_hlo_buffers(txt)
    assert aliased == {1}
    assert params[0] == ("state['p']", 16)
    assert params[1] == ("batch", 12)  # bf16 2x3 = 12 bytes
    by_instr = {b["instruction"]: b for b in buffers}
    assert "inner.1" not in by_instr        # fusion internals skipped
    assert "gte.1" not in by_instr          # views skipped
    assert by_instr["big.1"]["bytes"] == 128 * 2 * 4
    assert by_instr["big.1"]["op_path"] == "jit(f)/bcast"
    assert by_instr["big.1"]["provenance"] == "x.py:7"
    assert by_instr["pred.1"]["bytes"] == 16
    assert buffers[0]["instruction"] == "big.1"  # sorted largest first


# ---------------------------------------------------------------------------
# seeded violations — one per lint class, op path + provenance asserted
# ---------------------------------------------------------------------------

def _hog(x):
    # dot operands must materialize: outer(x, x) lands a 4 MiB temp (and
    # the dot result another) against 4 KiB of arguments — the blowup
    # shape of a rematerialization/fusion regression
    big = jnp.outer(x, x)
    return jnp.sum(big @ big)


def test_hbm_budget_finding_seeded():
    findings, rep = mc.check_program(_hog, (_sds((1024,)),), name="seeded-hog",
                                     budget=64 << 10)
    hits = [f for f in findings if f.lint == "hbm-budget"]
    assert len(hits) == 1
    assert "peak HBM" in hits[0].message
    assert "Largest buffers" in hits[0].message
    # attributed to the blowup op with source provenance
    assert hits[0].op_path and "jit(_hog)" in hits[0].op_path
    assert hits[0].provenance and "test_memcheck" in hits[0].provenance


def test_temp_blowup_finding_seeded():
    findings, rep = mc.check_program(_hog, (_sds((1024,)),), name="seeded-hog",
                                     temp_mult=2.0)
    hits = [f for f in findings if f.lint == "temp-blowup"]
    assert len(hits) == 1
    assert "MXTPU_MEMCHECK_TEMP_MULT" in hits[0].message
    assert hits[0].op_path and "jit(_hog)" in hits[0].op_path
    assert hits[0].provenance and "test_memcheck" in hits[0].provenance
    assert rep.temp_bytes > 2 * (rep.argument_bytes + rep.output_bytes)


def test_donation_waste_finding_seeded():
    """A donated buffer whose bytes cannot alias any output (shape
    changes) is pure waste: the finding names the argument by path and
    accounts the unrealized bytes."""
    def f(x):
        return x[::2] * jnp.float32(2.0)

    findings, rep = mc.check_program(f, (_sds((1024,)),),
                                     donate_argnums=(0,),
                                     name="seeded-waste")
    hits = [f_ for f_ in findings if f_.lint == "donation-waste"]
    assert len(hits) == 1
    assert hits[0].op_path == "x"       # HLO labels the entry param
    assert "4.00 KiB" in hits[0].message
    assert rep.wasted_donation_bytes == 4096
    assert rep.unaliased_donated == [("x", 4096)]


def test_donation_waste_quiet_when_alias_realized():
    def f(x):
        return x * jnp.float32(2.0)

    findings, rep = mc.check_program(f, (_sds((1024,)),),
                                     donate_argnums=(0,),
                                     name="clean-donation")
    assert [f_ for f_ in findings if f_.lint == "donation-waste"] == []
    assert rep.alias_bytes == 4096
    assert rep.unaliased_donated == []


def test_resident_set_finding_seeded(mlp_audit):
    _, reports = mlp_audit
    findings = mc.lint_resident_set(reports.values(), "mlp/resident-set",
                                    budget=1024)
    assert len(findings) == 1
    f = findings[0]
    assert f.lint == "resident-set"
    assert f.program == "mlp/resident-set"
    # every co-resident member is accounted in the message, and the op
    # path points at the largest temp holder
    for name in reports:
        assert name in f.message
    assert f.op_path in reports
    assert "jit caches keep every executable" in f.message
    # the footprint model: shared args/out once, every temp retained
    total = mc.resident_bytes(reports.values())
    assert total > max(r.peak_bytes for r in reports.values())
    assert total < sum(r.peak_bytes for r in reports.values()) + 1


def test_memory_lints_suppressible():
    tok = tc.add_suppression("temp-blowup", program="seeded-hog")
    try:
        findings, _ = mc.check_program(_hog, (_sds((1024,)),),
                                       name="seeded-hog", temp_mult=2.0)
        hits = [f for f in findings if f.lint == "temp-blowup"]
        assert hits and all(f.suppressed for f in hits)
        assert mc.unsuppressed(hits) == []
    finally:
        tc.remove_suppression(tok)


def test_unknown_mem_lint_rejected():
    with pytest.raises(MXNetError, match="unknown lint"):
        tc.add_suppression("hbm-banana")


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def test_donation_waste_needs_aliasing_evidence():
    """If the executable's HLO text is unavailable (or a future XLA's text
    no longer matches the parser) while the compiler DOES report alias
    savings, analyze_compiled must claim nothing about donation waste — a
    false claim would fail healthy deploys under MXTPU_MEMCHECK=error."""
    class FakeStats:
        argument_size_in_bytes = 4096
        output_size_in_bytes = 4096
        temp_size_in_bytes = 128
        alias_size_in_bytes = 4096     # the donation DID succeed
        generated_code_size_in_bytes = 0

    class FakeCompiled:
        def memory_analysis(self):
            return FakeStats()

        def as_text(self):
            raise RuntimeError("text unavailable on this backend")

    rep = mc.analyze_compiled(FakeCompiled(), "fake",
                              args=(_sds((1024,)),), donate_argnums=(0,))
    assert rep.alias_bytes == 4096
    assert rep.unaliased_donated == []       # no evidence -> no claim
    assert [f for f in mc.lint_report(rep, budget=1 << 30)
            if f.lint == "donation-waste"] == []


def test_baseline_tol_env_overrides_stored_band(mlp_audit, tmp_path,
                                                monkeypatch):
    """MXTPU_MEMCHECK_TOL (the operator loosening a gate run) must beat
    the tolerance stored inside the baseline file."""
    _, reports = mlp_audit
    path = str(tmp_path / "baseline.json")
    mc.write_baseline(reports, path, tol=0.1)
    name = "mlp/scan[k=2]"
    bad = dict(reports)
    bad[name] = _clone_with(bad[name],
                            temp_bytes=bad[name].temp_bytes + (1 << 20))
    monkeypatch.delenv("MXTPU_MEMCHECK_TOL", raising=False)
    failures, _ = mc.compare_baseline(bad, path)
    assert failures  # the stored 10% band catches the +1 MiB growth
    monkeypatch.setenv("MXTPU_MEMCHECK_TOL", "100.0")
    failures, _ = mc.compare_baseline(bad, path)
    assert failures == []  # env-widened band wins over the stored one


def test_budget_env_parsing(monkeypatch):
    monkeypatch.setenv("MXTPU_MEMCHECK_BUDGET", "12G")
    assert mc.budget_bytes() == 12 << 30
    monkeypatch.setenv("MXTPU_MEMCHECK_BUDGET", "1.5M")
    assert mc.budget_bytes() == int(1.5 * (1 << 20))
    monkeypatch.setenv("MXTPU_MEMCHECK_BUDGET", "2048")
    assert mc.budget_bytes() == 2048
    for bad in ("lots", "e", ".", "+", "E3", "-1G"):
        monkeypatch.setenv("MXTPU_MEMCHECK_BUDGET", bad)
        with pytest.raises(MXNetError, match="MXTPU_MEMCHECK_BUDGET"):
            mc.budget_bytes()


def test_budget_default_derives_from_device(monkeypatch):
    monkeypatch.delenv("MXTPU_MEMCHECK_BUDGET", raising=False)
    # CPU reports no bytes_limit -> the documented 16 GiB fallback
    assert mc.budget_bytes() == mc.device_budget()
    class FakeDev:
        def memory_stats(self):
            return {"bytes_limit": 123456789}
    assert mc.device_budget(FakeDev()) == 123456789


def test_memcheck_mode_knob(monkeypatch):
    from mxnet_tpu import engine
    # clear any override a prior test restored by effective value
    # (set_memcheck(prev) pins prev as an override, like set_tracecheck)
    engine.set_memcheck(None)
    monkeypatch.delenv("MXTPU_MEMCHECK", raising=False)
    assert engine.memcheck_mode() == "off"
    monkeypatch.setenv("MXTPU_MEMCHECK", "warn")
    assert engine.memcheck_mode() == "warn"
    monkeypatch.setenv("MXTPU_MEMCHECK", "error")
    assert engine.memcheck_mode() == "error"
    monkeypatch.setenv("MXTPU_MEMCHECK", "banana")
    with pytest.raises(MXNetError, match="MXTPU_MEMCHECK"):
        engine.memcheck_mode()
    monkeypatch.delenv("MXTPU_MEMCHECK", raising=False)
    prev = engine.set_memcheck("error")
    try:
        assert engine.memcheck_mode() == "error"
    finally:
        engine.set_memcheck(prev if prev != "off" else None)


# ---------------------------------------------------------------------------
# the baseline regression gate (ci/memcheck.sh contract)
# ---------------------------------------------------------------------------

def _clone_with(rep, **over):
    kw = dict(program=rep.program, platform=rep.platform,
              argument_bytes=rep.argument_bytes,
              output_bytes=rep.output_bytes, temp_bytes=rep.temp_bytes,
              alias_bytes=rep.alias_bytes,
              generated_code_bytes=rep.generated_code_bytes,
              top_buffers=rep.top_buffers, donated=rep.donated,
              unaliased_donated=rep.unaliased_donated)
    kw.update(over)
    return mc.MemoryReport(**kw)


def test_baseline_roundtrip_passes(mlp_audit, tmp_path):
    _, reports = mlp_audit
    path = str(tmp_path / "baseline.json")
    mc.write_baseline(reports, path)
    failures, notes = mc.compare_baseline(reports, path)
    assert failures == []
    assert notes == []


def test_baseline_catches_injected_temp_regression(mlp_audit, tmp_path):
    """The CI contract: a program whose temp bytes grew past the
    tolerance band fails the gate WITH the buffer breakdown in the
    message."""
    _, reports = mlp_audit
    path = str(tmp_path / "baseline.json")
    mc.write_baseline(reports, path)
    bad = dict(reports)
    name = "mlp/scan[k=2]"
    grown = bad[name].temp_bytes + (1 << 20)  # +1 MiB: over 10% + slack
    bad[name] = _clone_with(bad[name], temp_bytes=grown)
    failures, _notes = mc.compare_baseline(bad, path)
    assert len(failures) == 2  # temp grew, and peak (derived) grew with it
    joined = "\n".join(failures)
    assert name in joined
    assert "temp_bytes grew" in joined
    assert "Largest buffers" in joined
    assert "MXTPU_MEMCHECK_TOL" in joined


def test_baseline_missing_program_fails(mlp_audit, tmp_path):
    _, reports = mlp_audit
    path = str(tmp_path / "baseline.json")
    partial = {n: r for n, r in reports.items() if n != "mlp/step"}
    mc.write_baseline(partial, path)
    failures, notes = mc.compare_baseline(reports, path)
    assert len(failures) == 1
    assert "mlp/step" in failures[0]
    assert "--write-baseline" in failures[0]
    # and the reverse direction is a NOTE (stale entry), not a failure
    failures2, notes2 = mc.compare_baseline(partial, {
        "platform": jax.devices()[0].platform, "tolerance": 0.1,
        "programs": {n: {"peak_bytes": r.peak_bytes,
                         "temp_bytes": r.temp_bytes}
                     for n, r in reports.items()}})
    assert failures2 == []
    assert any("stale" in n for n in notes2)


def test_baseline_platform_mismatch_skips_gate(mlp_audit):
    _, reports = mlp_audit
    failures, notes = mc.compare_baseline(reports, {
        "platform": "tpu", "tolerance": 0.1,
        "programs": {"mlp/step": {"peak_bytes": 1, "temp_bytes": 1}}})
    assert failures == []
    assert len(notes) == 1 and "platform" in notes[0]


def test_baseline_shrink_is_a_note_not_a_failure(mlp_audit, tmp_path):
    _, reports = mlp_audit
    path = str(tmp_path / "baseline.json")
    # baseline claims the program used to be much bigger
    inflated = {n: _clone_with(r, temp_bytes=r.temp_bytes + (4 << 20),
                               argument_bytes=r.argument_bytes + (4 << 20))
                for n, r in reports.items()}
    mc.write_baseline(inflated, path)
    failures, notes = mc.compare_baseline(reports, path)
    assert failures == []
    assert any("shrank" in n for n in notes)


# ---------------------------------------------------------------------------
# CLI (tier-1 smoke of the ci/memcheck.sh gate)
# ---------------------------------------------------------------------------

def test_cli_smoke_json_mlp_lenet(capsys):
    """The tier-1 mirror of the full-zoo CI gate: mlp + lenet in json
    mode exit 0 with zero findings and a full per-program report."""
    rc = mc.main(["--models", "mlp,lenet", "--json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert data["findings"] == []
    assert data["suppressed"] == 0
    assert len(data["programs"]) == 8
    for rep in data["programs"].values():
        assert rep["peak_bytes"] > 0
        assert rep["temp_bytes"] > 0
    assert data["budget_bytes"] > 0
    assert data["platform"] == jax.devices()[0].platform


def test_cli_list_and_bad_model(capsys):
    assert mc.main(["--list"]) == 0
    assert "mlp" in capsys.readouterr().out
    with pytest.raises(MXNetError, match="unknown zoo model"):
        mc.main(["--models", "nope"])


def test_cli_write_and_gate_baseline(tmp_path, capsys):
    """CLI end-to-end: --write-baseline then --baseline passes; a doctored
    baseline (simulating a regression against it) fails with the
    breakdown on stdout."""
    path = str(tmp_path / "b.json")
    rc = mc.main(["--models", "mlp", "--quiet", "--write-baseline", path])
    capsys.readouterr()
    assert rc == 0
    rc = mc.main(["--models", "mlp", "--quiet", "--baseline", path])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 baseline regression(s)" in out
    # doctor the baseline: pretend the committed numbers were tiny
    with open(path) as f:
        base = json.load(f)
    for entry in base["programs"].values():
        entry["temp_bytes"] = 1
        entry["peak_bytes"] = 1
    # shrink the slack-dominated band by dropping the program size gap:
    # mlp programs are tiny, so gate a synthetic compare directly too
    with open(path, "w") as f:
        json.dump(base, f)
    rc = mc.main(["--models", "mlp", "--quiet", "--baseline", path])
    out = capsys.readouterr().out
    # mlp programs are under the 64 KiB absolute slack — the CLI must
    # still PASS (tiny programs can't regress meaningfully)...
    assert rc == 0
    # ...while a lenet-sized program (MiB temps) trips the gate
    rc = mc.main(["--models", "lenet", "--quiet", "--baseline", path])
    out = capsys.readouterr().out
    assert rc == 1
    assert "BASELINE REGRESSION" in out
    assert "not in the baseline" in out
