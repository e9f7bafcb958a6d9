"""flopcheck tests (docs/static_analysis.md "Roofline lints"): the
static per-kernel compute/memory roofline analyzer over compiled
programs.

The load-bearing assertions:

* the scheduled-HLO kernel parser builds the inventory right — dots by
  their contraction algebra, fusions by their callee sums, alias-aware
  bytes, in-loop multipliers from ``known_trip_count``, expansion-loop
  collapse (a scalar pool-backprop while becomes ONE merged kernel with
  one streaming pass of bytes, never per-iter bytes x trips), layout
  detection, and collectives/views/control-flow excluded;
* the roofline pricing holds: ``max(flops/peak, bytes/bw)`` per kernel,
  compute/memory bound vs the ridge, cost-analysis apportioning that
  normalizes on the once-each ``norm_flops`` basis;
* one SEEDED violation per roofline lint class — ``memory-bound-hot``,
  ``layout-copy``, ``tiny-dispatch``, ``predicted-mfu`` — is caught
  (with op path / source provenance where a real program seeds it);
* the baseline drift gate goes RED end-to-end on a seeded fusion
  regression (one clean dot shattered into two dozen mismatched dots)
  WITH the kernel breakdown and provenance (the ci/flopcheck.sh
  contract), and the absence-of-evidence discipline holds on both the
  write and compare paths;
* the CLI smoke (mlp, json mode) exits 0 with zero findings — the
  tier-1 mirror of the combined compile-once CI gate.
"""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu import flopcheck as fc  # noqa: E402
from mxnet_tpu import tracecheck as tc  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402

SDS = jax.ShapeDtypeStruct

# a hand-picked spec for deterministic pricing: ridge = 100 FLOP/B
_PEAK, _BW = 1e12, 1e10


def _kernel(name, flops=0.0, nbytes=0, mult=1, opcode="fusion",
            layout=False, op_path=None, prov=None, norm_flops=None):
    return fc.KernelEntry(name, opcode, flops, nbytes, multiplier=mult,
                          is_layout=layout, op_path=op_path,
                          provenance=prov, norm_flops=norm_flops)


def _fake_roofline(name, kernels, hlo_unavailable=False, loop_trips=1,
                   flops=None):
    return fc.RooflineReport(
        name, jax.devices()[0].platform, kernels, loop_trips=loop_trips,
        flops=flops, peak_flops_per_s=_PEAK, hbm_bytes_per_s=_BW,
        peak_source="test-spec", hlo_unavailable=hlo_unavailable)


# ---------------------------------------------------------------------------
# the scheduled-HLO kernel parser
# ---------------------------------------------------------------------------

_FAKE_HLO = """HloModule t, is_scheduled=true, entry_computation_layout={(f32[8,32]{1,0})->f32[8,16]{1,0}}

FileNames
1 "/tmp/t.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=9 end_line=9 column=1 end_column=9}
2 {file_name_id=1 function_name_id=1 line=4 end_line=4 column=1 end_column=9}
3 {file_name_id=1 function_name_id=1 line=5 end_line=5 column=1 end_column=9}
4 {file_name_id=1 function_name_id=1 line=6 end_line=6 column=1 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}
3 {file_location_id=3 parent_frame_id=1}
4 {file_location_id=4 parent_frame_id=1}


%fused_add (p0: f32[128,64], p1: f32[128,64]) -> f32[128,64] {
  %p0 = f32[128,64]{1,0} parameter(0)
  %p1 = f32[128,64]{1,0} parameter(1)
  ROOT %add.2 = f32[128,64]{1,0} add(f32[128,64]{1,0} %p0, f32[128,64]{1,0} %p1)
}

%scan.body (wp: (s32[1], f32[64,64])) -> (s32[1], f32[64,64]) {
  %wp = (s32[1]{0}, f32[64,64]{1,0}) parameter(0)
  %mul.3 = f32[64,64]{1,0} multiply(f32[64,64]{1,0} %g.1, f32[64,64]{1,0} %g.1), metadata={op_name="jit(f)/jit(main)/while/body/mul" stack_frame_id=1}
}

%exp.body (xp: (s32[1], f32[4096])) -> (s32[1], f32[4096]) {
  %xp = (s32[1]{0}, f32[4096]{0}) parameter(0)
  %add.7 = f32[1]{0} add(f32[1]{0} %e.1, f32[1]{0} %e.2)
}

ENTRY %main.1 (Arg_0.1: f32[8,32], Arg_1.2: f32[32,16]) -> f32[8,16] {
  %Arg_0.1 = f32[8,32]{1,0} parameter(0)
  %Arg_1.2 = f32[32,16]{1,0} parameter(1)
  %dot.4 = f32[8,16]{1,0} dot(f32[8,32]{1,0} %Arg_0.1, f32[32,16]{1,0} %Arg_1.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jit(main)/dot" stack_frame_id=2}
  %fusion.5 = f32[128,64]{1,0} fusion(f32[128,64]{1,0} %a.1, f32[128,64]{1,0} %a.2), kind=kLoop, calls=%fused_add, metadata={op_name="jit(f)/jit(main)/add" stack_frame_id=3}
  %copy.6 = f32[512,512]{0,1} copy(f32[512,512]{1,0} %fusion.5), metadata={op_name="jit(f)/jit(main)/copy" stack_frame_id=4}
  %dynamic-slice.12 = f32[1,16]{1,0} dynamic-slice(f32[8,16]{1,0} %dot.4, s32[1]{0} %i.1, s32[1]{0} %i.2), dynamic_slice_sizes={1,16}
  %while.8 = (s32[1]{0}, f32[64,64]{1,0}) while((s32[1]{0}, f32[64,64]{1,0}) %t.1), condition=%scan.cond, body=%scan.body, backend_config={"known_trip_count":{"n":"3"}}
  %while.9 = (s32[1]{0}, f32[4096]{0}) while((s32[1]{0}, f32[4096]{0}) %t.2), condition=%exp.cond, body=%exp.body, backend_config={"known_trip_count":{"n":"4096"}}
  %all-reduce.10 = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %dot.4), channel_id=1, replica_groups={{0,1}}, to_apply=%sum.1
  %transpose.11 = f32[16,8]{1,0} transpose(f32[8,16]{1,0} %dot.4), dimensions={1,0}
}
"""


def test_parser_kernel_inventory():
    kernels = {k.instruction: k for k in fc.parse_kernels(_FAKE_HLO)}
    # parameters, the while/all-reduce instructions themselves: not kernels
    assert sorted(kernels) == ["copy.6", "dot.4", "dynamic-slice.12",
                               "fusion.5", "mul.3", "transpose.11",
                               "while.9"]
    dot = kernels["dot.4"]
    assert dot.flops == 2.0 * (8 * 16) * 32      # 2 x out x contracted
    assert dot.bytes == (8 * 32 + 32 * 16 + 8 * 16) * 4
    assert not dot.is_layout and not dot.in_loop and dot.multiplier == 1
    assert dot.op_path == "jit(f)/jit(main)/dot"
    assert dot.provenance == "/tmp/t.py:4"
    fus = kernels["fusion.5"]
    assert fus.flops == 128 * 64                  # the callee's add
    assert fus.bytes == 3 * 128 * 64 * 4          # 2 operands + result
    assert not fus.is_layout
    # pure data motion: a copy kernel, and a bare transpose
    assert kernels["copy.6"].is_layout
    assert kernels["copy.6"].bytes == 2 * 512 * 512 * 4
    assert kernels["transpose.11"].is_layout
    # alias-aware: a dynamic-slice reads only the slice it extracts
    assert kernels["dynamic-slice.12"].bytes == 2 * (1 * 16 * 4)
    # the K-trip scan body is inventoried in-loop with its multiplier
    mul = kernels["mul.3"]
    assert mul.in_loop and mul.multiplier == 3
    assert mul.op_path == "jit(f)/jit(main)/while/body/mul"
    assert mul.provenance == "/tmp/t.py:9"


def test_parser_expansion_loop_collapses_to_one_streaming_kernel():
    """A 4096-trip scalar while (the CPU pool-backprop lowering) must
    become ONE merged kernel: FLOPs = body x trips, but bytes = one
    read + one write of the loop-carried tuple state — NOT body-bytes x
    trips (each scalar iteration references the full arrays it slices
    from, so that would bill petabytes); and the normalization basis
    stays the one-trip body (the XLA cost model counts a body once)."""
    kernels = {k.instruction: k for k in fc.parse_kernels(_FAKE_HLO)}
    w = kernels["while.9"]
    assert w.opcode == "while" and w.multiplier == 1
    assert w.flops == 1.0 * 4096          # 1-elem add body x 4096 trips
    assert w.norm_flops == 1.0
    assert w.bytes == 2 * (4 + 4 * 4096)  # 2 x (s32[1] + f32[4096])
    # the scan-depth while (3 trips) did NOT collapse: its body kernels
    # carry the multiplier instead
    assert "while.8" not in kernels


_NOTRIP_HLO = """HloModule t, is_scheduled=true

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %while.1 = (s32[1]{0}, f32[4]{0}) while((s32[1]{0}, f32[4]{0}) %t.1), condition=%c.1, body=%b.1
}

%b.1 (bp: (s32[1], f32[4])) -> (s32[1], f32[4]) {
  %bp = (s32[1]{0}, f32[4]{0}) parameter(0)
  %exp.1 = f32[4]{0} exponential(f32[4]{0} %g.1)
}
"""


def test_parser_while_without_trip_count_uses_loop_trips():
    kernels = fc.parse_kernels(_NOTRIP_HLO, loop_trips=5)
    assert len(kernels) == 1
    assert kernels[0].instruction == "exp.1"
    assert kernels[0].in_loop and kernels[0].multiplier == 5


def test_parser_empty_text():
    assert fc.parse_kernels("") == []


# ---------------------------------------------------------------------------
# the report + roofline pricing
# ---------------------------------------------------------------------------

def test_report_pricing_and_roofline():
    # intensity 1000 FLOP/B >= ridge 100: compute bound, flops-limited
    k1 = _kernel("k.dot", flops=1e6, nbytes=1000, op_path="path/dot")
    # zero-FLOP copy, 2 executions: memory bound, 2 x 2000/bw
    k2 = _kernel("k.copy", nbytes=2000, mult=2, opcode="copy", layout=True)
    rep = _fake_roofline("p/step", [k1, k2])
    assert rep.ridge_intensity == 100.0
    assert k1.bound == "compute" and k1.seconds == 1e6 / _PEAK
    assert k2.bound == "memory" and k2.seconds == 2000 / _BW
    assert rep.kernel_count == 3                 # multiplier semantics
    assert rep.bytes_per_dispatch == 1000 + 2 * 2000
    t = 1e6 / _PEAK + 2 * (2000 / _BW)
    assert abs(rep.predicted_step_seconds - t) < 1e-12
    assert abs(rep.predicted_mfu - 1e6 / (t * _PEAK)) < 1e-9
    # kernels rank by held step time: the copy (4e-7s) over the dot (1e-6s)?
    # no — 1e-6 > 4e-7, the dot leads and its op_path is the pinned identity
    assert rep.top_hotspot == "path/dot"
    assert rep.hotspots(5, memory_only=True) == [k2]
    assert "p/step" in rep.format()


def test_report_cost_analysis_apportioning_respects_norm_basis():
    """Apportioning scales structural estimates so their sum matches the
    XLA cost model — on the ``norm_flops`` basis: a collapsed expansion
    loop weighs in at its ONE-trip body, so it cannot steal the whole
    program's FLOP budget."""
    merged = _kernel("w", flops=100.0 * 50, nbytes=8, norm_flops=100.0)
    plain = _kernel("k", flops=100.0, nbytes=8)
    rep = _fake_roofline("p/step", [merged, plain], flops=400.0)
    # basis = 100 + 100 = 200, scale = 2
    by_name = {k.instruction: k for k in rep.kernels}
    assert by_name["w"].flops == 10000.0
    assert by_name["k"].flops == 200.0


def test_report_blind_program_claims_nothing():
    rep = _fake_roofline("p/step", [], hlo_unavailable=True)
    assert rep.predicted_mfu is None
    assert rep.top_hotspot is None
    assert rep.as_dict()["hlo_unavailable"] is True


# ---------------------------------------------------------------------------
# seeded roofline lints
# ---------------------------------------------------------------------------

def _hot_program_size():
    return 4 << 20  # 4M f32 = 16 MiB: far above the 1 MiB test floor


def _seeded_hot_add(x):
    return x + 1.0


def test_lint_memory_bound_hot_seeded_real_program():
    """The flash-attention signature, seeded with the simplest possible
    HBM-bound program: one elementwise add over 16 MiB holds ~100% of
    the predicted step at intensity far below any ridge."""
    findings, report = fc.check_program(
        _seeded_hot_add, (SDS((_hot_program_size(),), np.float32),),
        name="seed/hot", hot_threshold=0.5, hot_floor=1 << 20,
        mfu_floor=0.0)
    hot = [f for f in findings if f.lint == "memory-bound-hot"]
    assert hot, "the seeded HBM-bound add must fire memory-bound-hot"
    f = hot[0]
    assert f.program == "seed/hot"
    assert f.op_path
    assert f.provenance and "test_flopcheck" in f.provenance
    assert "MXTPU_FLOPCHECK_HOT_FRAC" in f.message
    assert report.kernels[0].bound == "memory"


def test_lint_layout_copy_seeded_and_share_gated():
    big_copy = _kernel("relayout", nbytes=10 << 20, opcode="copy",
                       layout=True, op_path="jit(f)/transpose",
                       prov="m.py:7")
    small = _kernel("k", flops=100.0, nbytes=1 << 20)
    rep = _fake_roofline("seed/layout", [big_copy, small])
    findings = fc.lint_report(rep, mfu_floor=0.0)
    lay = [f for f in findings if f.lint == "layout-copy"]
    assert len(lay) == 1
    assert lay[0].op_path == "jit(f)/transpose"
    assert lay[0].provenance == "m.py:7"
    assert "MXTPU_FLOPCHECK_LAYOUT_FRAC" in lay[0].message
    # the share gate: the same copy next to 1 GiB of real traffic is a
    # rounding error (the vgg scan-stacking case) — silent
    huge = _kernel("conv", flops=1e12, nbytes=1 << 30)
    rep2 = _fake_roofline("seed/layout2", [big_copy, huge])
    assert not [f for f in fc.lint_report(rep2, mfu_floor=0.0)
                if f.lint == "layout-copy"]


def test_lint_tiny_dispatch_seeded():
    # 5000 sub-microsecond executions of one in-loop kernel
    shard = _kernel("tiny", flops=10.0, nbytes=40, mult=5000,
                    op_path="jit(f)/while/body/slice", prov="m.py:3")
    rep = _fake_roofline("seed/tiny", [shard])
    findings = fc.lint_report(rep, tiny_floor_us=1.0, tiny_threshold=4096,
                              mfu_floor=0.0)
    tiny = [f for f in findings if f.lint == "tiny-dispatch"]
    assert len(tiny) == 1
    assert "5000" in tiny[0].message
    assert "MXTPU_FLOPCHECK_TINY_COUNT" in tiny[0].message
    assert tiny[0].op_path == "jit(f)/while/body/slice"
    # below the threshold: silent
    shard2 = _kernel("tiny", flops=10.0, nbytes=40, mult=100)
    assert not fc.lint_report(_fake_roofline("q", [shard2]),
                              tiny_floor_us=1.0, tiny_threshold=4096,
                              mfu_floor=0.0)


def test_lint_predicted_mfu_seeded_and_disabled_by_default():
    # one memory-bound kernel: mfu = 1e4 / (1e-4 x 1e12) = 1e-4
    k = _kernel("hbm", flops=1e4, nbytes=int(1e6), op_path="jit(f)/add")
    rep = _fake_roofline("seed/mfu", [k])
    findings = fc.lint_report(rep, hot_threshold=2.0, mfu_floor=0.9)
    mfu = [f for f in findings if f.lint == "predicted-mfu"]
    assert len(mfu) == 1
    assert "MXTPU_FLOPCHECK_MIN_MFU" in mfu[0].message
    assert "Inventory:" in mfu[0].message
    # default floor is 0 = disarmed
    assert not [f for f in fc.lint_report(rep, hot_threshold=2.0)
                if f.lint == "predicted-mfu"]


def test_suppression_registry_shared_with_tracecheck():
    k = _kernel("hbm", flops=1e4, nbytes=int(1e6))
    rep = _fake_roofline("supp/step", [k])
    token = tc.add_suppression("predicted-mfu", program="supp/")
    try:
        findings = fc.lint_report(rep, hot_threshold=2.0, mfu_floor=0.9)
        assert findings and all(f.suppressed for f in findings)
        assert tc.unsuppressed(findings) == []
    finally:
        tc.remove_suppression(token)
    findings = fc.lint_report(rep, hot_threshold=2.0, mfu_floor=0.9)
    assert tc.unsuppressed(findings)


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def test_knob_defaults_and_env(monkeypatch):
    for var in ("HOT_FRAC", "HOT_BYTES", "LAYOUT_BYTES", "LAYOUT_FRAC",
                "TINY_US", "TINY_COUNT", "MIN_MFU", "TOL"):
        monkeypatch.delenv("MXTPU_FLOPCHECK_" + var, raising=False)
    assert fc.hot_frac() == 0.6
    assert fc.hot_bytes() == 4 << 20
    assert fc.layout_bytes() == 4 << 20
    assert fc.layout_frac() == 0.25
    assert fc.tiny_us() == 1.0
    assert fc.tiny_count() == 4096
    assert fc.min_mfu() == 0.0
    assert fc.tolerance() == 0.1
    monkeypatch.setenv("MXTPU_FLOPCHECK_HOT_FRAC", "0.8")
    monkeypatch.setenv("MXTPU_FLOPCHECK_HOT_BYTES", "8M")
    monkeypatch.setenv("MXTPU_FLOPCHECK_LAYOUT_FRAC", "0.5")
    monkeypatch.setenv("MXTPU_FLOPCHECK_TINY_COUNT", "128")
    monkeypatch.setenv("MXTPU_FLOPCHECK_MIN_MFU", "0.4")
    assert fc.hot_frac() == 0.8
    assert fc.hot_bytes() == 8 << 20
    assert fc.layout_frac() == 0.5
    assert fc.tiny_count() == 128
    assert fc.min_mfu() == 0.4
    monkeypatch.setenv("MXTPU_FLOPCHECK_HOT_BYTES", "banana")
    with pytest.raises(MXNetError, match="MXTPU_FLOPCHECK_HOT_BYTES"):
        fc.hot_bytes()
    monkeypatch.setenv("MXTPU_FLOPCHECK_HOT_FRAC", "banana")
    with pytest.raises(MXNetError, match="MXTPU_FLOPCHECK_HOT_FRAC"):
        fc.hot_frac()


def test_flopcheck_mode_knob(monkeypatch):
    from mxnet_tpu import engine
    engine.set_flopcheck(None)
    monkeypatch.delenv("MXTPU_FLOPCHECK", raising=False)
    assert engine.flopcheck_mode() == "off"
    monkeypatch.setenv("MXTPU_FLOPCHECK", "warn")
    assert engine.flopcheck_mode() == "warn"
    monkeypatch.setenv("MXTPU_FLOPCHECK", "error")
    assert engine.flopcheck_mode() == "error"
    monkeypatch.setenv("MXTPU_FLOPCHECK", "banana")
    with pytest.raises(MXNetError, match="MXTPU_FLOPCHECK"):
        engine.flopcheck_mode()
    monkeypatch.delenv("MXTPU_FLOPCHECK", raising=False)
    prev = engine.set_flopcheck("error")
    try:
        assert engine.flopcheck_mode() == "error"
    finally:
        engine.set_flopcheck(prev if prev != "off" else None)


# ---------------------------------------------------------------------------
# the dispatch hook (MXTPU_FLOPCHECK) — flopcheck audits EVERY program,
# single-device included: a fusion regression needs no mesh to hurt
# ---------------------------------------------------------------------------

def _train_step():
    from mxnet_tpu import models
    from mxnet_tpu.train_step import TrainStep
    ts = TrainStep(models.mlp(num_classes=4, hidden=(16,)),
                   optimizer="sgd", learning_rate=0.1)
    state = ts.init({"data": (8, 16)}, {"softmax_label": (8,)})
    rng = np.random.default_rng(0)
    sb = {"data": jnp.asarray(rng.normal(size=(2, 8, 16)), jnp.float32),
          "softmax_label": jnp.asarray(rng.integers(0, 4, (2, 8)),
                                       jnp.float32)}
    return ts, state, sb


def test_dispatch_hook_audits_single_device_program_once():
    from mxnet_tpu import engine
    prev = engine.set_flopcheck("warn")
    try:
        before = set(fc._AUDITED)
        ts, state, sb = _train_step()
        state, m = ts.run_steps(state, sb)
        new = set(fc._AUDITED) - before
        assert len(new) == 1 and "scan" in next(iter(new))
        # second dispatch: memoized, no re-audit
        state, m = ts.run_steps(state, sb)
        assert set(fc._AUDITED) - before == new
        assert m.num_samples > 0
    finally:
        engine.set_flopcheck(prev if prev != "off" else None)


def test_dispatch_hook_error_mode_raises_on_finding(monkeypatch):
    """MXTPU_FLOPCHECK=error + an impossible MFU floor: the first
    dispatch fails fast with the roofline findings instead of burning a
    profiling session."""
    from mxnet_tpu import engine
    monkeypatch.setenv("MXTPU_FLOPCHECK_MIN_MFU", "0.999")
    prev = engine.set_flopcheck("error")
    try:
        ts, state, sb = _train_step()
        with pytest.raises(MXNetError, match="predicted-mfu"):
            ts.run_steps(state, sb)
    finally:
        engine.set_flopcheck(prev if prev != "off" else None)


def test_dispatch_hook_off_by_default(monkeypatch):
    from mxnet_tpu import engine
    engine.set_flopcheck(None)
    monkeypatch.delenv("MXTPU_FLOPCHECK", raising=False)
    before = set(fc._AUDITED)
    ts, state, sb = _train_step()
    ts.run_steps(state, sb)
    assert set(fc._AUDITED) == before


def test_dispatch_hook_blind_compiled_does_not_pass_vacuously():
    from mxnet_tpu import engine

    class FakeCompiled:
        def as_text(self):
            raise RuntimeError("no HLO text on this backend")

        def cost_analysis(self):
            return None

    class FakeJit:
        def lower(self, *a, **k):
            return self

        def compile(self):
            return FakeCompiled()

    prev = engine.set_flopcheck("error")
    try:
        fc._AUDITED.discard("blind-prog")
        with pytest.raises(MXNetError, match="unavailable"):
            fc.maybe_audit_dispatch("blind-prog", FakeJit(), ())
    finally:
        engine.set_flopcheck(prev if prev != "off" else None)


# ---------------------------------------------------------------------------
# the baseline drift gate (ci/flopcheck.sh contract)
# ---------------------------------------------------------------------------

def _uniform_report(name, count=4, ms_total=1.0):
    per = int(ms_total * 1e-3 / count * _BW)  # bytes so each kernel
    kernels = [_kernel("k.%d" % i, nbytes=per, op_path="path/k.%d" % i)
               for i in range(count)]         # prices ms_total/count
    return _fake_roofline(name, kernels)


def test_baseline_roundtrip_passes(tmp_path):
    reports = {"a/step": _uniform_report("a/step", 4, 1.0),
               "b/scan[k=2]": _uniform_report("b/scan[k=2]", 7, 2.0)}
    path = str(tmp_path / "b.json")
    fc.write_baseline(reports, path)
    failures, notes = fc.compare_baseline(reports, path)
    assert failures == []
    assert notes == []


def _clean_gate(x):
    return x @ x


def _regressed_gate(x):
    # two dozen mismatched-shape dots: XLA cannot fuse or CSE them, the
    # one-kernel step shatters into a pile
    acc = jnp.zeros((), jnp.float32)
    for i in range(1, 25):
        acc = acc + jnp.sum(x[:i, :] @ x)
    return acc


def test_baseline_fails_seeded_fusion_regression_end_to_end(tmp_path):
    """The acceptance contract: a baseline pinned on the clean one-dot
    program goes RED when the same program name shatters into two dozen
    kernels — with the kernel breakdown and source provenance in the
    failure (before any profiler runs)."""
    arg = (SDS((32, 32), np.float32),)
    clean = fc.analyze(_clean_gate, arg, name="gate/step")
    path = str(tmp_path / "b.json")
    fc.write_baseline({"gate/step": clean}, path)
    regressed = fc.analyze(_regressed_gate, arg, name="gate/step")
    assert regressed.kernel_count > clean.kernel_count * 2
    failures, _ = fc.compare_baseline({"gate/step": regressed}, path)
    assert failures
    joined = "\n".join(failures)
    assert "kernel_count grew" in joined
    assert "MXTPU_FLOPCHECK_TOL" in joined
    assert "Inventory:" in joined            # the breakdown rides along
    assert "test_flopcheck" in joined        # ...with provenance


def test_baseline_mfu_drop_fails_rise_and_hotspot_move_note():
    rep = _fake_roofline(
        "a/step", [_kernel("hbm", flops=1e4, nbytes=int(1e6),
                           op_path="path/hbm")])
    mfu = rep.predicted_mfu  # 1e-4
    base = {"platform": jax.devices()[0].platform, "tolerance": 0.1,
            "programs": {"a/step": {
                "kernel_count": 1,
                "predicted_step_ms": rep.predicted_step_ms,
                "predicted_mfu": 0.9, "top_hotspot": "path/other"}}}
    failures, notes = fc.compare_baseline({"a/step": rep}, base)
    assert any("predicted_mfu dropped" in f for f in failures)
    assert any("top hotspot moved" in n for n in notes)
    base["programs"]["a/step"]["predicted_mfu"] = mfu / 2
    base["programs"]["a/step"]["top_hotspot"] = "path/hbm"
    failures, notes = fc.compare_baseline({"a/step": rep}, base)
    assert failures == []
    assert any("rose" in n for n in notes)


def test_baseline_missing_stale_platform_shrink_collapse(tmp_path):
    reports = {"a/step": _uniform_report("a/step", 8, 4.0)}
    path = str(tmp_path / "b.json")
    fc.write_baseline(reports, path)
    # missing program fails (deliberate-add contract), stale is a note
    failures, notes = fc.compare_baseline(
        {"a/step": reports["a/step"],
         "new/step": _uniform_report("new/step", 1, 0.1)}, path)
    assert len(failures) == 1 and "new/step" in failures[0]
    assert "--write-baseline" in failures[0]
    failures2, notes2 = fc.compare_baseline({}, path)
    assert failures2 == []
    assert any("stale" in n for n in notes2)
    # platform mismatch skips the gate with one note
    failures3, notes3 = fc.compare_baseline(reports, {
        "platform": "made-up-platform", "tolerance": 0.1,
        "programs": {"a/step": {"kernel_count": 1,
                                "predicted_step_ms": 1.0}}})
    assert failures3 == []
    assert len(notes3) == 1 and "platform" in notes3[0]
    # shrinks are notes, not failures
    failures4, notes4 = fc.compare_baseline(
        {"a/step": _uniform_report("a/step", 4, 1.0)}, path)
    assert failures4 == []
    assert any("kernel_count shrank" in n for n in notes4)
    assert any("predicted_step_ms shrank" in n for n in notes4)
    # ...but a TOTAL collapse to zero kernels on a nonzero-pinned
    # program fails: indistinguishable from a parser gone blind
    failures5, _ = fc.compare_baseline(
        {"a/step": _fake_roofline("a/step", [])}, path)
    assert any("collapsed" in f for f in failures5)


def test_baseline_tol_env_overrides_stored_band(tmp_path, monkeypatch):
    path = str(tmp_path / "b.json")
    fc.write_baseline({"a/step": _uniform_report("a/step", 10, 1.0)},
                      path, tol=0.1)
    grown = {"a/step": _uniform_report("a/step", 13, 1.0)}
    monkeypatch.delenv("MXTPU_FLOPCHECK_TOL", raising=False)
    failures, _ = fc.compare_baseline(grown, path)
    assert failures  # +30% kernels past the stored 10% band
    monkeypatch.setenv("MXTPU_FLOPCHECK_TOL", "0.5")
    failures, _ = fc.compare_baseline(grown, path)
    assert failures == []  # env-widened band wins


def test_baseline_refuses_absence_of_evidence(tmp_path):
    blind = _fake_roofline("blind/step", [], hlo_unavailable=True)
    with pytest.raises(MXNetError, match="fabricated"):
        fc.write_baseline({"blind/step": blind},
                          str(tmp_path / "b.json"))
    path = str(tmp_path / "b2.json")
    fc.write_baseline({"blind/step": _uniform_report("blind/step", 2, 1.0)},
                      path)
    failures, _ = fc.compare_baseline({"blind/step": blind}, path)
    assert len(failures) == 1
    assert "absence of evidence" in failures[0]


# ---------------------------------------------------------------------------
# hotspots: the Pallas shopping list
# ---------------------------------------------------------------------------

def test_autotune_hotspot_report_accessor():
    from mxnet_tpu import autotune

    def fn(x, b):
        return x @ x + b

    entries = autotune.hotspot_report(
        fn, (SDS((128, 128), np.float32), SDS((128,), np.float32)),
        name="tune/fn", top=5, memory_only=False)
    assert entries
    fracs = [e["step_time_frac"] for e in entries]
    assert all(0.0 <= f <= 1.0 for f in fracs)
    assert sum(fracs) <= 1.0 + 1e-6
    times = [e["predicted_us"] * e["multiplier"] for e in entries]
    assert times == sorted(times, reverse=True)  # ranked by held time
    assert all(e["bound"] in ("compute", "memory") for e in entries)


def test_transformer_attention_kernels_ranked_with_provenance():
    """On the transformer zoo model the memory-bound hotspot table names
    the attention kernels by op path and ``ops/attention.py`` provenance.
    Their RANK is XLA:CPU's fusion choice at a toy size (they led the
    table under jaxlib 0.4 and follow the FFN kernels under 0.9), so the
    table's head is not asserted."""
    from mxnet_tpu.tracecheck import train_step_programs, zoo_train_step
    ts, data_shapes, label_shapes = zoo_train_step("transformer")
    rep = None
    for pname, jitfn, pargs in train_step_programs(
            ts, data_shapes, label_shapes, k=2, guard=False,
            name="transformer"):
        if pname.endswith("/step"):
            rep = fc.analyze(jitfn, pargs, name=pname, mesh=ts.mesh)
            break
    assert rep is not None
    top = rep.hotspots(8, memory_only=True)
    paths = [(k.op_path or "") + " " + (k.provenance or "") for k in top]
    assert any("MultiHeadAttention" in p and "ops/attention.py" in p
               for p in paths), paths


# ---------------------------------------------------------------------------
# CLI (tier-1 smoke of the ci/flopcheck.sh gate)
# ---------------------------------------------------------------------------

def test_cli_smoke_json_mlp(capsys):
    """The tier-1 mirror of the combined CI gate: mlp + lenet in json
    mode exit 0 with zero findings and a priced inventory for all 8
    programs."""
    rc = fc.main(["--models", "mlp,lenet", "--json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert data["findings"] == []
    assert data["suppressed"] == 0
    assert data["baseline_failures"] == []
    assert len(data["programs"]) == 8
    for rep in data["programs"].values():
        assert rep["kernel_count"] > 0
        assert rep["predicted_step_ms"] > 0
        assert rep["top_hotspot"]
        assert rep["hlo_unavailable"] is False
    assert data["platform"] == jax.devices()[0].platform
    assert data["analyzers_sharing_compile"] == 1


def test_cli_fails_on_hlo_unavailable_even_without_baseline(
        capsys, monkeypatch):
    """The absence-of-evidence contract holds in the no-baseline CLI
    modes too: a backend where as_text() fails must not print PASS over
    an audit that saw no HLO."""
    blind = _fake_roofline("mlp/step", [], hlo_unavailable=True)
    monkeypatch.setattr(fc, "compiled_zoo_programs",
                        lambda **kw: iter(()))
    monkeypatch.setattr(fc, "check_zoo",
                        lambda **kw: ([], {"mlp/step": blind}))
    rc = fc.main(["--models", "mlp", "--json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any("absence of evidence" in f
               for f in data["baseline_failures"])
    assert data["programs"]["mlp/step"]["hlo_unavailable"] is True


def test_cli_list_and_bad_model(capsys):
    assert fc.main(["--list"]) == 0
    assert "mlp" in capsys.readouterr().out
    with pytest.raises(MXNetError, match="unknown zoo model"):
        fc.main(["--models", "nope"])


def test_cli_write_and_gate_baseline_with_hotspots(tmp_path, capsys):
    path = str(tmp_path / "b.json")
    rc = fc.main(["--models", "mlp", "--quiet", "--write-baseline", path])
    capsys.readouterr()
    assert rc == 0
    with open(path) as f:
        base = json.load(f)
    assert len(base["programs"]) == 4
    rc = fc.main(["--models", "mlp", "--quiet", "--baseline", path,
                  "--hotspots", "3"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 baseline regression(s)" in out
    assert "ridge" in out                    # the hotspot table printed
    # a stale baseline entry is a note, not a failure
    base["programs"]["ghost/step"] = {"kernel_count": 1,
                                      "predicted_step_ms": 1.0}
    with open(path, "w") as f:
        json.dump(base, f)
    rc = fc.main(["--models", "mlp", "--quiet", "--baseline", path])
    out = capsys.readouterr().out
    assert rc == 0
    assert "stale" in out
