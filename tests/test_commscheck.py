"""commscheck tests (docs/static_analysis.md "Communication lints"): the
static collective-communication analyzer over compiled partitioned
programs.

The load-bearing assertions:

* the HLO collective parser handles every spelling the partitioner
  emits — explicit and iota replica_groups, tuple-typed (combined /
  tiled) collectives, async ``-start``/``-done`` pairs counted once,
  ``op_name``-based while-body detection with source provenance;
* the comms *signatures* of the parallel stack hold: ring attention is
  ppermute-only (no all-gather), Ulysses is all-to-all-only (3 in + 1
  out per attention), ``pipeline_spmd`` is an in-loop ppermute ring plus
  one final psum, and the data-parallel fused scan syncs by in-loop
  all-reduce only;
* one SEEDED violation per comms lint class — ``resharding-copy``,
  ``replicated-large``, ``gather-in-loop``, ``comms-bound`` — is caught
  with op path and source provenance asserted;
* the baseline drift gate fails a seeded in-scan all-gather regression
  WITH its byte count and provenance (the ci/commscheck.sh contract);
* the CLI smoke (mlp + lenet, json mode) exits 0 with zero findings and
  zero collectives — the tier-1 mirror of the full-zoo CI gate.
"""
import functools
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu import commscheck as cc  # noqa: E402
from mxnet_tpu import tracecheck as tc  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402

P = jax.sharding.PartitionSpec

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="commscheck partitioned-program tests need >=2 devices "
           "(conftest forces an 8-device virtual CPU mesh)")


def _mesh(n, names=("data",)):
    shape = (n,) if len(names) == 1 else (n // 2, 2)
    return jax.sharding.Mesh(
        np.array(jax.devices()[:n]).reshape(shape), names)


def _ns(mesh, spec):
    return jax.sharding.NamedSharding(mesh, spec)


def _sds(shape, mesh=None, spec=None, dtype=np.float32):
    if mesh is None:
        return jax.ShapeDtypeStruct(tuple(shape), dtype)
    return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                sharding=_ns(mesh, spec))


# ---------------------------------------------------------------------------
# the HLO parser
# ---------------------------------------------------------------------------

_FAKE_HLO = """HloModule t, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "a.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=3 end_line=3 column=1 end_column=9}
2 {file_name_id=1 function_name_id=1 line=7 end_line=7 column=1 end_column=9}
3 {file_name_id=1 function_name_id=1 line=9 end_line=9 column=1 end_column=9}
4 {file_name_id=1 function_name_id=1 line=11 end_line=11 column=1 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}
3 {file_location_id=3 parent_frame_id=1}
4 {file_location_id=4 parent_frame_id=1}


%wide.body (p: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
}

ENTRY %main.1 (Arg_0.1: f32[8], Arg_1.2: f32[16,4]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="state['w']"}
  %Arg_1.2 = f32[16,4]{1,0} parameter(1), metadata={op_name="batch"}
  %all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %mul.1), channel_id=1, replica_groups=[1,8]<=[8], use_global_device_ids=true, to_apply=%add, metadata={op_name="jit(f)/jit(main)/while/body/psum" stack_frame_id=1}
  %all-gather.1 = f32[64,4]{1,0} all-gather(f32[16,4]{1,0} %Arg_1.2), channel_id=2, replica_groups=[2,4]<=[4,2]T(1,0), dimensions={0}, metadata={op_name="jit(f)/jit(main)/gather" stack_frame_id=2}
  %all-to-all.1 = (f32[2,4]{1,0}, f32[2,4]{1,0}) all-to-all(f32[2,4]{1,0} %s.1, f32[2,4]{1,0} %s.2), channel_id=3, replica_groups={{0,1},{2,3},{4,5},{6,7}}, metadata={op_name="jit(f)/jit(main)/a2a" stack_frame_id=3}
  %collective-permute-start.1 = f32[4,4]{1,0} collective-permute-start(f32[4,4]{1,0} %q.1), channel_id=4, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}, metadata={op_name="jit(f)/jit(main)/while/body/ppermute" stack_frame_id=4}
  %collective-permute-done.1 = f32[4,4]{1,0} collective-permute-done(f32[4,4]{1,0} %collective-permute-start.1)
}
"""


def test_parser_kinds_groups_and_loop_detection():
    mesh = _mesh(8, ("data", "model"))  # 4x2 grid, flat-order ids
    entries = cc.parse_collectives(_FAKE_HLO, mesh=mesh, loop_trips=3)
    by_kind = {e.kind: e for e in entries}
    assert sorted(by_kind) == ["all-gather", "all-reduce", "all-to-all",
                               "collective-permute"]
    ar = by_kind["all-reduce"]
    assert ar.bytes == 32 and ar.group_size == 8
    assert ar.axes == ("data", "model")       # the full-mesh group
    assert ar.in_loop and ar.multiplier == 3  # /while/ path, 3 trips
    assert ar.provenance == "a.py:3"
    ag = by_kind["all-gather"]
    assert ag.bytes == 64 * 4 * 4
    assert ag.axes == ("data",)               # iota T(1,0): the data axis
    assert not ag.in_loop and ag.multiplier == 1
    assert ag.operand_params == ["batch"]     # consumes an entry param
    a2a = by_kind["all-to-all"]
    assert a2a.bytes == 2 * (2 * 4 * 4)       # TUPLE type: both operands
    assert a2a.axes == ("model",)             # explicit {{0,1},...} groups
    cp = by_kind["collective-permute"]        # -start counted, -done not
    assert cp.bytes == 4 * 4 * 4
    assert cp.in_loop and cp.multiplier == 3
    assert len([e for e in entries if e.kind == "collective-permute"]) == 1


_ASYNC_HLO = """HloModule t, is_scheduled=true, entry_computation_layout={(f32[8,4]{1,0})->f32[32,4]{1,0}}

FileNames
1 "a.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=4 end_line=4 column=1 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}


ENTRY %main.1 (p0: f32[8,4]) -> f32[32,4] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %all-gather-start.1 = (f32[8,4]{1,0}, f32[32,4]{1,0}) all-gather-start(f32[8,4]{1,0} %p0.copy), channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}, metadata={op_name="jit(f)/ag" stack_frame_id=1}
  %all-gather-done.1 = f32[32,4]{1,0} all-gather-done((f32[8,4]{1,0}, f32[32,4]{1,0}) %all-gather-start.1)
}
"""


def test_parser_async_start_uses_done_result_type_not_tuple_sum():
    """An async -start's own result type bundles operand AND result
    ((f32[shard], f32[full]) for all-gather-start): the payload must be
    the -done's single result type, not the tuple sum (which would
    double-count on TPU, where async pairs are the default)."""
    entries = cc.parse_collectives(_ASYNC_HLO)
    assert len(entries) == 1
    ag = entries[0]
    assert ag.kind == "all-gather"
    assert ag.bytes == 32 * 4 * 4        # the gathered result ONLY
    assert ag.group_size == 4
    # with the -done line stripped, the largest-tuple-element fallback
    # still avoids the operand+result double count
    stripped = "\n".join(ln for ln in _ASYNC_HLO.splitlines()
                         if "all-gather-done" not in ln)
    entries2 = cc.parse_collectives(stripped)
    assert entries2[0].bytes == 32 * 4 * 4


def test_hlo_unavailable_is_not_a_clean_audit(tmp_path):
    """If the executable's HLO text cannot be read, the empty inventory
    is absence of EVIDENCE: the report says so, the roofline claims
    nothing, and the drift gate fails the program instead of reading a
    pinned-20-collectives program as a 'nice shrink' to zero."""
    class FakeCompiled:
        def as_text(self):
            raise RuntimeError("text unavailable on this backend")

        def cost_analysis(self):
            return {"flops": 1e9}

    rep = cc.analyze_compiled(FakeCompiled(), "gate/scan")
    assert rep.hlo_unavailable
    assert rep.entries == []
    assert rep.predicted_efficiency is None   # no 1.0 claim
    path = str(tmp_path / "b.json")
    cc.write_baseline({"gate/scan": _fake_report("gate/scan", 20, 4096)},
                      path)
    failures, notes = cc.compare_baseline({"gate/scan": rep}, path)
    assert len(failures) == 1
    assert "absence of evidence" in failures[0]
    assert not any("shrank" in n for n in notes)
    # the write path refuses too: a fabricated zero must never be pinned
    with pytest.raises(MXNetError, match="fabricated"):
        cc.write_baseline({"gate/scan": rep}, str(tmp_path / "b2.json"))
    # and the armed dispatch hook does not pass vacuously
    from mxnet_tpu import engine
    prev = engine.set_commscheck("error")
    try:
        cc._AUDITED.discard("blind-prog")

        class FakeJit:
            def lower(self, *a, **k):
                return self

            def compile(self):
                return FakeCompiled()

        with pytest.raises(MXNetError, match="unavailable"):
            cc.maybe_audit_dispatch("blind-prog", FakeJit(), ())
    finally:
        engine.set_commscheck(prev if prev != "off" else None)


def test_parser_empty_replica_groups_defaults_to_whole_mesh():
    """The bare ``replica_groups={}`` spelling means every device in one
    group: the entry must price real wire bytes (whole-mesh group), not
    silently zero out the roofline; with no mesh at all, an unknown
    group still charges one full payload."""
    txt = ("ENTRY %main.1 (p0: f32[1024]) -> f32[1024] {\n"
           "  %p0 = f32[1024]{0} parameter(0)\n"
           "  %all-reduce.9 = f32[1024]{0} all-reduce(f32[1024]{0} %x.1),"
           " channel_id=1, replica_groups={}, use_global_device_ids=true,"
           " to_apply=%add\n}\n")
    mesh = _mesh(8)
    (e,) = cc.parse_collectives(txt, mesh=mesh)
    assert e.group_size == 8
    assert e.axes == ("data",)
    assert e.wire_bytes == cc._wire_bytes("all-reduce", 4096, 8) > 0
    (e2,) = cc.parse_collectives(txt)
    assert e2.group_size is None
    assert e2.wire_bytes == e2.bytes == 4096  # full payload, never zero


def test_parser_tuple_type_with_tpu_tiled_layouts():
    """TPU layouts carry tiling parens inside the braces: a tuple-typed
    combined all-reduce like ``(bf16[256,256]{1,0:T(8,128)}, ...)`` must
    still parse (a lazy type match would truncate at ``T(…)``'s paren
    and the dominant gradient all-reduce would vanish from the
    inventory)."""
    txt = ("ENTRY %main.1 (p0: bf16[256,256]) -> bf16[256,256] {\n"
           "  %all-reduce.3 = (bf16[256,256]{1,0:T(8,128)}, "
           "bf16[256]{0:T(256)}) all-reduce(bf16[256,256]{1,0:T(8,128)} "
           "%a.1, bf16[256]{0:T(256)} %b.1), channel_id=1, "
           "replica_groups={{0,1,2,3}}, to_apply=%add, "
           "metadata={op_name=\"jit(f)/psum\"}\n}\n")
    (e,) = cc.parse_collectives(txt)
    assert e.kind == "all-reduce"
    assert e.bytes == 256 * 256 * 2 + 256 * 2  # both tuple elements
    assert e.group_size == 4


def test_wire_bytes_model():
    # ring-algorithm costs: all-reduce 2(n-1)/n, gather (n-1)/n x result,
    # reduce-scatter (n-1) x its scattered result, ppermute one hop
    assert cc._wire_bytes("all-reduce", 800, 8) == 1400
    assert cc._wire_bytes("all-gather", 800, 8) == 700
    assert cc._wire_bytes("reduce-scatter", 100, 8) == 700
    assert cc._wire_bytes("collective-permute", 800, None) == 800
    assert cc._wire_bytes("all-reduce", 800, 1) == 0


def test_report_totals_and_efficiency_bounds():
    mesh = _mesh(8, ("data", "model"))
    entries = cc.parse_collectives(_FAKE_HLO, mesh=mesh, loop_trips=3)
    rep = cc.CommsReport("fake", "cpu", 8, entries, loop_trips=3,
                         flops=1e9)
    assert rep.collective_count == sum(e.multiplier for e in entries)
    assert rep.collective_bytes == sum(e.bytes * e.multiplier
                                       for e in entries)
    assert 0.0 < rep.predicted_efficiency < 1.0
    assert rep.compute_seconds > 0
    d = rep.as_dict()
    assert d["collective_count"] == rep.collective_count
    assert d["counts_by_kind"]["all-reduce"] == 3
    # collective-free program: efficiency is exactly 1.0
    empty = cc.CommsReport("empty", "cpu", 1, [], flops=1e9)
    assert empty.predicted_efficiency == 1.0
    # collectives but no cost-model FLOPs: no claim, not a guess
    blind = cc.CommsReport("blind", "cpu", 8, entries, flops=None)
    assert blind.predicted_efficiency is None


# ---------------------------------------------------------------------------
# comms signatures of the parallel stack
# ---------------------------------------------------------------------------

def _seq_spec():
    return P(None, None, "seq", None)


def test_ring_attention_signature_ppermute_only():
    """Ring attention rotates K/V via ppermute over neighbor links — its
    compiled signature is collective-permute ONLY (in the ring loop, on
    the 'seq' axis); an all-gather would mean the ring degenerated into
    every chip holding the full sequence."""
    from mxnet_tpu.parallel import ring as pring
    n = min(4, len(jax.devices()))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("seq",))
    fn = jax.shard_map(
        functools.partial(pring.ring_attention, axis_name="seq",
                          causal=True),
        mesh=mesh, in_specs=(_seq_spec(),) * 3, out_specs=_seq_spec(),
        check_vma=False)
    q = _sds((2, 4, 8 * n, 8), mesh, _seq_spec())
    rep = cc.analyze(jax.jit(fn), (q, q, q), name="ring-attn", mesh=mesh)
    counts = rep.counts_by_kind()
    assert counts == {"collective-permute": 2}  # the K and V rotations
    assert all(e.in_loop and e.axes == ("seq",) for e in rep.entries)
    findings = cc.lint_report(rep, min_eff=0.0)
    assert [f for f in findings if f.lint == "gather-in-loop"] == []


def test_ulysses_signature_all_to_all_only():
    """Ulysses converts sequence sharding to head sharding and back: 3
    input all-to-alls (q, k, v) + 1 output all-to-all per attention, and
    nothing else — no all-gather, no ppermute."""
    from mxnet_tpu.parallel import ring as pring
    n = min(4, len(jax.devices()))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("seq",))
    fn = jax.shard_map(
        functools.partial(pring.ulysses_attention, axis_name="seq"),
        mesh=mesh, in_specs=(_seq_spec(),) * 3, out_specs=_seq_spec(),
        check_vma=False)
    q = _sds((2, n, 8 * n, 8), mesh, _seq_spec())
    rep = cc.analyze(jax.jit(fn), (q, q, q), name="ulysses", mesh=mesh)
    assert rep.counts_by_kind() == {"all-to-all": 4}
    assert all(e.axes == ("seq",) for e in rep.entries)


def test_pipeline_spmd_signature_ppermute_ring_plus_final_psum():
    """The GPipe schedule: activations hop stage-to-stage via ppermute
    INSIDE the tick loop; one all-reduce (the last-stage output share)
    outside it. Both allowed — gather-in-loop stays clean."""
    from mxnet_tpu.parallel.pipeline import pipeline_apply
    n = min(4, len(jax.devices()))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("pipe",))

    def stage(p, x):
        return jnp.tanh(x @ p["w"])

    params = {"w": jax.ShapeDtypeStruct((n, 16, 16), np.float32)}
    batch = jax.ShapeDtypeStruct((8, 16), np.float32)

    def pfn(p, b):
        return pipeline_apply(stage, p, b, mesh, axis_name="pipe")

    rep = cc.analyze(jax.jit(pfn), (params, batch), name="pipeline",
                     mesh=mesh)
    counts = rep.counts_by_kind()
    assert counts.get("collective-permute") == 1
    assert counts.get("all-reduce") == 1
    cp = [e for e in rep.entries if e.kind == "collective-permute"][0]
    assert cp.in_loop
    findings = cc.lint_report(rep, min_eff=0.0)
    assert [f for f in findings if f.lint == "gather-in-loop"] == []


@pytest.fixture(scope="module")
def dp_scan_audit():
    """One compile of a data-parallel fused-scan program shared by the
    signature/lint tests (args carry real shardings, state built with
    the no-op initializer — nothing executes)."""
    from mxnet_tpu import models
    from mxnet_tpu.train_step import TrainStep
    from mxnet_tpu.parallel.mesh import data_parallel_mesh
    n = min(4, len(jax.devices()))
    mesh = data_parallel_mesh(n)
    ts = TrainStep(models.mlp(num_classes=4, hidden=(32,)),
                   optimizer="sgd", learning_rate=0.1, momentum=0.9,
                   mesh=mesh)
    batch, k = 8 * n, 2
    state = ts.init({"data": (batch, 64)}, {"softmax_label": (batch,)},
                    initializer=lambda desc, arr: None, seed=0)
    st = cc.struct_args(state)
    sb_spec = P(None, "data")
    sb = {"data": _sds((k, batch, 64), mesh, sb_spec),
          "softmax_label": _sds((k, batch), mesh, sb_spec)}
    args = (st, sb, ts._dispatch_key(), _sds((k,), mesh, P()))
    return cc.check_program(ts._build_scan(batch, k), args,
                            name="dp-mlp-scan", mesh=mesh, loop_trips=k,
                            min_eff=0.0)


def test_dp_scan_syncs_by_in_loop_all_reduce_only(dp_scan_audit):
    """The PR 7 contract, now statically pinned: the partitioned K-step
    scan syncs by all-reduce inside the while body (grad + metric psum)
    and nothing else — and every in-loop entry carries the K
    multiplier."""
    findings, rep = dp_scan_audit
    assert rep.collective_count > 0
    assert set(rep.counts_by_kind()) == {"all-reduce"}
    assert all(e.in_loop and e.multiplier == 2 for e in rep.entries)
    assert all(e.axes == ("data",) for e in rep.entries)
    assert 0.0 < rep.predicted_efficiency <= 1.0
    assert findings == []


def test_zoo_single_device_program_has_empty_inventory():
    findings, reports = cc.check_zoo(names=["mlp"], k=2, guard=False)
    assert findings == []
    for rep in reports.values():
        assert rep.entries == []
        assert rep.collective_count == 0
        assert rep.predicted_efficiency == 1.0


# ---------------------------------------------------------------------------
# seeded violations — one per comms lint class
# ---------------------------------------------------------------------------

def _gather_in_scan_program(n=4):
    """The regression the drift gate exists for: an all_gather inside
    the scan body."""
    from jax import shard_map
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("data",))

    def bad(xs):
        def body(c, x):
            g = jax.lax.all_gather(x, "data")
            return c + jnp.sum(g), None
        out, _ = jax.lax.scan(body, jnp.float32(0), xs)
        return out

    sm = shard_map(bad, mesh=mesh, in_specs=P(None, "data"), out_specs=P(),
                   check_vma=False)
    xs = _sds((3, 8 * n), mesh, P(None, "data"))
    return jax.jit(sm), (xs,), mesh


def test_gather_in_loop_finding_seeded():
    fn, args, mesh = _gather_in_scan_program()
    findings, rep = cc.check_program(fn, args, name="seeded-gather",
                                     mesh=mesh, loop_trips=3, min_eff=0.0)
    hits = [f for f in findings if f.lint == "gather-in-loop"]
    assert len(hits) == 1
    assert "/while/" in hits[0].op_path
    assert hits[0].provenance and "test_commscheck" in hits[0].provenance
    assert "x3 per dispatch" in hits[0].message
    # and tracecheck's collective-in-scan stays a working thin alias over
    # the same inventory pass (same program, historical lint id)
    alias = tc.check_collectives(fn, args, name="seeded-gather")
    assert [f.lint for f in alias] == ["collective-in-scan"]
    assert "/while/" in alias[0].op_path


def test_resharding_copy_finding_seeded():
    """An entry argument declared sharded but consumed replicated: the
    partitioner re-lays it out (an all-gather on the parameter) before
    first use — the silent copy PR 7's pre-sharded landing eliminated."""
    n = min(4, len(jax.devices()))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("data",))

    def f(x):
        y = jax.lax.with_sharding_constraint(x, _ns(mesh, P()))
        return jnp.sum(y)

    x = _sds((1024, 64), mesh, P("data"))
    findings, rep = cc.check_program(jax.jit(f), (x,), name="seeded-reshard",
                                     mesh=mesh, min_eff=0.0,
                                     repl_threshold=1 << 30)
    hits = [f_ for f_ in findings if f_.lint == "resharding-copy"]
    assert len(hits) == 1
    assert "'x'" in hits[0].message          # names the argument
    assert "all-gather" in hits[0].message
    assert hits[0].op_path
    assert hits[0].provenance and "test_commscheck" in hits[0].provenance


def test_replicated_large_finding_seeded():
    n = min(4, len(jax.devices()))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("data",))

    def f(x):
        h = x * jnp.float32(2.0)  # sharded intermediate...
        return jax.lax.with_sharding_constraint(h, _ns(mesh, P()))

    x = _sds((1024, 64), mesh, P("data"))
    findings, rep = cc.check_program(jax.jit(f), (x,), name="seeded-repl",
                                     mesh=mesh, min_eff=0.0,
                                     repl_threshold=64 << 10)
    hits = [f_ for f_ in findings if f_.lint == "replicated-large"]
    assert len(hits) == 1
    assert "MXTPU_COMMSCHECK_REPL_BYTES" in hits[0].message
    assert "axis data" in hits[0].message
    assert hits[0].provenance and "test_commscheck" in hits[0].provenance


def test_comms_bound_finding_seeded():
    """A comm-heavy loop against a high floor: the roofline flags the
    program as communication-bound WITH the inventory attached."""
    fn, args, mesh = _gather_in_scan_program()
    findings, rep = cc.check_program(fn, args, name="seeded-bound",
                                     mesh=mesh, loop_trips=3,
                                     min_eff=0.999)
    hits = [f for f in findings if f.lint == "comms-bound"]
    assert len(hits) == 1
    assert "MXTPU_COMMSCHECK_MIN_EFF" in hits[0].message
    assert "Inventory:" in hits[0].message
    assert "all-gather" in hits[0].message   # the inventory rides along
    assert rep.predicted_efficiency < 0.999


def test_comms_lints_suppressible_via_shared_registry():
    tok = tc.add_suppression("gather-in-loop", program="seeded-gather")
    try:
        fn, args, mesh = _gather_in_scan_program()
        findings, _ = cc.check_program(fn, args, name="seeded-gather",
                                       mesh=mesh, loop_trips=3,
                                       min_eff=0.0)
        hits = [f for f in findings if f.lint == "gather-in-loop"]
        assert hits and all(f.suppressed for f in hits)
        assert cc.unsuppressed(hits) == []
    finally:
        tc.remove_suppression(tok)


# ---------------------------------------------------------------------------
# knobs + the runtime hook
# ---------------------------------------------------------------------------

def test_repl_bytes_and_min_eff_env(monkeypatch):
    monkeypatch.delenv("MXTPU_COMMSCHECK_REPL_BYTES", raising=False)
    assert cc.repl_bytes() == 1 << 20
    monkeypatch.setenv("MXTPU_COMMSCHECK_REPL_BYTES", "4M")
    assert cc.repl_bytes() == 4 << 20
    monkeypatch.setenv("MXTPU_COMMSCHECK_REPL_BYTES", "banana")
    with pytest.raises(MXNetError, match="MXTPU_COMMSCHECK_REPL_BYTES"):
        cc.repl_bytes()
    monkeypatch.delenv("MXTPU_COMMSCHECK_MIN_EFF", raising=False)
    assert cc.min_efficiency() == 0.5
    monkeypatch.setenv("MXTPU_COMMSCHECK_MIN_EFF", "0.8")
    assert cc.min_efficiency() == 0.8


def test_commscheck_mode_knob(monkeypatch):
    from mxnet_tpu import engine
    engine.set_commscheck(None)
    monkeypatch.delenv("MXTPU_COMMSCHECK", raising=False)
    assert engine.commscheck_mode() == "off"
    monkeypatch.setenv("MXTPU_COMMSCHECK", "warn")
    assert engine.commscheck_mode() == "warn"
    monkeypatch.setenv("MXTPU_COMMSCHECK", "error")
    assert engine.commscheck_mode() == "error"
    monkeypatch.setenv("MXTPU_COMMSCHECK", "banana")
    with pytest.raises(MXNetError, match="MXTPU_COMMSCHECK"):
        engine.commscheck_mode()
    monkeypatch.delenv("MXTPU_COMMSCHECK", raising=False)
    prev = engine.set_commscheck("error")
    try:
        assert engine.commscheck_mode() == "error"
    finally:
        engine.set_commscheck(prev if prev != "off" else None)


def _dp_train_step(n=2):
    from mxnet_tpu import models
    from mxnet_tpu.train_step import TrainStep
    from mxnet_tpu.parallel.mesh import data_parallel_mesh
    mesh = data_parallel_mesh(n)
    ts = TrainStep(models.mlp(num_classes=4, hidden=(16,)),
                   optimizer="sgd", learning_rate=0.1, mesh=mesh)
    batch, k = 4 * n, 2
    state = ts.init({"data": (batch, 16)}, {"softmax_label": (batch,)})
    rng = np.random.default_rng(0)
    sb = ts.shard_superbatch({
        "data": rng.normal(size=(k, batch, 16)).astype(np.float32),
        "softmax_label": rng.integers(0, 4, (k, batch))
        .astype(np.float32)})
    return ts, state, sb


def test_dispatch_hook_audits_sharded_program_once(monkeypatch):
    """MXTPU_COMMSCHECK=warn: the first dispatch of a sharded program
    runs the comms audit once (one extra compile) and registers the
    program as audited; clean programs log nothing and training
    proceeds."""
    from mxnet_tpu import engine
    prev = engine.set_commscheck("warn")
    try:
        before = set(cc._AUDITED)
        ts, state, sb = _dp_train_step()
        state, m = ts.run_steps(state, sb)
        new = set(cc._AUDITED) - before
        assert len(new) == 1 and "scan" in next(iter(new))
        # second dispatch: memoized, no re-audit
        state, m = ts.run_steps(state, sb)
        assert set(cc._AUDITED) - before == new
        assert m.num_samples > 0
    finally:
        engine.set_commscheck(prev if prev != "off" else None)


def test_dispatch_hook_error_mode_raises_on_finding(monkeypatch):
    """MXTPU_COMMSCHECK=error + an impossible efficiency floor: the
    first sharded dispatch fails fast with the comms findings instead of
    burning a slow multichip run."""
    from mxnet_tpu import engine
    monkeypatch.setenv("MXTPU_COMMSCHECK_MIN_EFF", "0.9999")
    prev = engine.set_commscheck("error")
    try:
        ts, state, sb = _dp_train_step()
        with pytest.raises(MXNetError, match="comms-bound"):
            ts.run_steps(state, sb)
    finally:
        engine.set_commscheck(prev if prev != "off" else None)


def test_dispatch_hook_off_by_default(monkeypatch):
    from mxnet_tpu import engine
    engine.set_commscheck(None)
    monkeypatch.delenv("MXTPU_COMMSCHECK", raising=False)
    before = set(cc._AUDITED)
    ts, state, sb = _dp_train_step()
    ts.run_steps(state, sb)
    assert set(cc._AUDITED) == before


# ---------------------------------------------------------------------------
# the baseline drift gate (ci/commscheck.sh contract)
# ---------------------------------------------------------------------------

def _fake_report(name, count=0, nbytes=0, in_loop=True, kind="all-reduce",
                 prov=None):
    entries = []
    for i in range(count):
        entries.append(cc.CollectiveEntry(
            "%s.%d" % (kind, i), kind, nbytes // max(1, count),
            nbytes // max(1, count), 8, ("data",), None, in_loop, 1,
            "jit(f)/jit(main)/while/body/op", prov))
    return cc.CommsReport(name, jax.devices()[0].platform, 8, entries,
                          flops=1e9)


def test_baseline_roundtrip_passes(tmp_path):
    reports = {"a/scan[k=2]": _fake_report("a/scan[k=2]", 3, 3000),
               "b/step": _fake_report("b/step", 0, 0)}
    path = str(tmp_path / "b.json")
    cc.write_baseline(reports, path)
    failures, notes = cc.compare_baseline(reports, path)
    assert failures == []
    assert notes == []


def test_baseline_fails_seeded_in_scan_all_gather_with_provenance(tmp_path):
    """The acceptance contract: a baseline pinned on the clean psum-only
    scan FAILS when the same program grows an in-scan all-gather — with
    the gather's byte count and source provenance in the failure."""
    fn, args, mesh = _gather_in_scan_program()
    clean = {"gate/scan": _fake_report("gate/scan", 2, 2048)}
    path = str(tmp_path / "b.json")
    cc.write_baseline(clean, path)
    regressed = cc.analyze(fn, args, name="gate/scan", mesh=mesh,
                           loop_trips=3)
    assert any(e.kind == "all-gather" for e in regressed.entries)
    failures, _ = cc.compare_baseline({"gate/scan": regressed}, path)
    assert failures
    joined = "\n".join(failures)
    assert "collective_count grew" in joined or \
        "collective_bytes grew" in joined
    assert "all-gather" in joined            # the inventory rides along
    assert "test_commscheck" in joined       # ...with provenance
    assert "MXTPU_COMMSCHECK_TOL" in joined


def test_baseline_zero_pinned_program_fails_on_first_collective(tmp_path):
    """A single-device zoo program pins ZERO collectives — counts are
    HLO-deterministic, so there is no absolute slack and the first
    collective to appear fails at any tolerance."""
    path = str(tmp_path / "b.json")
    cc.write_baseline({"mlp/step": _fake_report("mlp/step", 0, 0)}, path)
    failures, _ = cc.compare_baseline(
        {"mlp/step": _fake_report("mlp/step", 1, 8)}, path, tol=10.0)
    assert len(failures) == 2  # count AND bytes grew past 0


def test_baseline_missing_stale_platform_and_shrink(tmp_path):
    reports = {"a/step": _fake_report("a/step", 4, 4096)}
    path = str(tmp_path / "b.json")
    cc.write_baseline(reports, path)
    # missing program fails, stale entry is a note
    failures, notes = cc.compare_baseline(
        {"a/step": reports["a/step"],
         "new/step": _fake_report("new/step", 1, 8)}, path)
    assert len(failures) == 1 and "new/step" in failures[0]
    assert "--write-baseline" in failures[0]
    failures2, notes2 = cc.compare_baseline({}, path)
    assert failures2 == []
    assert any("stale" in n for n in notes2)
    # platform mismatch skips the gate with one note
    failures3, notes3 = cc.compare_baseline(reports, {
        "platform": "tpu", "tolerance": 0.1,
        "programs": {"a/step": {"collective_count": 1,
                                "collective_bytes": 1}}})
    assert failures3 == []
    assert len(notes3) == 1 and "platform" in notes3[0]
    # shrink is a note, not a failure
    failures4, notes4 = cc.compare_baseline(
        {"a/step": _fake_report("a/step", 1, 1024)}, path)
    assert failures4 == []
    assert any("shrank" in n for n in notes4)
    # ...but a TOTAL collapse to zero on a nonzero-pinned program fails:
    # indistinguishable from a parser gone blind on an HLO format drift
    failures5, _ = cc.compare_baseline(
        {"a/step": _fake_report("a/step", 0, 0)}, path)
    assert len(failures5) == 2
    assert all("collapsed" in f for f in failures5)


def test_baseline_tol_env_overrides_stored_band(tmp_path, monkeypatch):
    reports = {"a/step": _fake_report("a/step", 10, 10240)}
    path = str(tmp_path / "b.json")
    cc.write_baseline(reports, path, tol=0.1)
    grown = {"a/step": _fake_report("a/step", 13, 13312)}
    monkeypatch.delenv("MXTPU_COMMSCHECK_TOL", raising=False)
    failures, _ = cc.compare_baseline(grown, path)
    assert failures  # +30% past the stored 10% band
    monkeypatch.setenv("MXTPU_COMMSCHECK_TOL", "0.5")
    failures, _ = cc.compare_baseline(grown, path)
    assert failures == []  # env-widened band wins


# ---------------------------------------------------------------------------
# CLI (tier-1 smoke of the ci/commscheck.sh gate)
# ---------------------------------------------------------------------------

def test_cli_smoke_json_mlp_lenet(capsys):
    """The tier-1 mirror of the full-zoo CI gate: mlp + lenet in json
    mode exit 0 with zero findings and ZERO collectives on every
    single-device program."""
    rc = cc.main(["--models", "mlp,lenet", "--json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert data["findings"] == []
    assert data["suppressed"] == 0
    assert len(data["programs"]) == 8
    for rep in data["programs"].values():
        assert rep["collective_count"] == 0
        assert rep["collective_bytes"] == 0
        assert rep["predicted_efficiency"] == 1.0
    assert data["platform"] == jax.devices()[0].platform


def test_cli_fails_on_hlo_unavailable_even_without_baseline(
        capsys, monkeypatch):
    """The absence-of-evidence contract holds in the no-baseline CLI
    modes too (the model-subset smoke): a backend where as_text() fails
    must not print PASS over an audit that saw no HLO."""
    blind = cc.CommsReport("mlp/step", jax.devices()[0].platform, 1, [],
                           hlo_unavailable=True)
    monkeypatch.setattr(cc, "check_zoo",
                        lambda **kw: ([], {"mlp/step": blind}))
    rc = cc.main(["--models", "mlp", "--json"])
    data = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert any("absence of evidence" in f
               for f in data["baseline_failures"])
    assert data["programs"]["mlp/step"]["hlo_unavailable"] is True


def test_cli_list_and_bad_model(capsys):
    assert cc.main(["--list"]) == 0
    assert "mlp" in capsys.readouterr().out
    with pytest.raises(MXNetError, match="unknown zoo model"):
        cc.main(["--models", "nope"])


def test_cli_write_and_gate_baseline(tmp_path, capsys):
    path = str(tmp_path / "b.json")
    rc = cc.main(["--models", "mlp", "--quiet", "--write-baseline", path])
    capsys.readouterr()
    assert rc == 0
    rc = cc.main(["--models", "mlp", "--quiet", "--baseline", path])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 baseline regression(s)" in out
    # a baseline claiming programs this CLI run does not audit: failure
    # comes only from the MISSING direction (deliberate-add contract)
    with open(path) as f:
        base = json.load(f)
    base["programs"]["ghost/step"] = {"collective_count": 0,
                                      "collective_bytes": 0}
    with open(path, "w") as f:
        json.dump(base, f)
    rc = cc.main(["--models", "mlp", "--quiet", "--baseline", path])
    out = capsys.readouterr().out
    assert rc == 0  # stale entries are notes, not failures
    assert "stale" in out


def test_sharded_programs_reject_insufficient_devices():
    if len(jax.devices()) >= 64:
        pytest.skip("cannot provoke the under-provisioned error here")
    with pytest.raises(MXNetError, match="xla_force_host_platform"):
        cc.sharded_programs(n_devices=64)
