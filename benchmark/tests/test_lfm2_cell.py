"""The files the ``lfm2-24b-a2b-ep8`` configuration brings: its work
functions against counts made by hand at the published sizes, and its
scope readers on the recorded chip trace (``testdata/decode_small``: a
small OPT program's, so the scope table is made up here: these check the
readers' arithmetic and what they do with nothing to read; the values are
not device measurements of any cell)."""
import json
import os

import pytest

from benchmark.harness import cells, peaks, tracered

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "testdata")
E, KV, VOCAB, DENSE, MOE = 2048, 512, 65536, 11776, 1536


def load_cfg():
    return cells.load_json(os.path.join(BENCH, "configs",
                                        "lfm2-24b-a2b-ep8.json"))


def load_ref():
    return cells.load_module(os.path.join(BENCH, "reference",
                                          "lfm2-24b-a2b-ep8.py"))


cfg = pytest.fixture(scope="module")(load_cfg)
ref = pytest.fixture(scope="module")(load_ref)


def reader(name):
    return cells.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def test_the_cut_is_what_the_issue_reckoned(cfg, ref):
    conv = 4 * E * E + 3 * E + E              # in, out, kernel, op norm
    attn = 2 * E * E + 2 * E * KV + E + 2 * 64
    expert = 3 * E * MOE
    router = E * 64 + 64 + E                  # weight, bias, ffn norm
    want = (30 * conv + 10 * attn + 2 * (3 * E * DENSE + E)
            + 38 * (8 * expert + router) + VOCAB * E + E)
    assert ref.param_count(cfg) == want
    assert abs(want - 3761e6) < 1e6           # 7.52 GB in bfloat16
    assert abs(38 * 8 * expert - 2869e6) < 1e6
    assert ref.weight_bytes(cfg) == 2 * want  # tied: the table IS the head


def test_the_three_work_functions_against_hand_counts(cfg, ref):
    f, b = ref.conv_layer_work(cfg, 64)
    assert f == 30 * 64 * (2 * 4 * E * E + 2 * 3 * E + 2 * E)
    assert b == 30 * (2 * (4 * E * E + 3 * E + E) + 64 * 3 * E * 2)
    ctx = [100] * 60 + [7] * 4
    f, b = ref.attn_layer_work(cfg, ctx)
    assert f == 10 * sum(2 * (2 * E * E + 2 * E * KV) + 4 * E * c
                         for c in ctx)
    assert b == 10 * (2 * (2 * E * E + 2 * E * KV + E + 128)
                      + sum((c + 1) * 2 * KV * 2 for c in ctx))
    f, b = ref.moe_layer_work(cfg, 64)
    # a position's top-4 of 64 meets the 8 held experts 0.5 times
    assert f == 38 * 64 * 2 * (E * 64 + 0.5 * 3 * E * MOE)
    assert b == 38 * 2 * (E * 64 + 64 + E + 8 * 3 * E * MOE)
    f, b = ref.step_work(cfg, ctx)
    assert f == sum(ref.flops_per_position(cfg, c) for c in ctx)
    assert b == ref.weight_bytes(cfg) \
        + 10 * sum((c + 1) * 2 * KV * 2 for c in ctx) + 30 * 64 * 3 * E * 2
    per = ref.flops_per_position(cfg, 1)
    assert abs(per - 2 * (30 * 4 * E * E + 10 * (2 * E * E + 2 * E * KV)
                          + 2 * 3 * E * DENSE
                          + 38 * (E * 64 + 0.5 * 3 * E * MOE)
                          + E * VOCAB)) < 1e6
    # the step is bound by its bytes: 7.5 GB of weights at 819 GB/s
    assert b / 819e9 > 10 * f / 197e12


class FakeRun(object):
    peaks = peaks.peaks_for("TPU v5 lite")

    def perf_to_trace_ns(self, t):
        return int(t * 1e9)


def make_ctx(ref):
    trace = tracered.Trace.from_file(
        os.path.join(DATA, "decode_small.xplane.pb"))
    with open(os.path.join(DATA, "decode_small.json")) as f:
        side = json.load(f)
    spans = [tuple(s) for s in side["spans"]]
    t0, t1 = side["expected"]["window_ns"]
    rids = sorted({r for _, _, _, a in spans for r in a.get("reqs", ())})
    records = [{"rid": r, "index": i, "prompt_len": 3 + i, "new": 5 + i,
                "due": t0 / 1e9, "measured": True}
               for i, r in enumerate(rids)]
    tiny = {"hidden_size": 256, "num_attention_heads": 4,
            "num_key_value_heads": 2, "num_hidden_layers": 4,
            "vocab_size": 1024, "intermediate_size": 512,
            "moe_intermediate_size": 128, "num_experts": 2,
            "num_experts_per_tok": 2, "router_width": 8,
            "num_dense_layers": 1, "conv_L_cache": 3,
            "layer_types": ["conv", "conv", "full_attention", "conv"],
            "program": "jit_decode_fn", "serve": {"slots": 4}}
    ops = trace.devices[sorted(trace.devices)[0]]["ops"]
    names = sorted({n for n, _, _ in ops})
    kinds = ("layer/conv", "cache_write/conv", "layer/attn", "cache_write/kv",
             "layer/moe/router", "layer/moe/experts", "head")
    table = {n: kinds[i % len(kinds)] for i, n in enumerate(names)}
    program = ("loop_program", t0, t0 + 1000,
               {"program": "jit_decode_fn", "scopes": table,
                "state": {"conv": [3, 2, 256, "bfloat16", 12288]}})
    return {"run": FakeRun(), "cfg": tiny, "ref": ref, "records": records,
            "inside": records, "spans": spans + [program], "trace": trace,
            "window_ns": (t0, t1), "setup_compile_s": 2.5, "result": {}}


@pytest.fixture(scope="module")
def ctx(ref):
    return make_ctx(ref)


def test_the_scope_readers_on_the_recorded_trace(ctx):
    conv = reader("conv_layer_roofline").read(ctx)
    gqa = reader("gqa_layer_roofline").read(ctx)
    moe = reader("moe_layer_roofline").read(ctx)
    assert conv > 0 and gqa > 0 and moe > 0
    # the same steps' least time over a SUBSET of the step's device time
    whole = reader("decode_step_roofline.lfm2").read(ctx)
    assert 0 < whole < conv + gqa + moe
    assert 0 < reader("decode_mfu.lfm2").read(ctx) < 1
    # the writes are counted with their own operator, not with the other's
    only_conv = dict(ctx, spans=[
        s if s[0] != "loop_program" else s[:3] + (dict(s[3], scopes={
            k: v for k, v in s[3]["scopes"].items()
            if v != "cache_write/conv"}),) for s in ctx["spans"]])
    assert reader("conv_layer_roofline").read(only_conv) > conv
    assert reader("gqa_layer_roofline").read(only_conv) == gqa


def test_the_scope_readers_return_nothing_where_there_is_nothing(ctx, ref):
    # a program that sends no loop_program span (the parent commit)
    bare = dict(ctx, spans=[s for s in ctx["spans"]
                            if s[0] != "loop_program"])
    # a reference that has no such work function (another configuration's)
    other = cells.load_module(os.path.join(BENCH, "reference",
                                           "opt-1.3b.py"))
    for name in ("conv_layer_roofline", "gqa_layer_roofline"):
        assert reader(name).read(bare) is None, name
        assert reader(name).read(dict(ctx, ref=other)) is None, name
    empty = dict(ctx, spans=[], records=[], inside=[],
                 trace=tracered.Trace({}))
    for name in ("conv_layer_roofline", "gqa_layer_roofline",
                 "decode_step_roofline.lfm2", "decode_mfu.lfm2"):
        assert reader(name).read(empty) is None, name


def test_the_new_cell_and_metrics_are_declared(cfg):
    bench = cells.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = "lfm2-24b-a2b-ep8.batch_wide"
    mine = {m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [cell]}
    assert mine == {"conv_layer_roofline", "gqa_layer_roofline",
                    "decode_step_roofline.lfm2", "decode_mfu.lfm2"}
    for name in mine:
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))
    entry, = [c for c in bench["configs"] if c["name"] == cfg["name"]]
    assert entry["reduced"] == cfg["reduced"] == ["num_experts"]
