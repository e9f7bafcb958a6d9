"""The files the ``mellum2-12b-a2.5b-ep4`` configuration brings: its work
functions against counts made by hand at the published sizes, and its
readers on the recorded chip trace (``testdata/decode_small``: a small OPT
program's, so the scope table and the ring's span arguments are made up
here: these check the readers' arithmetic and what they do with nothing to
read; the values are not device measurements of any cell)."""
import json
import os

import pytest

from benchmark.harness import cells, peaks, tracered

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "testdata")
CELL = "mellum2-12b-a2.5b-ep4.long_generation"
E, Q, KV, HD, VOCAB, MOE, WINDOW = 2304, 4096, 512, 128, 98304, 896, 1024
NEW = ("decode_step_roofline.mellum2", "decode_mfu.mellum2",
       "window_attn_layer_roofline", "full_attn_layer_roofline",
       "ring_wrapped_share", "ring_rows_share")


def load_cfg():
    return cells.load_json(os.path.join(BENCH, "configs",
                                        "mellum2-12b-a2.5b-ep4.json"))


def load_ref():
    return cells.load_module(os.path.join(BENCH, "reference",
                                          "mellum2-12b-a2.5b-ep4.py"))


cfg = pytest.fixture(scope="module")(load_cfg)
ref = pytest.fixture(scope="module")(load_ref)


def reader(name):
    return cells.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def test_the_cut_is_what_the_issue_reckoned(cfg, ref):
    attn = 2 * E * Q + 2 * E * KV + E + 2 * HD   # q, out, k, v, op norm, q/k
    expert = 3 * E * MOE
    router = E * 64 + E                           # weight, ffn norm
    want = 28 * (attn + 16 * expert + router) + 2 * VOCAB * E + E
    assert ref.param_count(cfg) == want
    assert abs(want - 3826e6) < 1e6               # 7.65 GB in bfloat16
    assert abs(28 * 16 * expert - 2774e6) < 1e6   # 5.55 GB of held experts
    # untied: the head counts, the table a step reads a row a slot of does not
    assert ref.weight_bytes(cfg) == 2 * (want - VOCAB * E)
    # the uncut model: 12.15e9 parameters
    uncut = 28 * (attn + 64 * expert + router) + 2 * VOCAB * E + E
    assert abs(uncut - 12.15e9) < 0.01e9
    # the state at 32 slots and 4096 rows, bfloat16: the ring 1.41 GB, the
    # per-position rows 1.88 GB; weights + state 10.94 GB
    ring, full = 2 * 21 * 32 * WINDOW * KV * 2, 2 * 7 * 32 * 4096 * KV * 2
    assert abs(2 * want + ring + full - 10.94e9) < 0.01e9


def test_the_work_functions_against_hand_counts(cfg, ref):
    # contexts under, at and over the window
    ctx = [100] * 10 + [1024] * 2 + [3000] * 20
    win = [min(c, WINDOW) for c in ctx]
    matrices = 2 * (2 * E * Q + 2 * E * KV)
    f, b = ref.window_attn_layer_work(cfg, ctx)
    assert f == 21 * sum(matrices + 4 * Q * r for r in win)
    assert b == 21 * (2 * (2 * E * Q + 2 * E * KV + E + 2 * HD)
                      + sum((r + 1) * 2 * KV * 2 for r in win))
    f, b = ref.full_attn_layer_work(cfg, ctx)
    assert f == 7 * sum(matrices + 4 * Q * c for c in ctx)
    assert b == 7 * (2 * (2 * E * Q + 2 * E * KV + E + 2 * HD)
                     + sum((c + 1) * 2 * KV * 2 for c in ctx))
    # a position past the window reads no more of a window layer than one
    # at it: required bytes never count rows a position does not attend
    assert ref.window_attn_layer_work(cfg, [1024]) \
        == ref.window_attn_layer_work(cfg, [4000])
    assert ref.full_attn_layer_work(cfg, [1024])[1] \
        < ref.full_attn_layer_work(cfg, [4000])[1]
    f, b = ref.moe_layer_work(cfg, 32)
    # a position's top-8 of 64 meets the 16 held experts 2 times
    assert f == 28 * 32 * 2 * (E * 64 + 2 * 3 * E * MOE)
    assert b == 28 * 2 * (E * 64 + E + 16 * 3 * E * MOE)
    f, b = ref.step_work(cfg, ctx)
    assert f == sum(ref.flops_per_position(cfg, c) for c in ctx)
    assert b == ref.weight_bytes(cfg) \
        + 21 * sum((r + 1) * 2 * KV * 2 for r in win) \
        + 7 * sum((c + 1) * 2 * KV * 2 for c in ctx)
    per = ref.flops_per_position(cfg, 1)
    assert abs(per - 2 * (28 * (2 * E * Q + 2 * E * KV)
                          + 28 * (E * 64 + 2 * 3 * E * MOE)
                          + E * VOCAB)) < 1e6
    # the step is bound by its bytes: 7.2 GB of weights at 819 GB/s
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
    t0, t1 = side["expected"]["window_ns"]
    # what a loop with a ring says of each step: the ring's rung and the
    # full array's, and where each request stood (made up: every second
    # request past a window of 8)
    spans = []
    for name, s, e, args in (tuple(s) for s in side["spans"]):
        if name == "decode_step":
            n = len(args.get("reqs", ()))
            args = dict(args, pos=[3 + 9 * (i % 2) for i in range(n)],
                        n=[1] * n, emit=[1] * n, ring_rows=8, rows=32)
        spans.append((name, s, e, args))
    rids = sorted({r for _, _, _, a in spans for r in a.get("reqs", ())})
    records = [{"rid": r, "index": i, "prompt_len": 3 + i, "new": 5 + i,
                "due": t0 / 1e9, "measured": True}
               for i, r in enumerate(rids)]
    tiny = {"hidden_size": 256, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 64,
            "num_hidden_layers": 4, "vocab_size": 1024,
            "moe_intermediate_size": 128, "num_experts": 2,
            "num_experts_per_tok": 2, "router_width": 8,
            "sliding_window": 8,
            "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
            "program": "jit_decode_fn", "serve": {"slots": 4}}
    ops = trace.devices[sorted(trace.devices)[0]]["ops"]
    names = sorted({n for n, _, _ in ops})
    kinds = ("layer/attn/window", "cache_write/kv/window", "layer/attn/full",
             "cache_write/kv/full", "layer/moe/router", "layer/moe/experts",
             "head")
    table = {n: kinds[i % len(kinds)] for i, n in enumerate(names)}
    program = ("loop_program", t0, t0 + 1000,
               {"program": "jit_decode_fn", "scopes": table, "state": {}})
    return {"run": FakeRun(), "cfg": tiny, "ref": ref, "records": records,
            "inside": records, "spans": spans + [program], "trace": trace,
            "window_ns": (t0, t1), "setup_compile_s": 2.5, "result": {}}


@pytest.fixture(scope="module")
def ctx(ref):
    return make_ctx(ref)


def test_the_readers_on_the_recorded_trace(ctx):
    window = reader("window_attn_layer_roofline").read(ctx)
    full = reader("full_attn_layer_roofline").read(ctx)
    assert window > 0 and full > 0
    whole = reader("decode_step_roofline.mellum2").read(ctx)
    assert 0 < whole < window + full + reader("moe_layer_roofline").read(ctx)
    assert 0 < reader("decode_mfu.mellum2").read(ctx) < 1
    # the writes are counted with their own kind, not with the other's
    less = dict(ctx, spans=[
        s if s[0] != "loop_program" else s[:3] + (dict(s[3], scopes={
            k: v for k, v in s[3]["scopes"].items()
            if v != "cache_write/kv/window"}),) for s in ctx["spans"]])
    assert reader("window_attn_layer_roofline").read(less) > window
    assert reader("full_attn_layer_roofline").read(less) == full
    # every second request stands past the window; the ring covers 8 rows
    # where a per-position window layer would cover 32
    steps = [a for n, s, _, a in ctx["spans"] if n == "decode_step"
             and ctx["window_ns"][0] <= s < ctx["window_ns"][1]]
    stood = [p for a in steps for p in a["pos"]]
    assert reader("ring_wrapped_share").read(ctx) \
        == pytest.approx(100.0 * sum(p >= 8 for p in stood) / len(stood))
    assert 0 < reader("ring_wrapped_share").read(ctx) <= 50
    assert reader("ring_rows_share").read(ctx) == pytest.approx(25.0)


def test_the_readers_return_nothing_where_there_is_nothing(ctx, ref):
    # a program whose steps say nothing of a ring (the parent commit, or
    # another architecture's)
    bare = dict(ctx, spans=[
        s if s[0] != "decode_step" else s[:3] + ({
            k: v for k, v in s[3].items() if k != "ring_rows"},)
        for s in ctx["spans"] if s[0] != "loop_program"])
    # a reference that has no such work function (another configuration's)
    other = cells.load_module(os.path.join(BENCH, "reference",
                                           "lfm2-24b-a2b-ep8.py"))
    for name in ("window_attn_layer_roofline", "full_attn_layer_roofline"):
        assert reader(name).read(bare) is None, name
        assert reader(name).read(dict(ctx, ref=other)) is None, name
    for name in ("ring_wrapped_share", "ring_rows_share"):
        assert reader(name).read(bare) is None, name
    empty = dict(ctx, spans=[], records=[], inside=[],
                 trace=tracered.Trace({}))
    for name in NEW:
        assert reader(name).read(empty) is None, name


def test_the_new_cell_and_metrics_are_declared(cfg):
    bench = cells.load_json(os.path.join(REPO, "BENCHMARK.json"))
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) <= set(NEW)
    # the whole step's two shares and at least one of the attention kinds'
    assert {"decode_step_roofline.mellum2", "decode_mfu.mellum2"} <= set(mine)
    assert {"window_attn_layer_roofline", "full_attn_layer_roofline"} \
        & set(mine)
    for name, m in mine.items():
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))
        assert m["moves"] == "decode_tok_per_s" and m["unit"] == "%"
    entry, = [c for c in bench["configs"] if c["name"] == cfg["name"]]
    assert entry["reduced"] == cfg["reduced"] == ["num_experts"]
    assert entry["source"] == cfg["source"]
    mix = cells.load_json(os.path.join(BENCH, "traffic",
                                       "long_generation.json"))
    assert mix == {
        "loop": "closed", "clients": 64, "multiset": 64, "order": "fixed",
        "lead_completions": 8,
        "prompt_len": [[0, 64], [0.5, 128], [1, 256]],
        "new_tokens": [[0, 1024], [0.5, 2048], [1, 3072]]}
