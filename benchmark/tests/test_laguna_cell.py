"""The files the ``laguna-s-2.1-ep8`` configuration brings: its work
functions against counts made by hand at the published sizes, and its
readers on the recorded chip trace (``testdata/decode_small``, through the
context ``test_mellum2_cell.py`` makes of it: a small OPT program's, so the
scope table and the ring's span arguments are made up there: these check
the readers' arithmetic and what they do with nothing to read; the values
are not device measurements of any cell)."""
import os

import pytest

from benchmark.harness import cells, tracered

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "laguna-s-2.1-ep8.long_generation"
E, HD, KV, VOCAB, DENSE, MOE, WINDOW = 3072, 128, 1024, 12544, 12288, 1024, 512
HW, HF = 72, 48                   # query heads of a window and a full layer
NEW = ("decode_step_roofline.laguna", "decode_mfu.laguna")
#: the lists of the benchmark's existing metrics the cell joins
JOINS = ("compile_s", "decode_step_ms_p50", "slot_occupancy",
         "prompt_step_share", "decode_gap_feed_ms", "decode_gap_dispatch_ms",
         "decode_gap_readback_ms", "decode_gap_commit_ms",
         "decode_gap_covered", "emitted_tok_per_s", "token_gap_p99_ms",
         "moe_layer_roofline", "moe_here_share", "moe_load_max_over_mean",
         "window_attn_layer_roofline", "full_attn_layer_roofline",
         "ring_wrapped_share", "ring_rows_share")


def load_cfg():
    return cells.load_json(os.path.join(BENCH, "configs",
                                        "laguna-s-2.1-ep8.json"))


def load_ref():
    return cells.load_module(os.path.join(BENCH, "reference",
                                          "laguna-s-2.1-ep8.py"))


cfg = pytest.fixture(scope="module")(load_cfg)
ref = pytest.fixture(scope="module")(load_ref)


def reader(name):
    return cells.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def attn(heads):
    """One attention operator's elements: q, out, k, v, the gate, the op
    norm and the q/k norms."""
    return 2 * E * heads * HD + 2 * E * KV + heads * E + E + 2 * HD


EXPERT = 3 * E * MOE
#: what an expert layer holds beside its 32 experts: router, ffn norm and
#: the shared expert
ALWAYS = E * 256 + E + 3 * E * MOE


def test_the_cut_is_the_deployments_share(cfg, ref):
    assert abs(attn(HW) - 63.14e6) < 0.01e6 and abs(attn(HF) - 44.19e6) \
        < 0.01e6
    dense = 3 * E * DENSE + E
    want = 9 * attn(HW) + 3 * attn(HF) + dense \
        + 11 * (ALWAYS + 32 * EXPERT) + 2 * VOCAB * E + E
    assert ref.param_count(cfg) == want
    assert abs(want - 4.325e9) < 1e6                  # 8.65 GB in bfloat16
    assert abs(11 * 32 * EXPERT - 3.322e9) < 1e6      # 6.64 GB of experts
    # untied: the head counts, the table a step reads a row a slot of does
    # not
    assert ref.weight_bytes(cfg) == 2 * (want - VOCAB * E)
    # the whole model: 48 layers (12 periods, the first layer dense), all
    # 256 experts and the whole vocabulary: 117.6e9 parameters
    whole = 36 * attn(HW) + 12 * attn(HF) + dense \
        + 47 * (ALWAYS + 256 * EXPERT) + 2 * 100352 * E + E
    assert abs(whole - 117.6e9) < 0.1e9
    # the state at 64 slots and 3584 rows, bfloat16: the full layers' rows
    # 2.82 GB, the ring 1.21 GB; weights and state 12.68 GB
    rows, ring = 2 * 3 * 64 * 3584 * KV * 2, 2 * 9 * 64 * WINDOW * KV * 2
    assert abs(2 * want + rows + ring - 12.68e9) < 0.01e9
    # the stage's layers: F S S S three times, the first dense
    assert [k for k, _, _ in ref.layers(cfg)] \
        == (["full_attention"] + ["sliding_attention"] * 3) * 3
    assert [d for _, _, d in ref.layers(cfg)] == [True] + [False] * 11


def test_the_work_functions_against_hand_counts(cfg, ref):
    # contexts under, at and over the window
    ctx = [100] * 10 + [512] * 2 + [3000] * 52
    win = [min(c, WINDOW) for c in ctx]

    def matrices(heads):
        return 2 * (2 * E * heads * HD + 2 * E * KV + heads * E)

    f, b = ref.window_attn_layer_work(cfg, ctx)
    assert f == 9 * sum(matrices(HW) + 4 * HW * HD * r for r in win)
    assert b == 9 * (2 * attn(HW) + sum((r + 1) * 2 * KV * 2 for r in win))
    f, b = ref.full_attn_layer_work(cfg, ctx)
    assert f == 3 * sum(matrices(HF) + 4 * HF * HD * c for c in ctx)
    assert b == 3 * (2 * attn(HF) + sum((c + 1) * 2 * KV * 2 for c in ctx))
    # a position past the window reads no more of a window layer than one
    # at it: required bytes never count rows a position does not attend
    assert ref.window_attn_layer_work(cfg, [512]) \
        == ref.window_attn_layer_work(cfg, [4000])
    f, b = ref.moe_layer_work(cfg, 64)
    # a position's top-10 of 256 meets the 32 held experts 1.25 times
    assert f == 11 * 64 * 2 * (E * 256 + EXPERT + 1.25 * EXPERT)
    assert b == 11 * 2 * (ALWAYS + 32 * EXPERT)
    f, b = ref.step_work(cfg, ctx)
    assert f == sum(ref.flops_per_position(cfg, c) for c in ctx)
    assert b == ref.weight_bytes(cfg) \
        + 9 * sum((r + 1) * 2 * KV * 2 for r in win) \
        + 3 * sum((c + 1) * 2 * KV * 2 for c in ctx)
    per = ref.flops_per_position(cfg, 1)
    assert abs(per - (9 * matrices(HW) + 3 * matrices(HF)
                      + 2 * 3 * E * DENSE
                      + 11 * 2 * (E * 256 + EXPERT + 1.25 * EXPERT)
                      + 2 * E * VOCAB)) < 1e6
    # the step is bound by its bytes: 8.5 GB of weights at 819 GB/s
    assert b / 819e9 > 10 * f / 197e12
    # attention (its weights and the K/V rows at 2048 positions a slot) is
    # about two fifths of a step's bytes
    mid = [2048] * 64
    share = (ref.window_attn_layer_work(cfg, mid)[1]
             + ref.full_attn_layer_work(cfg, mid)[1]) \
        / float(ref.step_work(cfg, mid)[1])
    assert 0.35 < share < 0.45


TINY = {"hidden_size": 256, "num_key_value_heads": 2, "head_dim": 64,
        "num_hidden_layers": 5, "vocab_size": 1024, "intermediate_size": 512,
        "moe_intermediate_size": 128, "shared_expert_intermediate_size": 128,
        "num_experts": 2, "num_experts_per_tok": 2, "router_width": 8,
        "sliding_window": 8, "gating": "per-head", "mlp_only_layers": [0],
        "layer_types": ["full_attention"] + ["sliding_attention"] * 3
        + ["full_attention"],
        "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
        "program": "jit_decode_fn", "serve": {"slots": 4}}


def make_ctx(ref):
    """The Mellum cell's context over the recorded trace, with this
    configuration's reference and a tiny cut of its sizes."""
    mellum = cells.load_module(os.path.join(BENCH, "tests",
                                            "test_mellum2_cell.py"))
    return dict(mellum.make_ctx(ref), cfg=TINY)


@pytest.fixture(scope="module")
def ctx(ref):
    return make_ctx(ref)


def test_the_readers_on_the_recorded_trace(ctx):
    window = reader("window_attn_layer_roofline").read(ctx)
    full = reader("full_attn_layer_roofline").read(ctx)
    moe = reader("moe_layer_roofline").read(ctx)
    assert window > 0 and full > 0 and moe > 0
    whole = reader("decode_step_roofline.laguna").read(ctx)
    assert 0 < whole < window + full + moe
    assert 0 < reader("decode_mfu.laguna").read(ctx) < 1
    assert whole == reader("decode_step_roofline.mellum2").read(ctx)


def test_the_readers_return_nothing_where_there_is_nothing(ctx):
    empty = dict(ctx, spans=[], records=[], inside=[],
                 trace=tracered.Trace({}))
    for name in NEW + ("window_attn_layer_roofline",
                       "full_attn_layer_roofline", "moe_layer_roofline"):
        assert reader(name).read(empty) is None, name


def test_the_new_cell_and_metrics_are_declared(cfg):
    bench = cells.load_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["layer"] == "Ops"
        assert m["moves"] == "decode_tok_per_s" and m["unit"] == "%"
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))
    for name in JOINS + ("decode_tok_per_s",):
        assert CELL in by_name[name]["workloads"], name
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if "workloads" not in m or CELL in m["workloads"]}
    assert reports == set(JOINS + NEW) | {"decode_tok_per_s", "setup_s"}
    entry, = [c for c in bench["configs"] if c["name"] == cfg["name"]]
    assert entry["reduced"] == cfg["reduced"] \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert entry["source"] == cfg["source"]
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (cfg["name"], "long_generation", 1)
