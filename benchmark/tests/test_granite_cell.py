"""The files the ``granite-4.0-h-micro`` configuration brings: its work
functions against counts made by hand at the published sizes, and its
readers on the recorded chip trace (``testdata/decode_small``, through the
context ``test_lfm2_cell.py`` makes of it, with a scope table of this
block's scopes made up here: these check the readers' arithmetic and what
they do with nothing to read; the values are not device measurements of
any cell)."""
import os

import pytest

from benchmark.harness import cells, tracered

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "granite-4.0-h-micro.batch_wide"
E, KV, VOCAB, FFN = 2048, 512, 100352, 8192
INNER, CONV, HEADS, STATE, TAPS = 4096, 4352, 64, 128, 4
NEW = ("ssm_layer_roofline", "ssm_step_share", "decode_step_roofline.granite",
       "decode_mfu.granite")
#: the lists of the benchmark's existing metrics the cell joins
JOINS = ("compile_s", "decode_step_ms_p50", "slot_occupancy",
         "prompt_step_share", "decode_gap_feed_ms", "decode_gap_dispatch_ms",
         "decode_gap_readback_ms", "decode_gap_commit_ms",
         "decode_gap_covered", "emitted_tok_per_s", "token_gap_p99_ms")
#: one mamba mixer's elements: in_proj, out_proj, the kernel and its bias,
#: A_log, D and dt_bias, the gated norm and the op norm
MAMBA = (INNER + CONV + HEADS) * E + INNER * E + 5 * CONV + 3 * HEADS \
    + INNER + E
ATTN = 2 * E * E + 2 * E * KV + E
MLP = 3 * E * FFN + E


def load_cfg():
    return cells.load_json(os.path.join(BENCH, "configs",
                                        "granite-4.0-h-micro.json"))


def load_ref():
    return cells.load_module(os.path.join(BENCH, "reference",
                                          "granite-4.0-h-micro.py"))


cfg = pytest.fixture(scope="module")(load_cfg)
ref = pytest.fixture(scope="module")(load_ref)


def reader(name):
    return cells.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def test_the_model_is_whole(cfg, ref):
    assert abs(MAMBA - 25.84e6) < 0.01e6 and abs(ATTN - 10.49e6) < 0.01e6
    want = 36 * (MAMBA + MLP) + 4 * (ATTN + MLP) + VOCAB * E + E
    assert ref.param_count(cfg) == want
    assert abs(want - 3.191e9) < 1e6                  # 6.38 GB in bfloat16
    assert ref.weight_bytes(cfg) == 2 * want          # tied: the table once
    # the state at 64 slots and 1024 rows: the matrix state 4.83 GB in
    # float32, the conv state 0.06 GB, K and V 0.54 GB: 11.81 GB in all
    ssm = 36 * 64 * INNER * STATE * 4
    conv = 36 * 64 * (TAPS - 1) * CONV * 2
    kv = 2 * 4 * 64 * 1024 * KV * 2
    assert abs(2 * want + ssm + conv + kv - 11.81e9) < 0.01e9


def test_the_work_functions_against_hand_counts(cfg, ref):
    ctx = [100] * 60 + [7] * 4
    state = INNER * STATE * 4 * 2 + TAPS * CONV * 2   # a position, a layer
    assert state == 4 * 2 ** 20 + 34816
    mixer_flops = 2 * ((INNER + CONV + HEADS) * E + INNER * E) \
        + 2 * TAPS * CONV + 5 * INNER * STATE
    f, b = ref.ssm_layer_work(cfg, 64)
    assert f == 36 * 64 * mixer_flops
    assert b == 36 * (2 * MAMBA + 64 * state)
    f, b = ref.step_work(cfg, ctx)
    assert f == sum(ref.flops_per_position(cfg, c) for c in ctx)
    kv = sum((c + 1) * 2 * KV * 2 for c in ctx)
    assert b == ref.weight_bytes(cfg) + 4 * kv + 36 * 64 * state
    per = ref.flops_per_position(cfg, 1)
    assert per == 36 * mixer_flops + 4 * (2 * (2 * E * E + 2 * E * KV)
                                          + 4 * E) \
        + 40 * 2 * 3 * E * FFN + 2 * E * VOCAB
    # the step is bound by its bytes, 16.34 GB at 400 rows a slot, the state
    # 60% of them and attention's K/V rows about 1%
    mid = [400] * 64
    flops, nbytes = ref.step_work(cfg, mid)
    assert nbytes / 819e9 > 5 * flops / 197e12
    assert abs(nbytes - 16.34e9) < 0.01e9
    assert 0.58 < 36 * 64 * state / float(nbytes) < 0.61
    assert 4 * sum((c + 1) * 2 * KV * 2 for c in mid) / float(nbytes) < 0.015


#: a tiny cut of the configuration's sizes for the readers' arithmetic
TINY = {"hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 5, "vocab_size": 97,
        "shared_intermediate_size": 96, "mamba_n_heads": 8,
        "mamba_d_head": 16, "mamba_d_state": 16, "mamba_d_conv": 4,
        "layer_types": ["mamba"] * 4 + ["attention"],
        "program": "jit_decode_fn", "serve": {"slots": 4}}
SCOPES = ("layer/ssm", "cache_write/ssm", "layer/attn", "cache_write/kv",
          "layer/mlp", "head")


def make_ctx(ref):
    """The LFM2 cell's context over the recorded trace, with this
    configuration's reference, a tiny cut of its sizes and its scopes in
    the step's table."""
    lfm2 = cells.load_module(os.path.join(BENCH, "tests", "test_lfm2_cell.py"))
    ctx = lfm2.make_ctx(ref)
    spans = []
    for name, s, e, args in ctx["spans"]:
        if name == "loop_program":
            names = sorted(args["scopes"])
            args = dict(args, scopes={n: SCOPES[i % len(SCOPES)]
                                      for i, n in enumerate(names)})
        spans.append((name, s, e, args))
    return dict(ctx, cfg=TINY, spans=spans)


@pytest.fixture(scope="module")
def ctx(ref):
    return make_ctx(ref)


def test_the_readers_on_the_recorded_trace(ctx):
    ssm = reader("ssm_layer_roofline").read(ctx)
    share = reader("ssm_step_share").read(ctx)
    whole = reader("decode_step_roofline.granite").read(ctx)
    assert ssm > 0 and 0 < whole
    assert 0 < reader("decode_mfu.granite").read(ctx) < 1
    # a third of the operations are the mixers' by the made-up table: their
    # union of intervals is a part of the step's time
    assert 0 < share < 100
    # the writes count with the mixer: without them its share drops
    no_writes = dict(ctx, spans=[
        s if s[0] != "loop_program" else s[:3] + (dict(s[3], scopes={
            k: v for k, v in s[3]["scopes"].items()
            if v != "cache_write/ssm"}),) for s in ctx["spans"]])
    assert reader("ssm_step_share").read(no_writes) < share
    assert reader("ssm_layer_roofline").read(no_writes) > ssm


def test_the_readers_return_nothing_where_there_is_nothing(ctx, ref):
    # a program that sends no loop_program span (the parent commit), or
    # whose step has no mixer (another configuration's)
    bare = dict(ctx, spans=[s for s in ctx["spans"]
                            if s[0] != "loop_program"])
    other = dict(ctx, spans=[
        s if s[0] != "loop_program" else s[:3] + (dict(s[3], scopes={
            k: "layer/attn" for k in s[3]["scopes"]}),) for s in ctx["spans"]])
    for name in ("ssm_layer_roofline", "ssm_step_share"):
        assert reader(name).read(bare) is None, name
    assert reader("ssm_step_share").read(other) is None
    lfm2 = cells.load_module(os.path.join(BENCH, "reference",
                                          "lfm2-24b-a2b-ep8.py"))
    assert reader("ssm_layer_roofline").read(dict(ctx, ref=lfm2)) is None
    empty = dict(ctx, spans=[], records=[], inside=[],
                 trace=tracered.Trace({}))
    for name in NEW:
        assert reader(name).read(empty) is None, name


def test_the_new_cell_and_metrics_are_declared(cfg):
    bench = cells.load_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["layer"] == ("State-space layer" if name.startswith("ssm")
                              else "Ops")
        assert m["moves"] == "decode_tok_per_s"
        assert m["source"] == "device_trace" and m["better"] == "higher"
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))
    for name in JOINS + ("decode_tok_per_s",):
        assert CELL in by_name[name]["workloads"], name
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if "workloads" not in m or CELL in m["workloads"]}
    assert reports == set(JOINS + NEW) | {"decode_tok_per_s", "setup_s"}
    entry, = [c for c in bench["configs"] if c["name"] == cfg["name"]]
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["source"] == cfg["source"]
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (cfg["name"], "batch_wide", 1)
    assert bench["workloads"][-1] is cell and len(cell["why"]) <= 200
