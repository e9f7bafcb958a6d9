"""Every per-layer reader, fed the recorded chip trace (decode) or
hand-made intervals (training), finds what it should and returns nothing
where there is nothing to read. These are checks of the arithmetic; the
values are not device measurements of any cell."""
import json
import os

import pytest

from benchmark.harness import cells, peaks, tracered

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "testdata")


def reader(name):
    return cells.load_module(os.path.join(BENCH, "metrics", name + ".py"))


class FakeRun(object):
    peaks = peaks.peaks_for("TPU v5 lite")

    def perf_to_trace_ns(self, t):
        return int(t * 1e9)


@pytest.fixture(scope="module")
def decode_ctx():
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
    cfg = {"hidden_size": 256, "ffn_dim": 512, "num_hidden_layers": 2,
           "num_attention_heads": 4, "vocab_size": 1024,
           "max_position_embeddings": 128, "program": "jit_decode_fn",
           "serve": {"slots": 4}}
    ref = cells.load_module(os.path.join(BENCH, "reference", "opt-1.3b.py"))
    return {"run": FakeRun(), "cfg": cfg, "ref": ref, "records": records,
            "inside": records, "spans": spans, "trace": trace,
            "window_ns": (t0, t1), "setup_compile_s": 2.5,
            "result": {"per_token_ms": [10.0, 20.0, 30.0, float("inf")]}}


def test_decode_readers_on_the_recorded_trace(decode_ctx):
    ctx = decode_ctx
    assert reader("compile_s").read(ctx) == 2.5
    step_ms = reader("decode_step_ms_p50").read(ctx)
    assert 0.5 < step_ms < 20
    assert reader("slot_occupancy").read(ctx) == pytest.approx(
        100.0 * sum(3 + i + 5 + i - 1 for i in range(6)) / (26 * 4))
    # request i: 2+i of its 7+2i steps feed a prompt position only
    assert reader("prompt_step_share").read(ctx) == pytest.approx(
        100.0 * sum(2 + i for i in range(6))
        / sum(7 + 2 * i for i in range(6)))
    for name in ("decode_step_roofline.batch", "decode_step_roofline.chat"):
        assert 0 < reader(name).read(ctx) <= 100
    for name in ("decode_mfu.batch", "decode_mfu.chat"):
        assert 0 < reader(name).read(ctx) < 1
    assert reader("queue_wait_p50_ms").read(ctx) >= 0
    ttft = reader("ttft_p50_ms").read(ctx)
    gap = reader("token_gap_p50_ms").read(ctx)
    assert ttft > gap > 0 and gap == pytest.approx(step_ms, rel=0.5)
    assert reader("req_ms_per_token_p90").read(ctx) == float("inf")


def test_decode_readers_return_nothing_where_there_is_nothing(decode_ctx):
    empty = dict(decode_ctx, spans=[], records=[], inside=[],
                 trace=tracered.Trace({}), result={})
    for name in ("decode_step_ms_p50", "slot_occupancy", "prompt_step_share",
                 "decode_step_roofline.batch", "decode_mfu.chat",
                 "queue_wait_p50_ms", "ttft_p50_ms", "token_gap_p50_ms",
                 "req_ms_per_token_p90"):
        assert reader(name).read(empty) is None, name


def test_train_readers_on_hand_made_intervals():
    ms = 1_000_000
    ops, modules = [], []
    for d in range(3):                    # three dispatches of K=4 steps
        base = d * 420 * ms
        modules.append(("jit_scan_fn(7)", base, base + 400 * ms))
        for step in range(4):
            s = base + step * 100 * ms
            ops.append(("fusion.9", s, s + 90 * ms))
            ops.append(("all-reduce-start.1", s + 90 * ms, s + 91 * ms))
            ops.append(("all-reduce-done.1", s + 91 * ms, s + 100 * ms))
    trace = tracered.Trace({"/device:TPU:0": {"ops": ops,
                                              "modules": modules}})
    spans = [("dispatch", 395 * ms, 425 * ms, {}),
             ("readback_stall", 815 * ms, 845 * ms, {}),
             ("data_wait", 100 * ms, 142 * ms, {}),
             ("dispatch", 0, 1 * ms, {})]
    ref = cells.load_module(os.path.join(BENCH, "reference", "resnet50.py"))
    cfg = {"num_layers": 50, "num_classes": 1000,
           "image_shape": [3, 224, 224], "program": "jit_scan_fn"}
    ctx = {"run": FakeRun(), "cfg": cfg, "ref": ref, "spans": spans,
           "trace": trace, "window_ns": (0, 1260 * ms), "k": 4,
           "batch": 128, "chips": 1, "setup_compile_s": 1.0}
    assert reader("train_step_ms").read(ctx) == pytest.approx(100.0)
    flops = ref.flops_per_sample(cfg)
    assert flops == pytest.approx(24.5e9, rel=0.01)
    assert reader("train_step_mfu").read(ctx) == pytest.approx(
        100.0 * flops * 3 * 4 * 128 / 1.24 / 197e12)
    # two 20 ms gaps between dispatches, each under a span; two dispatches
    assert reader("dispatch_gap_ms").read(ctx) == pytest.approx(40.0 / 2)
    assert reader("data_wait_share").read(ctx) == pytest.approx(
        100.0 * 42 / 1260)
    nothing = dict(ctx, trace=tracered.Trace({}), spans=[])
    for name in ("train_step_ms", "train_step_mfu", "dispatch_gap_ms",
                 "data_wait_share"):
        assert reader(name).read(nothing) is None, name
