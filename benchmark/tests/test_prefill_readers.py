"""The readers of what ``DecodeLoop`` says about its prefill passes in its
own ``decode_step`` spans (``prefill_position_share``,
``prefill_positions_per_pass``, ``harness/prefill.py``) and
``prefill_device_share.kimi``, on made-up spans and a made-up trace.
Checks of the arithmetic; no value is a measurement of any cell."""
import json
import os

import pytest

from benchmark.harness import cells, prefill, tracered

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1_000_000
NEW = ("prefill_position_share", "prefill_positions_per_pass",
       "prefill_device_share.kimi")
PROGRAM = ("loop_program", 0, 1, {"program": "jit_decode_fn", "scopes": {},
                                  "prefill_program": "jit_prefill_fn",
                                  "prefill_chunk": 256})


def reader(name):
    return cells.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def step(i, n, emit, entries=None, at=None):
    """One ``decode_step`` span as the loop ends it: ``n`` and ``emit``
    aligned with ``reqs``, ``prefill`` where a pass went ahead of it."""
    args = {"step": i, "reqs": list(range(len(n))), "pos": [0] * len(n),
            "n": n, "emit": emit}
    if entries is not None:
        args["prefill"] = entries
    t = (i if at is None else at) * 10 * MS
    return ("decode_step", t, t + 8 * MS, args)


def made_up_ctx():
    # four slots. Step 1: slots 2 and 3 join; their prompts ride. Step 3 is
    # behind a PACKED pass of two slots (59 + 63 positions, then the step's
    # one each); step 6 behind a pass of one slot; steps 0 and 9 lie
    # outside the window
    spans = [PROGRAM,
             step(0, [1, 1, 40, 1], [1, 1, 0, 1], [[7, 2, 0, 39]], at=-1),
             step(1, [1, 1, 1, 1], [1, 1, 0, 0]),
             step(2, [1, 1, 1, 1], [1, 1, 0, 0]),
             step(3, [1, 1, 60, 64], [1, 1, 0, 0],
                  [[12, 2, 2, 59], [13, 3, 2, 63]]),
             step(4, [1, 1, 1, 1], [1, 1, 1, 1]),
             step(5, [1, 1, 1, 1], [1, 1, 0, 1]),
             step(6, [1, 17, 1, 1], [1, 0, 0, 1], [[14, 1, 1, 16]]),
             step(7, [1, 1, 1, 1], [1, 0, 1, 1]),
             step(9, [1, 30, 1, 1], [1, 0, 1, 1], [[15, 1, 0, 29]], at=100)]
    return {"spans": spans, "window_ns": (0, 80 * MS)}


def test_passes_are_the_windows_entries_one_list_a_pass():
    ctx = made_up_ctx()
    assert prefill.has_pass(ctx)
    assert prefill.passes(ctx) == [[[12, 2, 2, 59], [13, 3, 2, 63]],
                                   [[14, 1, 1, 16]]]


def test_the_share_is_the_passes_positions_over_the_prompt_positions():
    ctx = made_up_ctx()
    # prompt positions, n - emit summed over steps 1..7:
    # 2 + 2 + (59 + 1 + 63 + 1) + 0 + 1 + (16 + 1 + 1) + 1 = 148
    assert reader("prefill_position_share").read(ctx) == pytest.approx(
        100.0 * (59 + 63 + 16) / 148)
    assert reader("prefill_positions_per_pass").read(ctx) == pytest.approx(
        (59 + 63 + 16) / 2)


def test_a_bare_entry_of_a_program_before_the_packed_pass_is_read_too():
    """PR 37's and 38's loops wrote ``prefill=[req, slot, pos0, n]``: one
    pass, one slot, no list around it."""
    ctx = made_up_ctx()
    ctx["spans"] = [PROGRAM, step(1, [17, 1], [0, 1], [21, 0, 0, 16]),
                    step(2, [1, 1], [0, 1]),
                    step(3, [1, 33], [1, 0], [22, 1, 4, 32])]
    assert prefill.passes(ctx) == [[[21, 0, 0, 16]], [[22, 1, 4, 32]]]
    assert reader("prefill_position_share").read(ctx) == pytest.approx(
        100.0 * 48 / 51)
    assert reader("prefill_positions_per_pass").read(ctx) == 24.0


def test_a_loop_with_a_pass_that_dispatched_none_reads_zero_and_nothing():
    ctx = made_up_ctx()
    ctx["spans"] = [PROGRAM, step(1, [1, 1], [0, 1]), step(2, [1, 1], [0, 1])]
    assert reader("prefill_position_share").read(ctx) == 0.0
    assert reader("prefill_positions_per_pass").read(ctx) is None
    # and a window that committed no prompt position has no share
    ctx["spans"] = [PROGRAM, step(1, [1, 1], [1, 1])]
    assert reader("prefill_position_share").read(ctx) is None


def test_a_loop_without_a_pass_gives_nothing_to_read():
    """Another architecture, or any commit before the pass: its
    ``loop_program`` names no prefill program; an older one has no
    ``emit`` either."""
    ctx = made_up_ctx()
    ctx["spans"] = [("loop_program", 0, 1, {"program": "jit_decode_fn",
                                            "scopes": {}}),
                    step(1, [1, 1], [0, 1]), step(2, [1, 1], [0, 1])]
    ctx["trace"] = tracered.Trace({"/device:TPU:0": {
        "ops": [("fusion.1", 0, 8 * MS)],
        "modules": [("jit_decode_fn(3)", 0, 8 * MS)]}})
    for name in NEW:
        assert reader(name).read(ctx) is None, name
    ctx["spans"] = [("decode_step", 10 * MS, 18 * MS, {"reqs": [1]})]
    for name in NEW:
        assert reader(name).read(ctx) is None, name


def test_the_kimi_share_is_the_reader_prefill_device_share_has():
    ctx = made_up_ctx()
    modules, ops = [], []
    for i in range(8):
        t = i * 10 * MS
        if i in (3, 6):     # a pass of 2 ms between two steps
            modules.append(("jit_prefill_fn(5)", t - 2 * MS, t))
            ops.append(("fusion.7", t - 2 * MS, t))
        modules.append(("jit_decode_fn(3)", t, t + 8 * MS))
        ops.append(("fusion.1", t, t + 8 * MS))
    ctx["trace"] = tracered.Trace({"/device:TPU:0": {"ops": ops,
                                                     "modules": modules}})
    want = 100.0 * 4 / (8 * 8 + 4)
    assert reader("prefill_device_share.kimi").read(ctx) \
        == reader("prefill_device_share").read(ctx) == pytest.approx(want)


def test_the_new_metrics_are_declared_with_their_cells_and_readers():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    tail = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    both = ["kimi-k2-ep32.batch_wide", "opt-1.3b.batch_saturated"]
    assert by_name["prefill_position_share"]["workloads"] \
        == by_name["prefill_positions_per_pass"]["workloads"] == both
    assert by_name["prefill_device_share.kimi"]["workloads"] == both[:1]
    # the Kimi cell's share is the OPT cell's, but for its name and cell
    assert {k: v for k, v in by_name["prefill_device_share.kimi"].items()
            if k not in ("name", "workloads")} \
        == {k: v for k, v in by_name["prefill_device_share"].items()
            if k not in ("name", "workloads")}
    (e2e,) = [m for m in bench["end_to_end"]
              if m["name"] == "decode_tok_per_s"]
    layers = {m["layer"] for m in bench["per_layer"][:-len(NEW)]}
    for m in tail:
        assert m["moves"] == "decode_tok_per_s"
        assert set(m["workloads"]) <= set(e2e["workloads"])
        assert m["layer"] in layers
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
