"""The reduction from trace and spans to numbers, on a small trace recorded
on the chip (``testdata/decode_small.*``: a 2-layer DecodeLoop serving six
requests on one v5e, 26 steps; recorded in PR 26) and on hand-made
intervals."""
import json
import os

import pytest

from benchmark.harness import spans as span_tools
from benchmark.harness import tracered

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


@pytest.fixture(scope="module")
def recorded():
    trace = tracered.Trace.from_file(
        os.path.join(DATA, "decode_small.xplane.pb"))
    with open(os.path.join(DATA, "decode_small.json")) as f:
        side = json.load(f)
    spans = [(n, s, e, a) for n, s, e, a in side["spans"]]
    return trace, spans, side["expected"]


def test_recorded_trace_reduces_to_the_numbers_recorded_with_it(recorded):
    trace, spans, want = recorded
    t0, t1 = want["window_ns"]
    assert sorted(trace.devices) == want["devices"] == ["/device:TPU:0"]
    assert trace.sync_ns is not None
    runs = trace.program_runs("jit_decode_fn")
    assert len(runs) == want["program_runs"] == want["n_decode_step_spans"]
    assert sum(e - s for s, e in runs) / 1e9 == pytest.approx(
        want["program_seconds"])
    assert trace.busy_seconds(t0, t1) == pytest.approx(want["busy_s"])
    assert trace.top_ops(5) == want["top_ops"]
    assert trace.program_runs("jit_no_such_program") == []


def test_recorded_trace_is_consistent_with_itself(recorded):
    trace, spans, want = recorded
    t0, t1 = want["window_ns"]
    busy = trace.busy_seconds(t0, t1)
    idle = sum(sec for _, sec in trace.attribute_gaps(
        [s[:3] for s in spans], t0, t1))
    # busy + idle is the window, and an operation's time is inside its program's
    assert busy + idle == pytest.approx((t1 - t0) / 1e9, rel=1e-6)
    assert busy <= want["program_seconds"] * 1.001
    # names are the operations' own, not whole HLO instructions
    assert all(" " not in name and "=" not in name
               for name, _ in trace.top_ops(10))
    # every step's device run lies inside a decode_step span of the host
    steps = sorted((s, e) for n, s, e, _ in spans if n == "decode_step")
    for (rs, re_), (ss, se) in zip(trace.program_runs("jit_decode_fn"), steps):
        assert ss <= rs and re_ <= se + 1_000_000
    bd = tracered.breakdown(trace, [s[:3] for s in spans], t0, t1)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["idle_gaps"][0][0] == "gap:decode_step"


def test_step_positions_follow_each_request(recorded):
    _, spans, _ = recorded
    rids = sorted({r for _, _, _, a in spans for r in a.get("reqs", ())})
    records = [{"rid": r, "prompt_len": 3 + i, "index": i}
               for i, r in enumerate(rids)]
    rows = span_tools.step_positions(spans, records)
    seen = {}
    for _, _, row in rows:
        for rec, pos in row:
            assert pos == seen.get(rec["rid"], 0)
            seen[rec["rid"]] = pos + 1
    # request i fed 3+i prompt positions and generated 5+i tokens
    assert [seen[r] for r in rids] == [3 + i + 5 + i - 1 for i in range(6)]


def test_union_gaps_and_exposed_collectives_on_hand_made_intervals():
    assert tracered.union_seconds([(0, 10), (5, 20), (30, 40)]) == 30e-9
    assert tracered.gaps([(5, 10), (20, 30)], 0, 40) == [(0, 5), (10, 20),
                                                         (30, 40)]
    dev = {"ops": [("fusion.1", 0, 100), ("all-reduce.3", 100, 160),
                   ("fusion.2", 140, 200), ("all-gather-done.1", 220, 250)],
           "modules": [("jit_scan_fn(1)", 0, 250)]}
    trace = tracered.Trace({"/device:TPU:0": dev, "/device:TPU:1": dev})
    # 100-140 and 220-250 are collective time with nothing else running
    assert trace.exposed_collective_seconds(0, 250) == pytest.approx(70e-9)
    assert trace.busy_seconds(0, 250) == pytest.approx(230e-9)
    named = trace.attribute_gaps([("dispatch", 190, 215),
                                  ("readback_stall", 300, 400)], 0, 250)
    assert named == [("dispatch", 20e-9)]
    # a last run that the end of the trace cut short is left out
    cut = tracered.Trace({"/device:TPU:0": {"ops": [], "modules": [
        ("jit_scan_fn(1)", 0, 400), ("jit_scan_fn(1)", 420, 820),
        ("jit_scan_fn(1)", 840, 1240), ("jit_scan_fn(1)", 1260, 1300)]}})
    assert cut.whole_runs("jit_scan_fn", 0, 1300) == [(0, 400), (420, 820),
                                                      (840, 1240)]
    assert len(cut.whole_runs("jit_scan_fn", 0, 1250)) == 3
    only_compute = tracered.Trace({"/device:TPU:0": {
        "ops": [("fusion.1", 0, 10)], "modules": []}})
    assert only_compute.exposed_collective_seconds(0, 10) is None
    assert tracered.op_name("%copy.118 = f32[2]{0} copy(%all-reduce.1)") \
        == "copy.118"
