"""The readers of what ``DecodeLoop`` records about a request, a prefill
pass and an empty loop (``first_token_p50_ms``, ``token_gap_p99_ms``,
``prefill_pass_ms``, ``prefill_device_share``, ``loop_idle_share``) and
their helper ``harness/requests.py``, on made-up records and traces: the
records go through the program's own tracer, as the loop sends them.
Checks of the arithmetic; no value is a measurement of any cell."""
import json
import os

import pytest

from benchmark.harness import cells, requests, tracered, window
from mxnet_tpu.obs import trace as obs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1_000_000
NEW = ("first_token_p50_ms", "token_gap_p99_ms", "prefill_pass_ms",
       "prefill_device_share", "loop_idle_share")


def reader(name):
    return cells.load_module(os.path.join(BENCH, "metrics", name + ".py"))


@pytest.fixture(autouse=True)
def _tracer():
    obs.stop()
    obs.clear()
    obs.start()
    yield
    obs.stop()
    obs.clear()


def record(rid, submit, token_us, **more):
    """One ``decode_request`` record as ``DecodeLoop._request_done`` sends
    it: an async pair under the request's id."""
    obs.async_complete(requests.NAME, 0.5, id=rid, req=rid, submit=submit,
                       token_us=token_us, emitted=len(token_us), **more)


def made_up_ctx():
    # three requests due at 10.000, 10.100, 10.200 s; the loop got each
    # 1, 2 and 3 ms later, and their first tokens 20, 30 and 50 ms after
    # that; gaps of 8 ms but for one of 17 (a co-rider's pass)
    record(11, 10.001, [20000, 28000, 36000, 53000], outcome="done")
    record(12, 10.102, [30000, 38000], outcome="done")
    record(13, 10.203, [50000], outcome="eos")
    record(14, 10.300, [], outcome="shed")          # no token: left out
    record(99, 9.000, [1000, 400000], outcome="done")   # not in the window
    obs.instant("decode_submit", req=11)            # other events pass by
    inside = [{"rid": 11, "due": 10.000, "index": 0},
              {"rid": 12, "due": 10.100, "index": 1},
              {"rid": 13, "due": 10.200, "index": 2},
              {"rid": 14, "due": 10.300, "index": 3},
              {"rid": None, "due": 10.400, "index": 4}]     # never sent
    # a window of 1000 ms: steps of 8 ms back to back but for two passes of
    # 9 and 10 ms, then nothing from 400 to 900 ms (the loop stood empty
    # from 405 to 890) and steps again
    modules, ops, t = [], [], 0
    for i in range(60):
        if i in (5, 20):
            dur = (9 if i == 5 else 10) * MS
            modules.append(("jit_prefill_fn(5)", t, t + dur))
            ops.append(("fusion.7", t, t + dur))
            t += dur
        if t >= 400 * MS and t < 900 * MS:
            t = 900 * MS
        modules.append(("jit_decode_fn(3)", t, t + 8 * MS))
        ops.append(("fusion.1", t, t + 8 * MS))
        t += 8 * MS
    trace = tracered.Trace({"/device:TPU:0": {"ops": ops,
                                              "modules": modules}})
    spans = [("loop_program", 0, 1, {"program": "jit_decode_fn",
                                     "scopes": {"fusion.1": "layer/attn"},
                                     "prefill_program": "jit_prefill_fn",
                                     "prefill_chunk": 128}),
             ("loop_idle", -50 * MS, 0, {"step": 0}),    # before the window
             ("loop_idle", 405 * MS, 890 * MS, {"step": 49}),
             ("loop_idle", 990 * MS, 1200 * MS, {"step": 60}),   # cut at 1000
             ("decode_step", 0, 8 * MS, {"reqs": [11]})]
    return {"inside": inside, "records": inside, "spans": spans,
            "trace": trace, "window_ns": (0, 1000 * MS)}, trace


def test_the_helper_returns_the_runs_records_by_rid():
    ctx, _ = made_up_ctx()
    recs = requests.by_rid(ctx)
    assert sorted(recs) == [11, 12, 13, 14, 99]
    assert recs[12]["token_us"] == [30000, 38000]
    assert recs[13]["outcome"] == "eos"
    # read once a ctx: what is recorded later is another run's
    record(15, 11.0, [1])
    assert sorted(requests.by_rid(ctx)) == [11, 12, 13, 14, 99]
    assert [r["rid"] for r, _ in requests.inside(ctx)] == [11, 12, 13]
    # what goes through the tracer is what a trace file holds
    json.dumps(obs.events())


def test_first_token_is_submit_plus_the_first_stamp_less_due():
    ctx, _ = made_up_ctx()
    # 1 + 20, 2 + 30 and 3 + 50 ms: the median of three is the second
    assert reader("first_token_p50_ms").read(ctx) == pytest.approx(32.0)
    ctx_closed = dict(ctx, inside=[dict(r, due=None)
                                   for r in ctx["inside"]])
    ctx_closed.pop("_requests")
    assert reader("first_token_p50_ms").read(ctx_closed) is None


def test_token_gaps_are_the_stamps_differences_over_the_windows_requests():
    ctx, _ = made_up_ctx()
    assert sorted(requests.token_gaps_ms(ctx)) == [8.0, 8.0, 8.0, 17.0]
    assert reader("token_gap_p99_ms").read(ctx) == 17.0
    assert window.percentile(requests.token_gaps_ms(ctx), 50) == 8.0


def test_the_pass_is_found_by_the_name_the_loop_gives_it():
    ctx, trace = made_up_ctx()
    assert requests.prefill_runs(ctx) == [(5 * 8 * MS, 5 * 8 * MS + 9 * MS),
                                          (169 * MS, 179 * MS)]
    assert reader("prefill_pass_ms").read(ctx) == pytest.approx(9.5)
    busy_s = trace.busy_seconds(0, 1000 * MS)
    assert busy_s == pytest.approx((60 * 8 + 19) / 1e3)
    assert reader("prefill_device_share").read(ctx) == pytest.approx(
        100.0 * 0.019 / busy_s)
    # a loop whose program span names no pass (another architecture)
    no_pass = dict(ctx, spans=[("loop_program", 0, 1, {
        "program": "jit_decode_fn", "scopes": {}})])
    assert requests.prefill_runs(no_pass) is None
    assert reader("prefill_pass_ms").read(no_pass) is None
    assert reader("prefill_device_share").read(no_pass) is None


def test_loop_idle_is_cut_to_the_window():
    ctx, _ = made_up_ctx()
    assert reader("loop_idle_share").read(ctx) == pytest.approx(
        100.0 * (485 + 10) / 1000)
    busy = dict(ctx, spans=[s for s in ctx["spans"]
                            if s[0] != "loop_idle" or s[2] <= 0])
    assert reader("loop_idle_share").read(busy) == 0.0


def test_a_program_without_the_records_gives_nothing_to_read():
    """A commit before the records were added: no ``decode_request``, no
    ``loop_idle``, a ``loop_program`` that names no pass."""
    ctx, _ = made_up_ctx()
    obs.clear()
    old = dict(ctx, spans=[("loop_program", 0, 1, {
        "program": "jit_decode_fn", "scopes": {}}),
        ("decode_step", 0, 8 * MS, {"reqs": [11]})])
    for name in NEW:
        assert reader(name).read(old) is None, name


def test_the_new_metrics_are_declared_with_their_cells_and_readers():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    tail = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == list(NEW)
    by_name = {m["name"]: m for m in tail}
    e2e = {m["name"]: m["workloads"] for m in bench["end_to_end"]
           if "workloads" in m}
    assert by_name["first_token_p50_ms"]["workloads"] \
        == by_name["loop_idle_share"]["workloads"] \
        == e2e["req_ms_per_token_p50"] == ["opt-1.3b.chat_steady"]
    assert by_name["token_gap_p99_ms"]["workloads"] \
        == e2e["decode_tok_per_s"]
    assert by_name["prefill_pass_ms"]["workloads"] \
        == by_name["prefill_device_share"]["workloads"] \
        == ["opt-1.3b.batch_saturated"]
    layers = {m["layer"] for m in bench["per_layer"][:-len(NEW)]}
    for m in tail:
        assert set(m["workloads"]) <= set(e2e[m["moves"]])
        assert m["layer"] in layers
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
