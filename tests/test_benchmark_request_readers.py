"""The benchmark's readers of ``DecodeLoop``'s own request records
(PERF.md, PR 38), beside ``tests/test_benchmark_kimi_cell.py``: a tiny
open-loop cell's files are driven through the benchmark's ``decode_loop``
entry on the CPU with ``--trace 1``, and what ``first_token_p50_ms`` reads
(``submit + token_us[0]`` of the window's requests, less their ``due``) is
held equal to a wrapper around ``GenerateFuture.token_times``, the way the
number was taken by hand before the loop recorded it. The numbers
themselves never print off the chip. From PR 39 the tiny closed-loop cell
is driven the same way for the readers of the prefill passes' own spans
(``prefill_position_share``, ``prefill_positions_per_pass``). The
harness's own tests of the readers
(``benchmark/tests/test_request_readers.py``, ``test_prefill_readers.py``)
run here too, so that tier-1 holds them."""
import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells  # noqa: E402

BENCH_TESTS = os.path.join(ROOT, "benchmark", "tests")
NEW = {"first_token_p50_ms": "tiny-lm.open", "loop_idle_share": "tiny-lm.open",
       "token_gap_p99_ms": "tiny-lm.closed",
       "prefill_pass_ms": "tiny-lm.closed",
       "prefill_device_share": "tiny-lm.closed",
       "prefill_position_share": "tiny-lm.closed",
       "prefill_positions_per_pass": "tiny-lm.closed",
       "prefill_device_share.kimi": "tiny-lm.closed"}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The harness's own throw-away root (tiny cells ADDED beside the
    benchmark's files), with the new metrics declared for them as
    ``BENCHMARK.json`` declares them for the real cells."""
    helpers = cells.load_module(os.path.join(BENCH_TESTS, "helpers.py"))
    root = helpers.make_root(tmp_path_factory.mktemp("requests_root"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    moves = {"tiny-lm.open": "req_ms_per_token_p50",
             "tiny-lm.closed": "decode_tok_per_s"}
    for name, cell in NEW.items():
        assert declared[name]["moves"] == moves[cell]
        bench["per_layer"].append(dict(declared[name], workloads=[cell]))
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def test_first_token_reads_what_a_wrapper_around_token_times_reads(
        tiny_root, monkeypatch):
    from benchmark import run as bench_run
    from benchmark.harness import requests, runner, window
    from mxnet_tpu.obs import trace as obs_trace
    from mxnet_tpu.serving import decode

    futures, seen = {}, {}
    generate = decode.DecodeLoop.generate

    def wrapped(self, *args, **kw):     # the wrapper: it keeps the futures
        fut = generate(self, *args, **kw)
        futures[fut.rid] = fut
        return fut

    def spy(run, ctx):
        seen["ctx"] = ctx
        seen["metrics"] = per_layer(run, ctx)
        return seen["metrics"]

    per_layer = runner.per_layer_metrics
    monkeypatch.setattr(decode.DecodeLoop, "generate", wrapped)
    monkeypatch.setattr(runner, "per_layer_metrics", spy)
    out, err = io.StringIO(), io.StringIO()
    try:
        line = bench_run.run_cell("tiny-lm.open", 3000038007, 1.0, 1,
                                  root=tiny_root, require_chip=False,
                                  compile_cache=False, out=out, err=err)
        ctx = seen["ctx"]
        recs = requests.by_rid(ctx)
        value = cells.load_module(os.path.join(
            tiny_root, "benchmark", "metrics",
            "first_token_p50_ms.py")).read(ctx)
    finally:
        obs_trace.stop()
        obs_trace.clear()
    assert line["correct"] and line["failed"] == 0, err.getvalue()
    assert line["metrics"] == {}            # no device metric off the chip
    inside = ctx["inside"]
    assert len(inside) == 8 and all(r["rid"] in futures for r in inside)
    # every request sent since the spans were armed (the warm-up came
    # before) has ONE record under its rid, whose stamps are the future's
    # own to the microsecond
    sent = [r for r in ctx["records"] if r["index"] >= 0]
    assert len(sent) > len(inside) and len(recs) == len(sent)
    for r in sent:
        fut, rec = futures[r["rid"]], recs[r["rid"]]
        assert rec["outcome"] == "done" and rec["emitted"] == r["new"]
        assert rec["submit"] == fut.t_submit >= r["t_submit"]
        for us, t in zip(rec["token_us"], fut.token_times):
            assert abs(rec["submit"] + us / 1e6 - t) <= 0.5e-6 + 1e-9
    by_hand = window.percentile(
        [(futures[r["rid"]].token_times[0] - r["due"]) * 1e3
         for r in inside], 50)
    assert value == seen["metrics"]["first_token_p50_ms"] > 0
    assert value == pytest.approx(by_hand, abs=1e-3)
    # the empty loop between arrivals is in the same run's spans
    assert 0 <= seen["metrics"]["loop_idle_share"] <= 100
    assert any(n == "loop_idle" for n, _, _, _ in ctx["spans"])


def test_the_pass_readers_read_the_loops_own_spans(tiny_root, monkeypatch):
    """The tiny closed-loop cell with ``--trace 1``: its loop's passes go
    from the ``decode_step`` spans (``prefill``: one entry a slot, in a
    list) through the entry to the two readers, which give what the same
    spans give by hand; the device's share has no device to read."""
    from benchmark import run as bench_run
    from benchmark.harness import runner, stepgaps
    from mxnet_tpu.obs import trace as obs_trace
    from mxnet_tpu.serving import decode

    seen = {}

    def spy(run, ctx):
        seen["ctx"] = ctx
        seen["metrics"] = per_layer(run, ctx)
        return seen["metrics"]

    per_layer = runner.per_layer_metrics
    monkeypatch.setattr(runner, "per_layer_metrics", spy)
    monkeypatch.setattr(decode, "MIN_PREFILL", 2)   # prompts of 3 to 10
    out, err = io.StringIO(), io.StringIO()
    # as in a real run, spans are armed AFTER the warm-up: the loop names
    # its programs in the first step it traces (this file's tracer fixture
    # has them on already)
    obs_trace.stop()
    try:
        line = bench_run.run_cell("tiny-lm.closed", 3000039007, 1.0, 1,
                                  root=tiny_root, require_chip=False,
                                  compile_cache=False, out=out, err=err)
    finally:
        obs_trace.stop()
        obs_trace.clear()
    assert line["correct"] and line["failed"] == 0, err.getvalue()
    assert line["metrics"] == {}            # no device metric off the chip
    args = stepgaps.step_args(seen["ctx"])
    passes = [a["prefill"] for a in args if "prefill" in a]
    assert passes and all(isinstance(p[0], list) and len(p) == 1
                          for p in passes)
    fed = sum(e[3] for p in passes for e in p)
    prompt = sum(sum(a["n"]) - sum(a["emit"]) for a in args)
    got = seen["metrics"]
    assert got["prefill_position_share"] == pytest.approx(
        100.0 * fed / prompt)
    assert 0 < got["prefill_position_share"] <= 100
    assert got["prefill_positions_per_pass"] == pytest.approx(
        fed / len(passes))
    assert got["prefill_positions_per_pass"] >= 2
    assert "prefill_device_share.kimi" not in got


_readers = cells.load_module(os.path.join(BENCH_TESTS,
                                          "test_request_readers.py"))
_tracer = _readers._tracer
for _name in dir(_readers):
    if _name.startswith("test_"):
        globals()[_name] = getattr(_readers, _name)
# PR 39's readers: their file's test of the declarations takes the place of
# ``test_request_readers.py``'s of the same name, which holds PR 38's five
# metrics to be the LAST of ``per_layer`` and so fails from the next PR on
# that appends one (PERF.md 7: a ``benchmark`` issue's to repair there);
# the five are held by name below
_passes = cells.load_module(os.path.join(BENCH_TESTS,
                                         "test_prefill_readers.py"))
for _name in dir(_passes):
    if _name.startswith("test_"):
        globals()[_name] = getattr(_passes, _name)


def test_the_request_records_metrics_stay_declared_for_their_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m["workloads"] for m in bench["end_to_end"]
           if "workloads" in m}
    assert by_name["first_token_p50_ms"]["workloads"] \
        == by_name["loop_idle_share"]["workloads"] \
        == e2e["req_ms_per_token_p50"] == ["opt-1.3b.chat_steady"]
    assert by_name["token_gap_p99_ms"]["workloads"] == e2e["decode_tok_per_s"]
    assert by_name["prefill_pass_ms"]["workloads"] \
        == by_name["prefill_device_share"]["workloads"] \
        == ["opt-1.3b.batch_saturated"]
    for name in _readers.NEW:
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py"))


# That test holds the prefill readers' three metrics to be the LAST of
# ``per_layer``; a later cell's own metrics are appended after them. It
# runs over BENCHMARK.json as it stood when they were the last: the
# metrics appended since taken off the end, nothing else changed.
_declared = getattr(
    _passes, "test_the_new_metrics_are_declared_with_their_cells_and_readers")


def test_the_new_metrics_are_declared_with_their_cells_and_readers(
        tmp_path, monkeypatch):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    bench["per_layer"] = bench["per_layer"][
        :names.index("prefill_device_share.kimi") + 1]
    with open(os.path.join(str(tmp_path), "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    os.symlink(os.path.join(ROOT, "benchmark"), str(tmp_path / "benchmark"))
    monkeypatch.setattr(_passes, "BENCH", str(tmp_path / "benchmark"))
    _declared()
