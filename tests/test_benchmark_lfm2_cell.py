"""The ``lfm2-24b-a2b-ep8.batch_wide`` cell's files (PERF.md, PR 34): what
``BENCHMARK.json`` says of the cell, the configuration against the model's
published ``config.json``, and a whole run of the benchmark's own
``decode_loop`` entry over the configuration's builder, reference and
readers at a tiny size on the CPU (the widths cut, which only a test may
do): ``correct`` comes out true, and false under the ``fp8`` control. The
harness's own tests of these files (``benchmark/tests/test_lfm2_cell.py``:
the work functions against hand counts, the scope readers on the recorded
trace) run here too, so that tier-1 holds them."""
import importlib.util
import io
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "lfm2-24b-a2b-ep8.batch_wide"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: LFM2-24B-A2B's config.json, as the catalog has it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv", "full_attention", "conv"] * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
REDUCED = {"num_experts": 8}


def _json(*rel):
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def test_the_configuration_is_the_published_one_but_for_the_experts_held():
    cfg = _json("benchmark", "configs", "lfm2-24b-a2b-ep8.json")
    entry, = [c for c in _json("BENCHMARK.json")["configs"]
              if c["name"] == "lfm2-24b-a2b-ep8"]
    assert entry["source"] == cfg["source"] and "config.json" in cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == sorted(REDUCED)
    assert len(entry["why"]) <= 200
    for key, value in PUBLISHED.items():
        assert cfg[key] == REDUCED.get(key, value), key
    assert cfg["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert cfg["router_width"] == PUBLISHED["num_experts"]
    assert cfg["share_index"] == 0
    assert "8 chips share each layer" in cfg["deployment"]
    assert cfg["serve"] == {"slots": 64, "max_len": 1024, "quantize": "bf16"}
    assert cfg["layer_types"].count("conv") == 30
    assert {"head", "head_dim", "route_eps", "dtype", "weights",
            "max_len"} <= set(cfg["assumed"])
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "LFM2-24B-A2B"]
        assert row["config"] == PUBLISHED
        assert row["source_url"] == cfg["source"]


def test_the_cell_is_what_the_issue_named():
    bench = _json("BENCHMARK.json")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config="lfm2-24b-a2b-ep8", traffic="batch_wide",
                        chips=1) and len(cell["why"]) <= 200
    assert bench["workloads"][4] is cell
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if "workloads" not in m or CELL in m["workloads"]}
    assert reports == {
        "decode_tok_per_s", "setup_s", "compile_s", "decode_step_ms_p50",
        "slot_occupancy", "prompt_step_share", "decode_gap_feed_ms",
        "decode_gap_dispatch_ms", "decode_gap_readback_ms",
        "decode_gap_commit_ms", "decode_gap_covered", "emitted_tok_per_s",
        "token_gap_p99_ms", "moe_here_share", "moe_load_max_over_mean",
        "conv_layer_roofline", "gqa_layer_roofline",
        "decode_step_roofline.lfm2", "decode_mfu.lfm2",
        # the packed prefill pass's engagement and its cost
        "prefill_position_share.lfm2", "prefill_device_share.lfm2"}
    # NOT moe_layer_roofline: the compiler fetches this model's 50 MB expert
    # stacks into fast memory under no scope, ahead of the product, so the
    # time under the expert layer's scopes leaves that out and the share
    # read 108.85% on the chip (PERF.md, PR 34); a share past 105% is refused
    assert "moe_layer_roofline" not in reports
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["conv_layer_roofline"] == "Short convolution"
    assert layers["gqa_layer_roofline"] == "Grouped-query attention"
    for m in bench["per_layer"]:
        if m.get("workloads") == [CELL]:
            assert m["moves"] == "decode_tok_per_s" and m["unit"] == "%"
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "metrics", m["name"] + ".py"))


def test_the_reference_imports_nothing_of_the_system():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "lfm2-24b-a2b-ep8.py")) as f:
        text = f.read()
    assert "import mxnet_tpu" not in text and "from mxnet_tpu" not in text


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A throw-away root: the benchmark's files as committed, and beside
    them a tiny cut of the configuration under the same builder, reference
    and readers."""
    root = str(tmp_path_factory.mktemp("lfm2_root"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = _json("benchmark", "configs", "lfm2-24b-a2b-ep8.json")
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=6, vocab_size=97, intermediate_size=96,
               moe_intermediate_size=32, num_experts=4, router_width=16,
               share_index=1, num_experts_per_tok=2,
               layer_types=["conv", "conv", "full_attention", "conv",
                            "full_attention", "conv"],
               init_std=0.2, embed_std=0.5, router_std=0.2,
               router_bias_std=0.2,
               # float32 on the CPU: the program then IS the reference to
               # rounding, and the reading does not hang on which six
               # requests a loaded machine finished (bfloat16 through the
               # loop: tests/test_lfm2_arch.py, on fixed requests)
               dtype="float32",
               serve={"slots": 3, "max_len": 64, "quantize": "none"},
               check={"requests": 6, "pad_to": {"default": 64}},
               limits={"default": {"gap_sq_mean": 1e-3, "bad_requests": 0,
                                   "requests_failed": 0}})
    with open(os.path.join(root, "benchmark", "configs", "tiny-lfm2.json"),
              "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(root, "benchmark", "reference",
                             "lfm2-24b-a2b-ep8.py"),
                os.path.join(root, "benchmark", "reference", "tiny-lfm2.py"))
    mix = dict(_json("benchmark", "traffic", "batch_wide.json"), clients=5,
               multiset=8, lead_completions=2,
               prompt_len=[[0, 3], [0.5, 6], [1, 10]],
               new_tokens=[[0, 4], [0.5, 10], [1, 20]])
    with open(os.path.join(root, "benchmark", "traffic", "tiny_wide.json"),
              "w") as f:
        json.dump(mix, f)
    bench = _json("BENCHMARK.json")
    bench["configs"] = [{"name": "tiny-lfm2", "source": "test", "reduced": [],
                         "file": "benchmark/configs/tiny-lfm2.json",
                         "why": "test"}]
    bench["workloads"] = [{"name": "tiny-lfm2.wide", "config": "tiny-lfm2",
                           "traffic": "tiny_wide", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-lfm2.wide"] * (CELL in m["workloads"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _drive(root, control=""):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import run as bench_run
    out, err = io.StringIO(), io.StringIO()
    line = bench_run.run_cell("tiny-lfm2.wide", 3000034007, 2.0, 0,
                              control=control, root=root, require_chip=False,
                              compile_cache=False, out=out, err=err)
    assert json.loads(out.getvalue().strip().splitlines()[-1]) \
        == json.loads(json.dumps(line))
    return line, err.getvalue()


def test_the_cell_runs_through_the_benchmarks_own_entry(tiny_root):
    line, err = _drive(tiny_root)
    assert line["failed"] == 0, err
    assert line["correct"], err
    assert line["device"]["platform"] == "cpu" and line["metrics"] == {}
    assert line["counts"]["tokens_generated"] > 0
    # float32 against float32: the served token IS the reference's best
    assert line["compared"]["gap_sq_mean"]["value"] < 1e-6
    assert "note tokens_compared" in err


def test_the_fp8_control_comes_out_not_correct(tiny_root):
    line, err = _drive(tiny_root, control="fp8")
    assert not line["correct"] and line["failed"] == 0
    assert not line["compared"]["gap_sq_mean"]["ok"]
    assert "note program" in err        # the program's own readings, noted


def _harness_tests():
    path = os.path.join(ROOT, "benchmark", "tests", "test_lfm2_cell.py")
    spec = importlib.util.spec_from_file_location("bench_test_lfm2_cell",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", [
    "test_the_cut_is_what_the_issue_reckoned",
    "test_the_three_work_functions_against_hand_counts",
    "test_the_scope_readers_on_the_recorded_trace",
    "test_the_scope_readers_return_nothing_where_there_is_nothing",
    "test_the_new_cell_and_metrics_are_declared"])
def test_the_harness_tests_of_the_cells_files(name, tmp_path, monkeypatch):
    """``test_the_new_cell_and_metrics_are_declared`` holds the cell's OWN
    metrics to PR 34's four: it runs over BENCHMARK.json as it stood
    then, the two prefill metrics of the cell alone taken off
    (they are held by name below), nothing else changed."""
    mod = _harness_tests()
    if name == "test_the_new_cell_and_metrics_are_declared":
        bench = _json("BENCHMARK.json")
        bench["per_layer"] = [m for m in bench["per_layer"]
                              if m["name"] not in PASS_METRICS]
        with open(os.path.join(str(tmp_path), "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
        monkeypatch.setattr(mod, "REPO", str(tmp_path))
    fn = getattr(mod, name)
    wants = fn.__code__.co_varnames[:fn.__code__.co_argcount]
    have = {"cfg": mod.load_cfg(), "ref": mod.load_ref()}
    if "ctx" in wants:
        have["ctx"] = mod.make_ctx(have["ref"])
    fn(**{k: have[k] for k in wants})


# ---------------------------------------------------------------------------
# the packed prefill pass's two metrics in this cell
# ---------------------------------------------------------------------------

#: name -> (the reader it delegates to, source, layer, better)
PASS_METRICS = {
    "prefill_position_share.lfm2": ("prefill_position_share", "program_span",
                                    "Decode", "higher"),
    "prefill_device_share.lfm2": ("prefill_device_share", "device_trace",
                                  "Ops", "lower")}


def _prefill_reader_tests():
    path = os.path.join(ROOT, "benchmark", "tests", "test_prefill_readers.py")
    spec = importlib.util.spec_from_file_location("bench_test_prefill", path)
    mod = importlib.util.module_from_spec(spec)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", sorted(PASS_METRICS))
def test_the_pass_metrics_are_declared_for_this_cell(name):
    bench = _json("BENCHMARK.json")
    entry, = [m for m in bench["per_layer"] if m["name"] == name]
    shared, source, layer, better = PASS_METRICS[name]
    assert entry == {"name": name, "unit": "%", "better": better,
                     "source": source, "layer": layer,
                     "moves": "decode_tok_per_s", "workloads": [CELL]}
    # the shared reader's own declaration names its cells, not this one
    base, = [m for m in bench["per_layer"] if m["name"] == shared]
    assert CELL not in base["workloads"]
    assert (base["unit"], base["better"], base["layer"], base["source"]) \
        == (entry["unit"], better, layer, source)


@pytest.mark.parametrize("name", sorted(PASS_METRICS))
def test_the_pass_metrics_read_what_the_shared_readers_read(name):
    """On made-up spans and a made-up trace (two passes of 2 ms between
    eight steps of 8 ms): the reading of the reader each delegates to;
    nothing where the loop has no prefill program, as the parent's LFM2
    loop has none."""
    from benchmark.harness import tracered
    mod = _prefill_reader_tests()
    ctx = mod.made_up_ctx()
    ms = mod.MS
    modules, ops = [], []
    for i in range(8):
        t = i * 10 * ms
        if i in (3, 6):
            modules.append(("jit_prefill_fn(5)", t - 2 * ms, t))
            ops.append(("fusion.7", t - 2 * ms, t))
        modules.append(("jit_decode_fn(3)", t, t + 8 * ms))
        ops.append(("fusion.1", t, t + 8 * ms))
    ctx["trace"] = tracered.Trace({"/device:TPU:0": {"ops": ops,
                                                     "modules": modules}})
    shared = PASS_METRICS[name][0]
    got = mod.reader(name).read(ctx)
    assert got == mod.reader(shared).read(ctx)
    assert got == pytest.approx(100.0 * (59 + 63 + 16) / 148
                                if shared == "prefill_position_share"
                                else 100.0 * 4 / (8 * 8 + 4))
    ctx["spans"] = [("loop_program", 0, 1, {"program": "jit_decode_fn",
                                            "scopes": {}})] + ctx["spans"][1:]
    assert mod.reader(name).read(ctx) is None
