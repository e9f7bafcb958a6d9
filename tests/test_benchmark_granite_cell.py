"""The ``granite-4.0-h-micro.batch_wide`` cell's files: the configuration
against the model's published ``config.json`` (every key, nothing cut),
what ``BENCHMARK.json`` says of the cell, and a whole run of the
benchmark's own ``decode_loop`` entry over the configuration's builder,
reference and readers at a tiny size on the CPU (the widths cut, which
only a test may do): ``correct`` comes out true, and false under the
``fp8`` control. The harness's own tests of these files
(``benchmark/tests/test_granite_cell.py``: the work functions against
hand counts, the readers on the recorded trace) run here too, so that
tier-1 holds them."""
import importlib.util
import io
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "granite-4.0-h-micro"
CELL = CONFIG + ".batch_wide"
SEED = 3000043007
#: granite-4.0-h-micro's config.json as published (the source's keys)
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


def _json(*rel):
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def test_the_configuration_is_the_published_one_whole():
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    entry, = [c for c in _json("BENCHMARK.json")["configs"]
              if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and "config.json" in cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == []
    assert len(entry["why"]) <= 200
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert "One whole replica a chip" in cfg["deployment"]
    assert cfg["serve"] == {"slots": 64, "max_len": 1024, "quantize": "bf16"}
    assert {"head", "head_dim", "dtype", "state", "conv_state", "reset",
            "weights", "max_len", "limits", "control"} <= set(cfg["assumed"])


def test_the_cell_is_the_one_declared():
    bench = _json("BENCHMARK.json")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG, traffic="batch_wide", chips=1)
    assert len(cell["why"]) <= 200
    # the mix is the Kimi and LFM2 cells' file, unedited
    mix = _json("benchmark", "traffic", "batch_wide.json")
    assert (mix["clients"], mix["multiset"], mix["lead_completions"]) \
        == (128, 128, 64)


def test_the_reference_imports_nothing_of_the_system():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           CONFIG + ".py")) as f:
        text = f.read()
    assert "import mxnet_tpu" not in text and "from mxnet_tpu" not in text


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A throw-away root: the benchmark's files as committed, and beside
    them a tiny cut of the configuration under the same builder, reference
    and readers."""
    root = str(tmp_path_factory.mktemp("granite_root"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=10, vocab_size=97, intermediate_size=96,
               shared_intermediate_size=96, mamba_n_heads=8, mamba_d_head=16,
               mamba_d_state=16, attention_multiplier=1 / 16.0,
               layer_types=(["mamba"] * 4 + ["attention"]) * 2,
               embed_std=1.0, init_std=0.3, ssm_in_std=0.4, out_std=2.0,
               # float32 on the CPU: the program then IS the reference to
               # rounding, and the reading does not hang on which requests
               # a loaded machine finished (bfloat16 through the loop:
               # tests/test_granite_hybrid_arch.py, on fixed requests)
               dtype="float32",
               serve={"slots": 3, "max_len": 64, "quantize": "none"},
               check={"requests": 6, "pad_to": {"default": 64}},
               limits={"default": {"gap_sq_mean": 1e-3, "bad_requests": 0,
                                   "requests_failed": 0}})
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-granite.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(root, "benchmark", "reference", CONFIG + ".py"),
                os.path.join(root, "benchmark", "reference",
                             "tiny-granite.py"))
    mix = dict(_json("benchmark", "traffic", "batch_wide.json"), clients=5,
               multiset=8, lead_completions=2,
               prompt_len=[[0, 3], [0.5, 6], [1, 10]],
               new_tokens=[[0, 4], [0.5, 10], [1, 20]])
    with open(os.path.join(root, "benchmark", "traffic", "tiny_wide.json"),
              "w") as f:
        json.dump(mix, f)
    bench = _json("BENCHMARK.json")
    bench["configs"] = [{"name": "tiny-granite", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "benchmark/configs/tiny-granite.json"}]
    bench["workloads"] = [{"name": "tiny-granite.wide",
                           "config": "tiny-granite", "traffic": "tiny_wide",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-granite.wide"] * (CELL in m["workloads"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _drive(root, control=""):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import run as bench_run
    out, err = io.StringIO(), io.StringIO()
    line = bench_run.run_cell("tiny-granite.wide", SEED, 2.0, 0,
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
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    path = os.path.join(ROOT, "benchmark", "tests", "test_granite_cell.py")
    spec = importlib.util.spec_from_file_location("bench_test_granite_cell",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", [
    "test_the_model_is_whole",
    "test_the_work_functions_against_hand_counts",
    "test_the_readers_on_the_recorded_trace",
    "test_the_readers_return_nothing_where_there_is_nothing",
    "test_the_new_cell_and_metrics_are_declared"])
def test_the_harness_tests_of_the_cells_files(name, tmp_path, monkeypatch):
    """``test_the_new_cell_and_metrics_are_declared`` holds the cell's OWN
    metrics to PR 43's: it runs over BENCHMARK.json with the two prefill
    metrics of the cell alone taken off (they are held by name below),
    nothing else changed."""
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
    "prefill_position_share.granite": ("prefill_position_share",
                                       "program_span", "Decode", "higher"),
    "prefill_device_share.granite": ("prefill_device_share", "device_trace",
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
    # appended after every metric the benchmark had
    assert [m["name"] for m in bench["per_layer"][-2:]] \
        == ["prefill_position_share.granite", "prefill_device_share.granite"]
    # the shared reader's own declaration names its cells, not this one
    base, = [m for m in bench["per_layer"] if m["name"] == shared]
    assert CELL not in base["workloads"]
    assert (base["unit"], base["better"], base["layer"], base["source"]) \
        == (entry["unit"], better, layer, source)


@pytest.mark.parametrize("name", sorted(PASS_METRICS))
def test_the_pass_metrics_read_what_the_shared_readers_read(name):
    """On made-up spans and a made-up trace (two passes of 2 ms between
    eight steps of 8 ms): the reading of the reader each delegates to;
    nothing where the loop has no prefill program, as the parent's Granite
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
