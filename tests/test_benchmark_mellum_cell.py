"""The ``mellum2-12b-a2.5b-ep4.long_generation`` cell's files (PERF.md, PR
36): what ``BENCHMARK.json`` says of the cell, the configuration against
the model's published ``config.json``, and a whole run of the benchmark's
own ``decode_loop`` entry over the configuration's builder, reference and
readers at a tiny size on the CPU (the widths cut, which only a test may
do; a window of 8, so every request wraps the ring): ``correct`` comes out
true, and false under the ``fp8`` control and with the window lifted. The
harness's own tests of these files (``benchmark/tests/test_mellum2_cell.py``:
the work functions against hand counts, the readers on the recorded trace)
run here too, so that tier-1 holds them."""
import importlib.util
import io
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "mellum2-12b-a2.5b-ep4.long_generation"
CONFIG = "mellum2-12b-a2.5b-ep4"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: Mellum2-12B-A2.5B-Instruct's config.json, as the catalog has it
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
REDUCED = {"num_experts": 16}


def _json(*rel):
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def test_the_configuration_is_the_published_one_but_for_the_experts_held():
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    entry, = [c for c in _json("BENCHMARK.json")["configs"]
              if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and "config.json" in cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == sorted(REDUCED)
    assert len(entry["why"]) <= 200
    for key, value in PUBLISHED.items():
        assert cfg[key] == REDUCED.get(key, value), key
    assert cfg["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert cfg["router_width"] == PUBLISHED["num_experts"]
    assert cfg["share_index"] == 0
    assert "4 chips share each layer" in cfg["deployment"]
    assert cfg["serve"] == {"slots": 32, "max_len": 4096, "quantize": "bf16"}
    assert cfg["layer_types"].count("sliding_attention") == 21
    assert cfg["check"] == {"requests": 16, "pad_to": {"default": 3584}}
    assert {"qk_norm", "router", "rope", "window", "mtp", "dtype", "weights",
            "max_len", "limits", "control"} <= set(cfg["assumed"])
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "Mellum2-12B-A2.5B-Instruct"]
        assert row["config"] == PUBLISHED
        assert row["source_url"] == cfg["source"]


def test_the_cell_is_what_the_issue_named():
    bench = _json("BENCHMARK.json")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG, traffic="long_generation",
                        chips=1) and len(cell["why"]) <= 200
    assert bench["workloads"][5] is cell
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if "workloads" not in m or CELL in m["workloads"]}
    always = {
        "decode_tok_per_s", "setup_s", "compile_s", "decode_step_ms_p50",
        "slot_occupancy", "prompt_step_share", "decode_gap_feed_ms",
        "decode_gap_dispatch_ms", "decode_gap_readback_ms",
        "decode_gap_commit_ms", "decode_gap_covered", "emitted_tok_per_s",
        "token_gap_p99_ms", "moe_here_share", "moe_load_max_over_mean",
        "decode_step_roofline.mellum2", "decode_mfu.mellum2",
        "ring_wrapped_share", "ring_rows_share"}
    # a share of a roofline is listed only where it read under 100% on the
    # chip with the step as committed (PERF.md, PR 36): the whole step's
    # and at least one of the two attention kinds' are
    maybe = {"window_attn_layer_roofline", "full_attn_layer_roofline",
             "moe_layer_roofline"}
    assert always <= reports <= always | maybe
    assert reports & {"window_attn_layer_roofline",
                      "full_attn_layer_roofline"}
    # its prefixes would sum both kinds of attention layer
    assert "gqa_layer_roofline" not in reports
    layers = {m["name"]: m["layer"] for m in bench["per_layer"]}
    assert layers["ring_wrapped_share"] == layers["ring_rows_share"] \
        == "Window attention"
    for m in bench["per_layer"]:
        if m.get("workloads") == [CELL]:
            assert m["moves"] == "decode_tok_per_s" and m["unit"] == "%"
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "metrics", m["name"] + ".py"))


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
    root = str(tmp_path_factory.mktemp("mellum_root"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    rope = cfg["rope_parameters"]
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, num_hidden_layers=5, vocab_size=97,
               moe_intermediate_size=32, num_experts=4, router_width=16,
               share_index=1, num_experts_per_tok=2, sliding_window=8,
               layer_types=["sliding_attention"] * 3
               + ["full_attention", "sliding_attention"],
               mlp_layer_types=["sparse"] * 5,
               rope_parameters=dict(rope, full_attention=dict(
                   rope["full_attention"],
                   original_max_position_embeddings=16)),
               init_std=0.2, head_std=0.3, router_std=0.2,
               # float32 on the CPU: the program then IS the reference to
               # rounding, and the reading does not hang on which requests
               # a loaded machine finished (bfloat16 through the loop:
               # tests/test_mellum_arch.py, on fixed requests)
               dtype="float32",
               serve={"slots": 3, "max_len": 64, "quantize": "none"},
               check={"requests": 6, "pad_to": {"default": 64}},
               limits={"default": {"gap_sq_mean": 1e-3, "bad_requests": 0,
                                   "requests_failed": 0}})
    with open(os.path.join(root, "benchmark", "configs", "tiny-mellum.json"),
              "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(root, "benchmark", "reference", CONFIG + ".py"),
                os.path.join(root, "benchmark", "reference",
                             "tiny-mellum.py"))
    # every request runs past the window of 8
    mix = dict(_json("benchmark", "traffic", "long_generation.json"),
               clients=5, multiset=8, lead_completions=2,
               prompt_len=[[0, 3], [0.5, 6], [1, 10]],
               new_tokens=[[0, 10], [0.5, 20], [1, 40]])
    with open(os.path.join(root, "benchmark", "traffic", "tiny_long.json"),
              "w") as f:
        json.dump(mix, f)
    bench = _json("BENCHMARK.json")
    bench["configs"] = [{"name": "tiny-mellum", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "benchmark/configs/tiny-mellum.json"}]
    bench["workloads"] = [{"name": "tiny-mellum.long",
                           "config": "tiny-mellum", "traffic": "tiny_long",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-mellum.long"] * (CELL in m["workloads"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _drive(root, control=""):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import run as bench_run
    out, err = io.StringIO(), io.StringIO()
    line = bench_run.run_cell("tiny-mellum.long", 3000036007, 2.0, 0,
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


def test_a_reference_with_its_window_lifted_is_another_model(tiny_root):
    """Serving the window layers as full ones would not pass: the tokens a
    reference WITHOUT the band puts first lie under the true reference's
    best by far more than the limit, at contexts past the window."""
    import numpy as np
    import jax.numpy as jnp
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.harness import cells
    cell = cells.Cell(tiny_root, "tiny-mellum.long")
    ref, cfg = cell.reference(), cell.config
    p = ref.make_params(cfg, 3000036007)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 97, 40),
                       jnp.int32)
    true = np.asarray(ref.forward(p, toks, cfg))
    lifted = np.asarray(ref.forward(p, toks, dict(cfg, sliding_window=4096)))
    # the same model until a position has more than a window behind it
    np.testing.assert_allclose(lifted[:8], true[:8], rtol=1e-5, atol=1e-5)
    first = lifted[8:].argmax(-1)
    gap = true[8:].max(-1) - true[8:][np.arange(32), first]
    assert float(np.mean(gap * gap)) > 10 * cell.limits()["gap_sq_mean"]


def _harness_tests():
    path = os.path.join(ROOT, "benchmark", "tests", "test_mellum2_cell.py")
    spec = importlib.util.spec_from_file_location("bench_test_mellum2_cell",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", [
    "test_the_cut_is_what_the_issue_reckoned",
    "test_the_work_functions_against_hand_counts",
    "test_the_readers_on_the_recorded_trace",
    "test_the_readers_return_nothing_where_there_is_nothing",
    "test_the_new_cell_and_metrics_are_declared"])
def test_the_harness_tests_of_the_cells_files(name, tmp_path, monkeypatch):
    mod = _harness_tests()
    fn = getattr(mod, name)
    if name == "test_the_new_cell_and_metrics_are_declared":
        # it holds the cell's own metrics to list the cell ALONE, which a
        # later cell that reads the same metric appends itself to: it runs
        # over BENCHMARK.json as it stood when this cell was the last, the
        # later cells taken off every list, nothing else changed
        bench = _json("BENCHMARK.json")
        names = [w["name"] for w in bench["workloads"]]
        later = set(names[names.index(CELL) + 1:])
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "workloads" in m:
                m["workloads"] = [w for w in m["workloads"]
                                  if w not in later]
        with open(os.path.join(str(tmp_path), "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
        monkeypatch.setattr(mod, "REPO", str(tmp_path))
    wants = fn.__code__.co_varnames[:fn.__code__.co_argcount]
    have = {"cfg": mod.load_cfg(), "ref": mod.load_ref()}
    if "ctx" in wants:
        have["ctx"] = mod.make_ctx(have["ref"])
    fn(**{k: have[k] for k in wants})
