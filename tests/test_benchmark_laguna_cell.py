"""The ``laguna-s-2.1-ep8.long_generation`` cell's files (PERF.md): what
``BENCHMARK.json`` says of the cell, the configuration against the model's
published ``config.json``, and a whole run of the benchmark's own
``decode_loop`` entry over the configuration's builder, reference and
readers at a tiny size on the CPU (the widths cut, which only a test may
do; a window of 8, so every request wraps the ring): ``correct`` comes out
true, and false under the ``fp8`` control and with the window or the gate
lifted (``benchmark/tools/lifted_control.py``). The harness's own tests of
these files (``benchmark/tests/test_laguna_cell.py``: the work functions
against hand counts, the readers on the recorded trace) run here too, so
that tier-1 holds them."""
import importlib.util
import io
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "laguna-s-2.1-ep8.long_generation"
CONFIG = "laguna-s-2.1-ep8"
SEED = 3000041007
F, S = "full_attention", "sliding_attention"
#: Laguna-S-2.1's config.json (poolside/Laguna-S-2.1)
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
    "intermediate_size": 12288, "num_hidden_layers": 48,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": [F, S, S, S] * 12,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
    "moe_router_logit_softcapping": 0}
REDUCED = {"num_hidden_layers": 12, "num_experts": 32, "vocab_size": 12544}


def _json(*rel):
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def test_the_configuration_is_the_published_one_but_for_three_keys():
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    entry, = [c for c in _json("BENCHMARK.json")["configs"]
              if c["name"] == CONFIG]
    assert entry["source"] == cfg["source"] and "config.json" in cfg["source"]
    assert "poolside/Laguna-S-2.1" in cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == sorted(REDUCED)
    assert len(entry["why"]) <= 200
    for key, value in PUBLISHED.items():
        assert cfg[key] == REDUCED.get(key, value), key
    assert cfg["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert (cfg["router_width"], cfg["share_index"]) == (256, 0)
    assert "32 chips" in cfg["deployment"] \
        and "8 chips share each layer" in cfg["deployment"]
    assert cfg["serve"] == {"slots": 64, "max_len": 3584, "quantize": "bf16"}
    assert cfg["check"] == {"requests": 16, "pad_to": {"default": 3584}}
    assert {"qk_norm", "router", "shared_expert", "stage", "dtype",
            "weights", "max_len", "limits", "control"} <= set(cfg["assumed"])


def test_the_cell_is_the_one_declared():
    bench = _json("BENCHMARK.json")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG, traffic="long_generation",
                        chips=1) and len(cell["why"]) <= 200
    assert bench["workloads"][6] is cell
    (tok,) = [m for m in bench["end_to_end"]
              if m["name"] == "decode_tok_per_s"]
    assert CELL in tok["workloads"]
    # it reports what the Mellum cell reports, its own two shares of the
    # whole step in the place of Mellum's
    mellum = "mellum2-12b-a2.5b-ep4.long_generation"
    mine = {m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    theirs = {m["name"] for m in bench["per_layer"]
              if mellum in m.get("workloads", ())}
    assert mine - theirs == {"decode_step_roofline.laguna",
                             "decode_mfu.laguna"}
    assert theirs - mine == {"decode_step_roofline.mellum2",
                             "decode_mfu.mellum2"}


def test_the_reference_imports_nothing_of_the_system():
    for rel in (("reference", CONFIG + ".py"),
                ("tools", "lifted_control.py")):
        with open(os.path.join(ROOT, "benchmark", *rel)) as f:
            text = f.read()
        assert "import mxnet_tpu" not in text and "from mxnet_tpu" not in text


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A throw-away root: the benchmark's files as committed, and beside
    them a tiny cut of the configuration under the same builder, reference
    and readers."""
    root = str(tmp_path_factory.mktemp("laguna_root"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = _json("benchmark", "configs", CONFIG + ".json")
    cfg.update(hidden_size=64, num_key_value_heads=2, head_dim=16,
               num_hidden_layers=5, vocab_size=97, intermediate_size=96,
               moe_intermediate_size=32, shared_expert_intermediate_size=24,
               num_experts=4, router_width=16, share_index=1,
               num_experts_per_tok=3, sliding_window=8,
               num_attention_heads_per_layer=[4, 6, 6, 6] * 12,
               init_std=0.2, head_std=0.3, router_std=0.2, gate_std=0.15,
               # float32 on the CPU: the program then IS the reference to
               # rounding, and the reading does not hang on which requests
               # a loaded machine finished (bfloat16 through the loop:
               # tests/test_laguna_arch.py, on fixed requests)
               dtype="float32",
               serve={"slots": 3, "max_len": 64, "quantize": "none"},
               check={"requests": 6, "pad_to": {"default": 64}},
               limits={"default": {"gap_sq_mean": 1e-3, "bad_requests": 0,
                                   "requests_failed": 0}})
    with open(os.path.join(root, "benchmark", "configs", "tiny-laguna.json"),
              "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(root, "benchmark", "reference", CONFIG + ".py"),
                os.path.join(root, "benchmark", "reference",
                             "tiny-laguna.py"))
    # every request runs past the window of 8
    mix = dict(_json("benchmark", "traffic", "long_generation.json"),
               clients=5, multiset=8, lead_completions=2,
               prompt_len=[[0, 3], [0.5, 6], [1, 10]],
               new_tokens=[[0, 10], [0.5, 20], [1, 40]])
    with open(os.path.join(root, "benchmark", "traffic", "tiny_long.json"),
              "w") as f:
        json.dump(mix, f)
    bench = _json("BENCHMARK.json")
    bench["configs"] = [{"name": "tiny-laguna", "source": "test",
                         "reduced": [], "why": "test",
                         "file": "benchmark/configs/tiny-laguna.json"}]
    bench["workloads"] = [{"name": "tiny-laguna.long",
                           "config": "tiny-laguna", "traffic": "tiny_long",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-laguna.long"] * (CELL in m["workloads"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _bench_module(*rel):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    path = os.path.join(ROOT, "benchmark", *rel)
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _drive(root, control=""):
    from benchmark import run as bench_run
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    out, err = io.StringIO(), io.StringIO()
    line = bench_run.run_cell("tiny-laguna.long", SEED, 2.0, 0,
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


def test_the_window_and_the_gate_lifted_come_out_not_correct(tiny_root):
    tool = _bench_module("tools", "lifted_control.py")
    out, err = io.StringIO(), io.StringIO()
    result = tool.run_lifted("tiny-laguna.long", SEED, 2.0, root=tiny_root,
                             require_chip=False, compile_cache=False,
                             out=out, err=err)
    assert set(result) == {"window_lifted", "gate_lifted"}
    for name, res in result.items():
        assert not res["correct"], (name, err.getvalue())
        gap = res["compared"]["gap_sq_mean"]
        assert not gap["ok"] and gap["value"] > 10 * gap["limit"], name
        assert res["compared"]["requests_failed"]["value"] == 0
    # the program's own readings are noted beside them, and are sound
    program = [ln for ln in err.getvalue().splitlines()
               if ln.startswith("note program ")]
    assert program and json.loads(program[0].split(" ", 2)[2])[
        "gap_sq_mean"] < 1e-6


@pytest.mark.parametrize("name", [
    "test_the_cut_is_the_deployments_share",
    "test_the_work_functions_against_hand_counts",
    "test_the_readers_on_the_recorded_trace",
    "test_the_readers_return_nothing_where_there_is_nothing",
    "test_the_new_cell_and_metrics_are_declared"])
def test_the_harness_tests_of_the_cells_files(name):
    mod = _bench_module("tests", "test_laguna_cell.py")
    fn = getattr(mod, name)
    wants = fn.__code__.co_varnames[:fn.__code__.co_argcount]
    have = {"cfg": mod.load_cfg(), "ref": mod.load_ref()}
    if "ctx" in wants:
        have["ctx"] = mod.make_ctx(have["ref"])
    fn(**{k: have[k] for k in wants})
