"""The ``kimi-k2-ep32.batch_wide`` cell's files (PERF.md, PR 29): what
``BENCHMARK.json`` says of the cell, the configuration against the model's
published ``config.json``, and a whole run of the benchmark's own
``decode_loop`` entry over the configuration's builder, reference and
readers at a tiny size on the CPU (the widths cut, which only a test may
do): ``correct`` comes out true, and false under the ``fp8`` control."""
import io
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "kimi-k2-ep32.batch_wide"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: Kimi-K2-Instruct's config.json, as the catalog has it
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "kimi_k2",
    "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 384, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 0, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 50000, "routed_scaling_factor": 2.827,
    "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = {"num_hidden_layers": 7, "n_routed_experts": 12,
           "vocab_size": 20480}


def _json(*rel):
    with open(os.path.join(ROOT, *rel)) as f:
        return json.load(f)


def test_the_configuration_is_the_published_one_but_for_its_three_cuts():
    cfg = _json("benchmark", "configs", "kimi-k2-ep32.json")
    entry, = [c for c in _json("BENCHMARK.json")["configs"]
              if c["name"] == "kimi-k2-ep32"]
    assert entry["source"] == cfg["source"] and "config.json" in cfg["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) \
        == sorted(REDUCED)
    for key, value in PUBLISHED.items():
        assert cfg[key] == REDUCED.get(key, value), key
    assert cfg["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert cfg["router_width"] == PUBLISHED["n_routed_experts"]
    assert "32 chips share each layer" in cfg["deployment"]
    assert cfg["serve"] == {"slots": 64, "max_len": 1024, "quantize": "bf16"}
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "Kimi-K2-Instruct"]
        assert row["config"] == PUBLISHED
        assert row["source_url"] == cfg["source"]


def test_the_cell_is_what_the_issue_named():
    bench = _json("BENCHMARK.json")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config="kimi-k2-ep32", traffic="batch_wide",
                        chips=1) and len(cell["why"]) <= 200
    mix = _json("benchmark", "traffic", "batch_wide.json")
    assert (mix["loop"], mix["clients"], mix["multiset"], mix["order"],
            mix["lead_completions"]) == ("closed", 128, 128, "fixed", 64)
    assert [k[1] for k in mix["prompt_len"]] == [32, 64, 128]
    assert [k[1] for k in mix["new_tokens"]] == [128, 256, 384]
    reports = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
               if "workloads" not in m or CELL in m["workloads"]}
    assert {"decode_tok_per_s", "setup_s", "decode_step_roofline.kimi",
            "decode_mfu.kimi", "moe_layer_roofline", "mla_layer_roofline",
            "moe_here_share", "moe_load_max_over_mean", "compile_s",
            "decode_step_ms_p50", "slot_occupancy", "emitted_tok_per_s",
            "decode_gap_feed_ms"} <= reports
    for m in bench["per_layer"]:
        if m.get("workloads") == [CELL]:
            assert m["moves"] == "decode_tok_per_s"
            assert os.path.isfile(os.path.join(
                ROOT, "benchmark", "metrics", m["name"] + ".py"))


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A throw-away root: the benchmark's files as committed, and beside
    them a tiny cut of the configuration under the same builder, reference
    and readers."""
    root = str(tmp_path_factory.mktemp("kimi_root"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = _json("benchmark", "configs", "kimi-k2-ep32.json")
    cfg.update(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
               kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
               v_head_dim=8, intermediate_size=96, moe_intermediate_size=32,
               num_experts_per_tok=4, n_routed_experts=4, router_width=16,
               share_index=1, num_hidden_layers=3, vocab_size=97,
               init_std=0.2, router_std=0.2, router_bias_std=0.2,
               # float32 on the CPU: the program then IS the reference to
               # rounding, and the reading does not hang on which six
               # requests a loaded machine finished (bfloat16 through the
               # loop: tests/test_deepseek_v3_decode.py, on fixed requests)
               dtype="float32",
               serve={"slots": 3, "max_len": 64, "quantize": "none"},
               check={"requests": 6, "pad_to": {"default": 64}},
               limits={"default": {"gap_sq_mean": 1e-3, "bad_requests": 0,
                                   "requests_failed": 0}})
    with open(os.path.join(root, "benchmark", "configs", "tiny-kimi.json"),
              "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(root, "benchmark", "reference",
                             "kimi-k2-ep32.py"),
                os.path.join(root, "benchmark", "reference", "tiny-kimi.py"))
    mix = dict(_json("benchmark", "traffic", "batch_wide.json"), clients=5,
               multiset=8, lead_completions=2,
               prompt_len=[[0, 3], [0.5, 6], [1, 10]],
               new_tokens=[[0, 4], [0.5, 10], [1, 20]])
    with open(os.path.join(root, "benchmark", "traffic", "tiny_wide.json"),
              "w") as f:
        json.dump(mix, f)
    bench = _json("BENCHMARK.json")
    bench["configs"] = [{"name": "tiny-kimi", "source": "test", "reduced": [],
                         "file": "benchmark/configs/tiny-kimi.json",
                         "why": "test"}]
    bench["workloads"] = [{"name": "tiny-kimi.wide", "config": "tiny-kimi",
                           "traffic": "tiny_wide", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny-kimi.wide"] * (CELL in m["workloads"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def _drive(root, control="", trace=0):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import run as bench_run
    out, err = io.StringIO(), io.StringIO()
    line = bench_run.run_cell("tiny-kimi.wide", 3000029007, 2.0, trace,
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
    assert line["compared"]["gap_sq_mean"]["value"] < 1e-6
    assert "note tokens_compared" in err


def test_the_fp8_control_comes_out_not_correct(tiny_root):
    line, err = _drive(tiny_root, control="fp8")
    assert not line["correct"] and line["failed"] == 0
    assert not line["compared"]["gap_sq_mean"]["ok"]
    assert "note program" in err        # the program's own readings, noted
