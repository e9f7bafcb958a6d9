"""The controls, at a size a test run can hold: the plain reference put in
the program's place and computed in bfloat16 has to come out as NOT
correct by the same comparison and limits that pass the float32 one."""
import json
import os

import numpy as np
import pytest

from benchmark.harness import cells, runner

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


class FakeRun(object):
    def __init__(self):
        self.notes = {}


def _tiny(name):
    with open(os.path.join(HERE, "tiny", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def lm():
    import jax
    import jax.numpy as jnp
    cfg = dict(_tiny("tiny-lm"), vocab_size=4096, init_std=0.12)
    ref = cells.load_module(os.path.join(BENCH, "reference", "opt-1.3b.py"))
    params = ref.make_params(cfg, 3000000007)
    dev = {k: jnp.asarray(v) for k, v in params.items()}
    pad = 64

    def greedy(dtype, prompt, new):
        fwd = jax.jit(lambda p, t: ref.forward(p, t, cfg, dtype))
        seq = list(prompt)
        for _ in range(new):
            ids = np.zeros(pad, np.int32)
            ids[:len(seq)] = seq
            seq.append(int(np.argmax(np.asarray(fwd(dev, jnp.asarray(ids))
                                                )[len(seq) - 1])))
        return seq[len(prompt):]

    return cfg, ref, params, greedy, pad


def _records(greedy, dtype, vocab):
    rng = np.random.default_rng(5)
    recs = []
    for i in range(6):
        prompt = rng.integers(0, vocab, 6 + i).tolist()
        recs.append({"index": i, "prompt": prompt, "prompt_len": len(prompt),
                     "new": 40, "tokens": greedy(dtype, prompt, 40),
                     "error": None})
    return recs


def _opt_limits():
    """The limits both OPT cells are held to, from the configuration's
    file, on the numbers ``check_tokens`` reads."""
    with open(os.path.join(BENCH, "configs", "opt-1.3b.json")) as f:
        limits = json.load(f)["limits"]["default"]
    assert limits.pop("requests_failed") == 0     # read by the entry's run()
    return limits


@pytest.mark.parametrize("dtype,correct", [("float32", True),
                                           ("bfloat16", False)])
def test_served_tokens_control(lm, dtype, correct):
    """Tokens decoded in bfloat16, served in the program's place, fail the
    cells' own limit on ``gap_sq_mean``; float32 ones pass it."""
    entry = cells.load_module(os.path.join(BENCH, "entries", "decode_loop.py"))
    cfg, ref, params, greedy, pad = lm
    sample = _records(greedy, dtype, cfg["vocab_size"])
    run = FakeRun()
    readings = entry.check_tokens(run, ref, cfg, params, sample, pad, "all")
    ok, compared = runner.compare(readings, _opt_limits())
    assert run.notes["tokens_compared"] == 240
    assert ok is correct, compared
    assert compared["gap_sq_mean"]["ok"] is correct
    if dtype == "float32":
        assert run.notes["control.fp8"]["gap_sq_mean"] \
            > run.notes["control.bf16"]["gap_sq_mean"]


@pytest.mark.parametrize("control", ["bf16", "fp8"])
def test_control_goes_through_the_comparison_and_fails(lm, control):
    """``--control <name>``: the lower precision's first token at each
    position of the SAME prompts and served tokens is held to the cells'
    own limits by ``runner.compare`` and comes out not correct, by
    ``gap_sq_mean``; the program's own readings are noted beside it."""
    entry = cells.load_module(os.path.join(BENCH, "entries", "decode_loop.py"))
    cfg, ref, params, greedy, pad = lm
    sample = _records(greedy, "float32", cfg["vocab_size"])
    run = FakeRun()
    readings = entry.check_tokens(run, ref, cfg, params, sample, pad, control)
    limits = _opt_limits()
    ok, compared = runner.compare(readings, limits)
    assert ok is False and compared["gap_sq_mean"]["ok"] is False, compared
    assert compared["bad_requests"]["ok"]
    assert runner.compare(run.notes["program"], limits)[0] is True


def test_training_control_and_planted_faults():
    """The reference put in the program's place, over both warm-up
    dispatches (the second starts from the first's state, as donated state
    does): sound it passes the limits; in bfloat16, and with each fault
    planted, it fails them, and the number that catches it is named."""
    import jax
    entry = cells.load_module(os.path.join(BENCH, "entries", "module_fit.py"))
    ref = cells.load_module(os.path.join(BENCH, "reference", "resnet50.py"))
    cfg = _tiny("tiny-resnet")
    batch, k = 8, int(cfg["fit"]["steps_per_dispatch"])
    params0, _ = ref.make_params(cfg, 3000000011)
    batches = ref.make_batches(cfg, 3000000011, k, batch)
    blocks = [batches, batches]
    true = entry.follow(ref, cfg, params0, blocks)
    limits = cfg["limits"]["default"]

    def verdict(other):
        readings, _ = entry.read_gaps(true, other, ())
        return runner.compare(readings, {n: v for n, v in limits.items()
                                         if n in readings})

    assert verdict(entry.follow(ref, cfg, params0, blocks))[0] is True
    faults = entry.variants_of(batch, 1)
    assert sorted(faults) == ["bf16", "half_batch", "one_pass", "unchanged",
                              "weights_lr"]
    assert verdict(entry.follow(ref, cfg, params0, blocks,
                                **faults["bf16"]))[0] is False
    assert verdict(entry.follow(ref, cfg, params0, blocks,
                                **faults["half_batch"]))[0] is False
    # every kernel and matrix updated at half the rate, BatchNorm sound:
    # the median over all leaves is the BatchNorm leaves' and may pass;
    # the median over the leaves of two or more dimensions does not
    ok, compared = verdict(entry.follow(ref, cfg, params0, blocks,
                                        **faults["weights_lr"]))
    assert ok is False
    assert compared["momentum_gap_weights"]["value"] > 0.3
    assert compared["dparam_gap_weights"]["ok"] is False
    # a step that returns its state unchanged: no momentum, no change
    zeros = jax.tree_util.tree_map(np.zeros_like, true[0][1])
    ok, compared = verdict([(t[0], zeros, zeros) for t in true])
    assert ok is False
    assert compared["dparam_gap_median"]["value"] == pytest.approx(1.0)
    # a fault that starts with the second dispatch is the second's to catch
    ok, compared = verdict([true[0], (true[1][0], zeros, zeros)])
    assert ok is False and compared["dparam_gap_median"]["ok"]
    assert compared["dparam_gap_median_2"]["value"] == pytest.approx(1.0)


def test_leaf_gap_is_the_gap_of_norms_by_the_worst_leaf():
    entry = cells.load_module(os.path.join(BENCH, "entries", "module_fit.py"))
    ref = {"a": np.ones(4), "b": 0.001 * np.ones(4), "c": 2 * np.ones(4)}
    prog = {"a": 1.1 * np.ones(4), "b": 0.002 * np.ones(4), "c": -2 * np.ones(4)}
    gap, where = entry.leaf_gap(prog, ref)
    # c: equal norms, no gap; b: small leaf, held against the median leaf
    assert where == "a" and gap == pytest.approx(0.1)
    assert entry.leaf_gap(prog, ref, skip=("a",))[0] == pytest.approx(
        0.002 / 2.001)
    nan = dict(prog, c=np.full(4, np.nan))
    assert entry.leaf_gap(nan, ref)[1] == "c"
