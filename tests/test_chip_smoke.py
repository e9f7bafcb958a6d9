"""chip_smoke.py's three legs at tiny shapes on CPU through the same
functions the chip run calls, plus the start-up contracts the smoke
stands on: no CPU stand-in for a missing chip, a compile cache that can be
placed, one process per chip, a replica that lives where it was told to."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, serving
from mxnet_tpu.base import MXNetError

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

import chip_smoke  # noqa: E402
import launch  # noqa: E402

IMAGE = (3, 16, 16)


@pytest.fixture(scope="module")
def meter():
    return chip_smoke.CompileMeter()


def test_train_and_deploy_legs_tiny(meter):
    mod, facts = chip_smoke.train_leg(
        meter, [mx.cpu(0)], 8, IMAGE, num_layers=18, num_classes=10,
        k=2, dispatches=2)
    assert facts["scan_keys"] == ["(8, 2)"]
    assert facts["loss_last"] < facts["loss_first"]
    assert facts["programs_compiled"] > 0
    # XLA:CPU picks its convolution kernel by batch, so the bucket-1 and
    # batch-8 programs agree to ~1e-4 here (exactly, on the v5e)
    eng, served = chip_smoke.deploy_leg(meter, mod, IMAGE, atol=1e-3)
    assert served["rows"] == [1, 3, 8]
    assert eng.devices == [jax.devices()[0]]


@pytest.mark.slow
def test_train_leg_data_parallel_tiny(meter):
    """The four-chip branch: mesh, replicated params, per-chip superbatch
    shards (the memory check needs a backend that reports memory). Slow:
    tier-1 is near its wall-clock limit and the four-chip host runs the
    real thing."""
    ctxs = [mx.cpu(i) for i in range(4)]
    mod, facts = chip_smoke.train_leg(
        meter, ctxs, 8, IMAGE, num_layers=18, num_classes=10, k=2,
        dispatches=2)
    assert len(facts["param_devices"]) == 4
    assert mod._fused.mesh.shape["data"] == 4


def test_decode_leg_tiny(meter):
    facts = chip_smoke.decode_leg(
        meter, mx.cpu(0), layers=2, embed=32, heads=2, vocab=64,
        max_len=48, slots=2, requests=3, prompt_range=(10, 20), max_new=4,
        margin=0.01)
    assert facts["first_token_checked"] >= 1
    assert facts["decode_steps"] > 0
    # prompts go in by chunks (PR 37), through a program that holds the
    # step's rule and reads its weights once
    assert 0 < facts["prefill_passes"] <= 3
    assert facts["prefill_positions"] <= facts["prompt_positions"] \
        == sum(facts["prompt_lens"]) - 3
    assert facts["prefill_program"]["chunk"] == 48
    assert facts["prefill_program"]["weight_reads_max"] == 1
    assert facts["prefill_program"]["layout_bytes_max"] < 2 * 2 * 48 * 32 * 4
    # (layers, slots, rows, heads * head_dim) float32, no cache-sized copy
    assert facts["step_program"]["cache_bytes"] == 2 * 2 * 48 * 32 * 4
    assert facts["step_program"]["layout_bytes_max"] < 2 * 2 * 48 * 32 * 4


@pytest.mark.parametrize("relaid", [True, False])
def test_cache_relayouts_sees_the_copy_the_lint_lets_through(relaid):
    """The decode leg's check on the step program: a kernel that only
    moves data and moves a whole cache is found in flopcheck's inventory
    even where its share of the program's traffic (6% here, 3% for each
    of the four copies on the v5e before PR 28) is under the
    ``layout-copy`` lint's 25%; a program that updates the cache in place
    is clean."""
    import jax.numpy as jnp
    cache = jax.ShapeDtypeStruct((64, 256, 128), np.float32)
    weights = jax.ShapeDtypeStruct((16, 64, 256, 128), np.float32)

    def step(c, w):
        c = jnp.transpose(c, (0, 2, 1)) if relaid else c.at[3, 5].set(1.0)
        return c, (w * 2.0).sum()

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        cache, weights).compile()
    found, facts = chip_smoke.cache_relayouts(compiled, "step", 64 * 256
                                              * 128 * 4)
    if relaid:
        assert found and all("moves 8388608 bytes" in f for f in found)
        assert not any("layout-copy" in f for f in found)   # lint silent
        assert facts["layout_bytes_max"] == 8388608
    else:
        assert found == [] and facts["layout_bytes_max"] < 8388608
    assert facts["cache_bytes"] == 8388608 and facts["kernels"] >= 2


def test_smoke_script_refuses_cpu():
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "'cpu'" in r.stderr
    assert r.stdout.strip() == ""       # no result line without a chip


def test_compile_cache_placement(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert engine.setup_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(ROOT, ".jax_cache")
        assert engine.setup_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_accelerator_context_needs_an_accelerator():
    for ctx in (mx.tpu(0), mx.gpu(0), mx.tpu(99)):
        with pytest.raises(MXNetError, match="no accelerator"):
            ctx.to_device()
    assert mx.num_devices() == 0
    assert mx.current_context() == mx.cpu(0)
    devs = jax.devices()
    assert mx.cpu(7).to_device() == devs[7]
    assert mx.cpu(9).to_device() == devs[1]      # cpu ids still wrap


def _mlp_files():
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=8,
                                name="fc1")
    net = mx.sym.SoftmaxOutput(mx.sym.Activation(net, act_type="relu"),
                               name="softmax")
    rs = np.random.RandomState(0)
    params = {"arg:fc1_weight": rs.randn(8, 6).astype(np.float32),
              "arg:fc1_bias": rs.randn(8).astype(np.float32)}
    return net, params


def test_one_element_contexts_places_on_that_device():
    """``contexts=[dev]`` is a replica on THAT device (it used to fall
    through to the default device, stacking every one-chip replica of a
    fleet on chip 0)."""
    net, params = _mlp_files()
    x = np.random.RandomState(1).rand(3, 6).astype(np.float32)
    ref = serving.ServingEngine(net, params, {"data": (6,)}, buckets=(4,))
    eng = serving.ServingEngine(net, params, {"data": (6,)}, buckets=(4,),
                                contexts=[mx.cpu(5)])
    assert ref.devices == [jax.devices()[0]]
    assert eng.devices == [jax.devices()[5]] and eng.model_devices == 1
    np.testing.assert_array_equal(eng.infer({"data": x})[0],
                                  ref.infer({"data": x})[0])
    lm = chip_smoke.lm_params(vocab=16, embed=8, heads=2, layers=1,
                              max_len=8)
    loop = serving.DecodeLoop(lm, 1, 2, 8, slots=1, contexts=[mx.cpu(6)])
    try:
        assert loop.devices == [jax.devices()[6]]
        assert loop._state["k"].devices() == {jax.devices()[6]}
        assert len(loop.generate([1, 2], 2).result(timeout=60.0)) == 2
    finally:
        loop.close()


def test_launch_local_runs_workers_on_cpu_or_refuses(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert launch.local_platform(3) == "cpu"
    assert launch.local_platform(1) == ""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert launch.local_platform(1) == "tpu"   # one worker may own the chip
    with pytest.raises(SystemExit, match="one process"):
        launch.local_platform(2)
