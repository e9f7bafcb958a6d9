"""Whole runs on the CPU at tiny sizes: the entries are driven in-process
past the look for a chip, on a throw-away root whose tiny configurations,
mixes and cells are ADDED files (``helpers.make_root``), and each fault a
cell can have is planted under the timed path to see ``correct`` come out
false. The last line names the CPU and carries counts, never a rate, share
or time under a device metric's name."""
import io
import json
import os
import subprocess
import sys

import pytest

from benchmark import run as bench_run

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(tmp_path_factory):
    """One persistent compile cache for the module: the runs below repeat
    the same tiny programs."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jaxcache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    yield
    jax.config.update("jax_compilation_cache_dir", None)


def drive(root, workload, seed=3000000001, seconds=1.0, trace=0):
    out, err = io.StringIO(), io.StringIO()
    line = bench_run.run_cell(workload, seed, seconds, trace, root=root,
                              require_chip=False, compile_cache=False,
                              out=out, err=err)
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    assert last == json.loads(json.dumps(line))
    return last, err.getvalue()


def well_formed_cpu_line(line):
    assert list(line)[:3] == ["correct", "attempted", "failed"]
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}            # no device metric off the chip
    assert line["counts"] and line["attempted"] > 0
    for c in line["compared"].values():
        assert set(c) == {"value", "limit", "ok"}


def test_closed_loop_run_is_correct_and_names_the_cpu(tiny_root):
    line, err = drive(tiny_root, "tiny-lm.closed")
    well_formed_cpu_line(line)
    assert line["correct"] and line["failed"] == 0
    assert line["counts"]["tokens_generated"] > 0
    assert any(l.startswith("compared gap_sq_mean value=")
               for l in err.splitlines())
    assert err.strip().splitlines()[-1].startswith("compared ")


def test_open_loop_traced_run_reports_the_window(tiny_root):
    line, _ = drive(tiny_root, "tiny-lm.open", seed=7, trace=1)
    well_formed_cpu_line(line)
    assert line["correct"]
    assert line["device"]["window_s"] > 0 and "busy_s" in line["device"]
    assert line["attempted"] == line["counts"]["requests_due"] == 8


def test_an_altered_token_is_not_correct(tiny_root, monkeypatch):
    from mxnet_tpu.serving import decode

    orig = decode.DecodeLoop._retire

    def retire(self, i):
        slot = self._slots[i]
        if len(slot.emitted) > 2:
            slot.emitted[1] = (slot.emitted[1] + 1) % self.vocab_size
        return orig(self, i)

    monkeypatch.setattr(decode.DecodeLoop, "_retire", retire)
    line, _ = drive(tiny_root, "tiny-lm.closed")
    assert line["correct"] is False
    assert line["compared"]["gap_max"]["ok"] is False


def test_a_dropped_request_is_not_correct(tiny_root, monkeypatch):
    from mxnet_tpu.serving import decode

    orig = decode.DecodeLoop._retire
    seen = []

    def retire(self, i):
        seen.append(i)
        if len(seen) == 12:                  # one answer comes back short
            self._slots[i].emitted.pop()
        return orig(self, i)

    monkeypatch.setattr(decode.DecodeLoop, "_retire", retire)
    line, _ = drive(tiny_root, "tiny-lm.closed")
    short = line["compared"]["bad_requests"]["value"]
    assert line["correct"] is (short == 0)   # in the sample or not


def test_fit_run_is_correct_and_each_fault_is_not(tiny_root, monkeypatch):
    line, err = drive(tiny_root, "tiny-resnet.train")
    well_formed_cpu_line(line)
    assert line["correct"], err
    assert line["counts"]["dispatches"] >= 1

    from mxnet_tpu.module import module as mod_module
    orig = mod_module.Module._dispatch_fused_steps

    # a step that returns its state unchanged
    from mxnet_tpu import train_step
    run_steps = train_step.TrainStep.run_steps

    def unchanged(self, state, superbatch, *args, **kwargs):
        import jax
        keep = jax.tree_util.tree_map(lambda x: x.copy(), state)
        _, sums = run_steps(self, state, superbatch, *args, **kwargs)
        return keep, sums

    monkeypatch.setattr(train_step.TrainStep, "run_steps", unchanged)
    line, _ = drive(tiny_root, "tiny-resnet.train")
    assert line["correct"] is False
    assert not line["compared"]["dparam_gap_median"]["ok"]
    monkeypatch.setattr(train_step.TrainStep, "run_steps", run_steps)

    # the same fault from the second dispatch on, the first to take donated
    # state: the first dispatch's numbers pass, the second's do not
    calls = []

    def unchanged_later(self, state, superbatch, *args, **kwargs):
        calls.append(1)
        if len(calls) == 1:
            return run_steps(self, state, superbatch, *args, **kwargs)
        return unchanged(self, state, superbatch, *args, **kwargs)

    monkeypatch.setattr(train_step.TrainStep, "run_steps", unchanged_later)
    line, _ = drive(tiny_root, "tiny-resnet.train")
    assert line["correct"] is False
    assert line["compared"]["dparam_gap_median"]["ok"]
    assert not line["compared"]["dparam_gap_median_2"]["ok"]
    monkeypatch.setattr(train_step.TrainStep, "run_steps", run_steps)

    # half of the batch left out, the mean taken over the rest
    def half(self, super_batch, guard=None):
        import jax.numpy as jnp
        for arrs in (super_batch.data, super_batch.label):
            for a in arrs:
                x = a.data
                h = x.shape[1] // 2
                a._set_data(jnp.concatenate([x[:, :h], x[:, :h]], axis=1))
        return orig(self, super_batch, guard)

    monkeypatch.setattr(mod_module.Module, "_dispatch_fused_steps", half)
    line, _ = drive(tiny_root, "tiny-resnet.train")
    assert line["correct"] is False


DP4 = r"""
import io, json, sys
sys.path[:0] = [%(repo)r, %(tests)r]
import helpers
from benchmark import run as bench_run
root = helpers.make_root(%(tmp)r)
fault = sys.argv[1] == "fault"
if fault:
    # the exchange between chips left out, as the program would show it:
    # every chip trains on chip 0's rows and nothing of the others arrives
    from mxnet_tpu.module import module as mod_module
    import jax.numpy as jnp
    orig = mod_module.Module._dispatch_fused_steps
    def alone(self, super_batch, guard=None):
        for arrs in (super_batch.data, super_batch.label):
            for a in arrs:
                x = a.data
                q = x.shape[1] // 4
                a._set_data(jnp.concatenate([x[:, :q]] * 4, axis=1))
        return orig(self, super_batch, guard)
    mod_module.Module._dispatch_fused_steps = alone
out = io.StringIO()
line = bench_run.run_cell("tiny-resnet.train_dp4", 11, 1.0, 0, root=root,
                          require_chip=False, compile_cache=False, out=out,
                          err=io.StringIO())
print(json.dumps({"correct": line["correct"], "device": line["device"],
                  "compared": line["compared"]}))
"""


@pytest.mark.parametrize("mode", ["sound", "fault"])
def test_four_device_run_and_the_exchange_left_out(tmp_path, mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = DP4 % {"repo": REPO, "tests": os.path.dirname(
        os.path.abspath(__file__)), "tmp": str(tmp_path)}
    p = subprocess.run([sys.executable, "-c", code, mode], env=env,
                       capture_output=True, text=True, timeout=1500)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is (mode == "sound"), res
