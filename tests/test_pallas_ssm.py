"""The Mamba-2 state kernels (``ops/pallas_ssm.py``, and from PR 45 the
packed prefill pass's, ``ops/pallas_ssd.py``) in Pallas interpret
mode on the CPU, against the jnp form the Granite 4.0-H token pass runs
everywhere but the chip (``serving/granite_hybrid.py::_state_step``), in the
state's own layout ``(layers, slots, d_state, heads * d_head)`` at small
widths: three layers, three slots, four heads of 8 lanes, ``d_state`` 16.

* the stepped layer and the read-out agree with the jnp form to float32
  rounding, whichever layer and block (one that does not divide its
  dimension takes the whole of it);
* every other layer of the array is left bit for bit as it was;
* a slot at ``pos == 0`` (its decay 0) starts from a zero state, whatever
  it held;
* the packed prefill pass's kernel (``pallas_ssd.ssm_segments``: a grid
  step a segment, the carried slots' state read and written in place, each
  segment's rows' read-out of the state it started from) agrees with its
  jnp form (``_segments_step``) to float32 rounding, for a segment that
  starts a request (its decay 0), segments of one row, as many segments
  as slots, and a pass that carries none; every slot and layer it does
  not carry is left bit for bit;
* the kernels are the passes' on the chip only: importing
  ``mxnet_tpu.serving`` imports no Pallas, and the CPU's lowering of the
  step and of the pass holds no kernel call.

The kernels' lowering for the chip, at published widths inside the step and
the pass, is held by ``tests/test_deepseek_v3_tpu_compile.py``."""
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_ssd, pallas_ssm
from mxnet_tpu.serving.granite_hybrid import _segments_step, _state_step

LAYERS, SLOTS, ROWS, HEADS, HEAD = 3, 3, 16, 4, 8
WIDTH = HEADS * HEAD


def _operands(seed=0, fresh=()):
    """A state of large values, a lane's decay (0 for the slots in
    ``fresh``: their requests' first position), ``u``, ``b`` and ``c``."""
    rng = np.random.RandomState(seed)
    state = rng.randn(LAYERS, SLOTS, ROWS, WIDTH).astype(np.float32) * 50
    decay = np.repeat(rng.uniform(0.2, 1.0, (SLOTS, HEADS)), HEAD, axis=1)
    decay[list(fresh)] = 0.0
    u, b, c = (rng.randn(SLOTS, w).astype(np.float32)
               for w in (WIDTH, ROWS, ROWS))
    return state, decay.astype(np.float32), u, b, c


def _kernel(state, n, decay, u, b, c, **blocks):
    return pallas_ssm.ssm_step(jnp.asarray(state), jnp.int32(n), decay, u,
                               b, c, interpret=True, **blocks)


# a block that does not divide its dimension (2 slots of 3, 12 lanes of
# 32) takes the whole dimension
@pytest.mark.parametrize("slots", [1, 2, 3])
@pytest.mark.parametrize("lanes", [8, 12, 16, 32])
@pytest.mark.parametrize("layer", [0, 1, 2])
def test_the_kernel_steps_one_layer_in_place_as_the_jnp_form(layer, lanes,
                                                             slots):
    state, decay, u, b, c = _operands(layer)
    want_state, want_y = _state_step(layer, jnp.asarray(state), decay, u, b,
                                     c)
    got_state, got_y = _kernel(state, layer, decay, u, b, c, slots=slots,
                               lanes=lanes)
    assert got_state.shape == state.shape and got_state.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got_state[layer]),
                               np.asarray(want_state[layer]),
                               rtol=2e-6, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=2e-5, atol=1e-2)
    # the layers the call was not given are the array's, bit for bit
    for other in set(range(LAYERS)) - {layer}:
        np.testing.assert_array_equal(np.asarray(got_state[other]),
                                      state[other])


@pytest.mark.parametrize("layer", [0, 2])
def test_a_slot_at_its_first_position_starts_from_a_zero_state(layer):
    state, decay, u, b, c = _operands(7, fresh=(1,))
    got_state, got_y = _kernel(state, layer, decay, u, b, c)
    # the slot's old state (values of hundreds) is gone: b (x) u alone
    fresh = b[1][:, None] * u[1][None, :]
    np.testing.assert_array_equal(np.asarray(got_state[layer, 1]), fresh)
    np.testing.assert_allclose(np.asarray(got_y[1]),
                               (fresh * c[1][:, None]).sum(0),
                               rtol=2e-5, atol=1e-4)
    # the other slots carried theirs
    assert not np.allclose(np.asarray(got_state[layer, 0]),
                           b[0][:, None] * u[0][None, :])


def test_serving_imports_no_pallas():
    code = ("import sys, mxnet_tpu.serving; "
            "print(sorted(m for m in sys.modules if 'pallas' in m))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True, timeout=300)
    assert out.stdout.strip() == "[]", out.stdout


def test_the_cpu_lowering_of_the_step_runs_the_jnp_form():
    """Of the step and of the packed prefill pass."""
    from mxnet_tpu.serving import decode
    from mxnet_tpu.serving.granite_hybrid import GraniteHybridArch
    arch = GraniteHybridArch(dict(
        hidden_size=16, num_attention_heads=2, num_key_value_heads=1,
        num_hidden_layers=2, vocab_size=11, shared_intermediate_size=24,
        mamba_n_heads=HEADS, mamba_d_head=HEAD, mamba_d_state=ROWS,
        mamba_d_conv=4, mamba_n_groups=1, mamba_expand=2,
        embedding_multiplier=12, residual_multiplier=0.22,
        attention_multiplier=0.25, logits_scaling=8, rms_norm_eps=1e-5,
        layer_types=["mamba", "attention"]))
    nslots, rows = 2, 16
    params = {k: jax.ShapeDtypeStruct(v, jnp.float32)
              for k, v in arch.param_shapes().items()}
    state = {k: jax.ShapeDtypeStruct((a.layers, nslots, a.depth(rows),
                                      a.width), a.dtype)
             for k, a in arch.slot_state(None, "none").items()}
    state.update(seed=jax.ShapeDtypeStruct((nslots,), np.uint32),
                 tok=jax.ShapeDtypeStruct((nslots,), np.int32))
    feed = [jax.ShapeDtypeStruct((nslots,), d)
            for d in (np.int32, np.int32, np.float32, np.int32, np.float32,
                      np.uint32, np.bool_)]
    text = jax.jit(decode._build_decode_fn(arch)).lower(
        state, params, *feed).as_text()
    assert "tpu_custom_call" not in text
    rows = jax.ShapeDtypeStruct((8,), np.int32)
    text = jax.jit(decode._build_prefill_fn(arch)).lower(
        state, params, rows, rows, rows,
        jax.ShapeDtypeStruct((), np.int32)).as_text()
    assert "tpu_custom_call" not in text


#: rows of a packed pass in the segment kernel's tests, and its segments:
#: name -> [(slot, first row, row past the last)]; a slot whose decay is
#: 0 starts a request
PASS_ROWS = 24
PASSES = {
    "three_one_starting_a_request": [(2, 0, 7), (0, 7, 10), (1, 10, 19)],
    "one_row_segments": [(1, 0, 1), (0, 1, 2)],
    "every_slot": [(1, 0, 3), (2, 3, 4), (0, 4, 24)],
    "none": []}


def _pass(seed, segments):
    rng = np.random.RandomState(seed)
    state = rng.randn(LAYERS, SLOTS, ROWS, WIDTH).astype(np.float32) * 50
    nj = SLOTS
    slot, lo, hi = (np.zeros(nj, np.int32) for _ in range(3))
    for j, (s, a, b) in enumerate(segments):
        slot[j], lo[j], hi[j] = s, a, b
    dec = rng.uniform(0.2, 1.0, (nj, WIDTH)).astype(np.float32)
    dec[0] *= seed % 2             # the first segment may start a request
    c, b = (rng.randn(PASS_ROWS, ROWS).astype(np.float32) for _ in range(2))
    e, w = (rng.randn(PASS_ROWS, WIDTH).astype(np.float32) for _ in range(2))
    return state, (slot, lo, hi, np.int32(len(segments)), dec, c, e, b, w)


@pytest.mark.parametrize("lanes", [8, 12, 32])
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("case", sorted(PASSES))
def test_the_segment_kernel_carries_each_slot_as_the_jnp_form(case, layer,
                                                             lanes):
    state, args = _pass(layer + len(case), PASSES[case])
    want_state, want_y = _segments_step(layer, jnp.asarray(state), *args)
    got_state, got_y = pallas_ssd.ssm_segments(
        jnp.asarray(state), jnp.int32(layer), *args, lanes=lanes,
        interpret=True)
    assert got_state.shape == state.shape and got_y.shape == want_y.shape
    np.testing.assert_allclose(np.asarray(got_state), np.asarray(want_state),
                               rtol=2e-6, atol=1e-3)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=2e-5, atol=1e-2)
    carried = {s for s, _, _ in PASSES[case]}
    for other in range(LAYERS):
        for s in range(SLOTS):
            if other != layer or s not in carried:
                np.testing.assert_array_equal(np.asarray(got_state[other, s]),
                                              state[other, s])
    if not carried:
        assert not np.asarray(got_y).any()
