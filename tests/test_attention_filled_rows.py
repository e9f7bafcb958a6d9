"""Attention over the cache reads only the rows that are filled
(``serving/blocks.py``: ``over_filled_rows``, ``rows_ladder``,
``filled_rung``; PERF.md PR 35): every architecture's token pass picks,
from the ``pos`` it is fed, the smallest prefix of a fixed ladder of the
cache's rows that holds every slot's position, and runs its unchanged
score, mask, softmax and mix over that prefix inside one branch of a
``lax.switch``.

Held here, tiny and on the CPU: the bounded pass gives the logits and the
state of the WHOLE-CACHE pass (the same pass built with a ladder of one
rung, which has no branch: what every pass was before) at every edge of the
ladder, over a cache full of noise, with a slot at position 0 beside the
deepest one and an empty slot (``pos`` 0, ``live`` false) beside full ones;
under a 2-device model mesh for OPT; a loop whose request crosses a rung
serves the whole-cache loop's stream, for each architecture, and a
speculative loop the target-only one's."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chip_smoke
from mxnet_tpu import serving
from mxnet_tpu.serving import blocks, decode
from test_lfm2_arch import KIMI_TINY, TINY as LFM2_TINY, _load as _reference

ROWS, SLOTS = 768, 4
LADDER = (192, 384, 768)
#: the deepest position of a step: one under each rung's edge, on it (the
#: next rung's first), and the last row
EDGES = [191, 192, 383, 384, 767]
OPT = dict(vocab=48, embed=128, heads=4, layers=2)


def _whole(monkeypatch_ctx):
    """Inside it, every pass is built with one rung: the whole cache."""
    for mod in (blocks, decode):    # the passes' ladder and the loop's
        monkeypatch_ctx.setattr(mod, "rows_ladder", lambda rows: (rows,))


def _noise(rs, shape, dtype):
    return jnp.asarray(rs.randn(*shape).astype(np.float32)).astype(dtype)


def _model(name, dtype="float32"):
    """``(build, params, state)``: a builder of the architecture's token
    pass as ``f(state, params, tokens, pos, live)``, placed parameters and
    a slot state FULL OF NOISE (what retired requests leave behind: a row
    the mask fails to hide, or a branch fails to read, changes the result)."""
    rs = np.random.RandomState(11)
    if name == "opt":
        params = {k: jnp.asarray(v) for k, v in chip_smoke.lm_params(
            OPT["vocab"], OPT["embed"], OPT["heads"], OPT["layers"], ROWS,
            seed=1).items()}
        arch = decode.OptArch(OPT["layers"], OPT["heads"])

        def build(mesh=None):
            inner = arch.build_token_pass(mesh=mesh)
            return lambda state, p, tokens, pos, live: inner(state, p,
                                                             tokens, pos)
    else:
        cfg = dict(KIMI_TINY if name == "kimi" else LFM2_TINY, dtype=dtype)
        ref = _reference("kimi-k2-ep32" if name == "kimi"
                         else "lfm2-24b-a2b-ep8")
        params = ref.make_params(cfg, 7)
        arch = (serving.DeepseekV3Arch if name == "kimi"
                else serving.Lfm2Arch)(cfg)
        build = arch.build_token_pass
    quant = "none" if dtype == "float32" else "bf16"
    state = {k: _noise(rs, (a.layers, SLOTS, a.depth(ROWS), a.width), a.dtype)
             for k, a in arch.slot_state(params, quant).items()}
    state.update({k: jnp.zeros(s, np.int32)
                  for k, s in arch.counters().items()})
    return build, params, state


def _feed(deepest):
    """Slot 0 at position 0, slot 1 at the deepest, slot 2 EMPTY as the
    loop feeds it (``pos`` 0, not live), slot 3 in between."""
    return (jnp.asarray([3, 7, 0, 11], np.int32),
            jnp.asarray([0, deepest, 0, deepest // 2], np.int32),
            jnp.asarray([True, True, False, True]))


def _close(new, old, eps):
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(old)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=eps * float(np.abs(b).max()))


@pytest.fixture(scope="module", params=["opt", "kimi", "lfm2"])
def passes(request):
    """``(bounded, whole, params, state)`` of one architecture in float32,
    both passes traced once."""
    build, params, state = _model(request.param)
    bounded = jax.jit(build())
    with pytest.MonkeyPatch.context() as mp:
        _whole(mp)
        whole = jax.jit(build()).lower(state, params, *_feed(5)).compile()
    return bounded, whole, params, state


# ---------------------------------------------------------------------------
# the ladder
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows, ladder", [
    (24, (24,)), (383, (192, 383)), (384, (192, 384)), (ROWS, LADDER),
    (1024, (256, 512, 1024)),
    # one trash row under speculation, in whole tiles of two and four bytes
    (1040, (272, 528, 1040)), (776, (208, 400, 776)),
    (2048, (256, 512, 1024, 2048))])
def test_the_ladder_is_a_constant_of_the_allocated_depth(rows, ladder):
    assert blocks.rows_ladder(rows) == ladder
    assert all(r % blocks.PREFIX_TILE == 0 for r in ladder[:-1])
    assert ladder[0] >= min(rows, blocks.MIN_PREFIX_ROWS)


@pytest.mark.parametrize("rows", [24, ROWS, 776, 1024])
def test_host_and_device_pick_the_same_rung(rows):
    """``rows_covered`` (what the span and the counters say: ``filled_rung``
    under numpy) against ``filled_rung`` traced (what the program does), at
    every edge and past the last row, where a speculative window's trash
    positions stand."""
    ladder = blocks.rows_ladder(rows)
    pick = jax.jit(lambda pos: blocks.filled_rung(jnp.max(pos), ladder, jnp))
    for top in sorted({0, rows - 1, rows, rows + 2}
                      | {r + d for r in ladder for d in (-1, 0)}):
        pos = jnp.asarray([0, top, 1], np.int32)
        assert ladder[int(pick(pos))] == blocks.rows_covered(ladder, top)
    assert blocks.rows_covered(ladder, 0) == ladder[0]
    assert blocks.rows_covered(ladder, rows + 2) == rows


# ---------------------------------------------------------------------------
# the three passes at the ladder's edges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("deepest", EDGES)
def test_the_bounded_pass_gives_the_whole_cache_pass(passes, deepest):
    bounded, whole, params, state = passes
    feed = _feed(deepest)
    new_state, new_logits = bounded(state, params, *feed)
    old_state, old_logits = whole(state, params, *feed)
    eps = 64 * np.finfo(np.float32).eps
    _close(new_logits, old_logits, eps)
    _close(new_state, old_state, eps)
    assert np.isfinite(np.asarray(new_logits)).all()


def test_the_bounded_pass_has_a_branch_a_rung_and_the_whole_one_none(passes):
    bounded, whole, params, state = passes
    text = bounded.lower(state, params, *_feed(5)).as_text()
    assert "stablehlo.case" in text
    assert "stablehlo.case" not in whole.as_text() \
        and " conditional(" not in whole.as_text()


@pytest.mark.parametrize("name", ["kimi", "lfm2"])
def test_bfloat16_rows_round_the_same_weights(name):
    """The stored dtype is the operand of both cache products: the branch
    rounds the same normalised float32 weights to bfloat16, so the gap to
    the whole-cache pass stays float32 rounding carried through bfloat16
    operands (one unit in the last place of a bfloat16 here and there)."""
    build, params, state = _model(name, "bfloat16")
    bounded = jax.jit(build())
    with pytest.MonkeyPatch.context() as mp:
        _whole(mp)
        whole = jax.jit(build()).lower(state, params, *_feed(5)).compile()
    for deepest in (191, 192, 767):
        _, new = bounded(state, params, *_feed(deepest))
        _, old = whole(state, params, *_feed(deepest))
        _close(new, old, 4 * 2.0 ** -8)
    assert {str(v.dtype) for k, v in state.items()
            if k in ("k", "v", "conv", "latent")} == {"bfloat16"}


@pytest.mark.parametrize("deepest", [191, 192, 767])
def test_opt_under_a_two_device_model_mesh(deepest):
    """Lanes are sharded, rows are not: a row prefix does not cross
    shards, and the sharded bounded pass gives the single-chip whole one."""
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs the forced multi-device host")
    from mxnet_tpu.serving.engine import _model_mesh
    mesh = _model_mesh(list(devs[:2]))
    build, params, state = _model("opt")
    part = jax.sharding.PartitionSpec(
        *decode.OptArch(OPT["layers"], OPT["heads"]).slot_partition())
    sharded = {k: jax.device_put(v, jax.sharding.NamedSharding(mesh, part))
               for k, v in state.items()}
    feed = _feed(deepest)
    new_state, new_logits = jax.jit(build(mesh))(sharded, params, *feed)
    assert "stablehlo.case" in jax.jit(build(mesh)).lower(
        sharded, params, *feed).as_text()
    assert len(new_state["k"].sharding.device_set) == 2
    with pytest.MonkeyPatch.context() as mp:
        _whole(mp)
        old_state, old_logits = jax.jit(build())(state, params, *feed)
    eps = 64 * np.finfo(np.float32).eps
    _close(new_logits, old_logits, eps)
    _close(new_state, old_state, eps)


# ---------------------------------------------------------------------------
# loops whose requests cross a rung
# ---------------------------------------------------------------------------

MAX_LEN = 384          # rungs 192 and 384
LONG = [1 + i % 40 for i in range(184)]     # 184 + 16 new: crosses row 192


def _streams(make):
    loop = make()
    try:
        assert loop._ladder == blocks.rows_ladder(loop._rows)
        futs = [loop.generate(LONG, 16), loop.generate([5, 9, 11], 12),
                loop.generate([7], 20)]
        return [f.result(timeout=300) for f in futs], loop.health.report()
    finally:
        loop.close()


def _loop_of(name, **kw):
    kw.setdefault("prefix_cache", False)
    if name == "opt":
        params = chip_smoke.lm_params(OPT["vocab"], OPT["embed"],
                                      OPT["heads"], OPT["layers"], 400,
                                      seed=1)
        return lambda: serving.DecodeLoop(
            params, OPT["layers"], OPT["heads"], MAX_LEN, slots=2, **kw)
    cfg = KIMI_TINY if name == "kimi" else LFM2_TINY
    params = _reference("kimi-k2-ep32" if name == "kimi"
                        else "lfm2-24b-a2b-ep8").make_params(cfg, 7)
    arch = serving.DeepseekV3Arch if name == "kimi" else serving.Lfm2Arch
    return lambda: serving.DecodeLoop(params, max_len=MAX_LEN, slots=2,
                                      arch=arch(cfg), **kw)


@pytest.mark.parametrize("name", ["opt", "kimi", "lfm2"])
def test_a_loop_that_crosses_a_rung_serves_the_whole_cache_stream(name):
    make = _loop_of(name)
    got, health = _streams(make)
    with pytest.MonkeyPatch.context() as mp:
        _whole(mp)
        want, whole_health = _streams(make)
    assert got == want and [len(t) for t in got] == [16, 12, 20]
    # the bounded loop read both rungs, the whole-cache one every row
    assert health["cache_rows_allocated"] == whole_health["cache_rows_read"] \
        == whole_health["cache_rows_allocated"]
    steps = health["decode_steps"]
    assert 192 * steps < health["cache_rows_read"] < MAX_LEN * steps


def test_a_speculative_window_over_a_rung_is_token_identical():
    """The window's positions pick their own rungs (``pos0 + j``): a round
    that starts under the first rung's edge and ends past it, and the trash row past
    ``max_len`` in the last rung only."""
    plain, _ = _streams(_loop_of("opt", spec_k=0))
    params = chip_smoke.lm_params(OPT["vocab"], OPT["embed"], OPT["heads"],
                                  OPT["layers"], 400, seed=1)
    spec, health = _streams(_loop_of("opt", spec_k=2, draft_params=params,
                                     draft_num_layers=OPT["layers"]))
    assert spec == plain
    assert health["spec_rounds"] > 0
    assert health["cache_rows_allocated"] \
        == 3 * 392 * health["decode_steps"]       # 385 rows in whole tiles
