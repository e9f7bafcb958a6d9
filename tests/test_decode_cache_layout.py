"""The decode KV cache's stored layout (docs/serving.md "Decode loop";
PERF.md, PR 28): ``(layers, slots, rows, heads * head_dim)`` float32, the
model's width minor, rows a multiple of 8, a model mesh sharding the minor
dimension by groups of whole heads. Over head sizes 64 and 128, a plain
cache (rows a multiple of 128) and a speculative one (``max_len + 1`` rows,
rounded up), on one and on two model shards (forced host devices):

* ``token_pass`` over the cache gives the full forward's log-probabilities
  (``models/transformer.py`` through ``Module.predict``) at every position;
* a prefix slab extracted from one slot and implanted into ANOTHER
  reproduces the cold stream;
* the cache's shape and sharding are what ``docs/serving.md`` says.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import models, serving
from mxnet_tpu.serving import decode

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS, HEADS, VOCAB, SLOTS = 2, 2, 48, 2
#: (max_len, spec_k): rows 128 as allocated; 41 rows wanted, 48 allocated
CACHES = {"plain": (128, 0), "spec": (40, 2)}


@pytest.fixture(scope="module", params=[
    (d, cache, shards) for d in (64, 128) for cache in sorted(CACHES)
    for shards in (1, 2)], ids=lambda p: "d%d-%s-x%d" % p)
def built(request):
    head_dim, cache, shards = request.param
    max_len, spec_k = CACHES[cache]
    embed = HEADS * head_dim
    params = chip_smoke.lm_params(VOCAB, embed, HEADS, LAYERS, max_len,
                                  seed=head_dim + shards)
    # a smaller scale than the smoke's 0.3: at width 256 that saturates
    # the softmax and every position's top-1 margin with it
    params = {k: v * np.float32(0.25) for k, v in params.items()}
    kw = {"contexts": shards} if shards > 1 else {}
    if spec_k:      # the target drafts for itself: every proposal accepted
        kw.update(spec_k=spec_k, draft_params=params)
    loop = serving.DecodeLoop(params, LAYERS, HEADS, max_len, slots=SLOTS,
                              **kw)
    yield {"loop": loop, "params": params, "head_dim": head_dim,
           "embed": embed, "max_len": max_len, "spec_k": spec_k,
           "shards": shards}
    loop.close()


def _reference_logp(params, embed, max_len, tokens):
    """log-softmax of the plain symbol's full forward: (n, seq, vocab)."""
    n, seq = tokens.shape
    sym = models.transformer(vocab_size=VOCAB, embed=embed, num_heads=HEADS,
                             num_layers=LAYERS, seq_len=seq,
                             max_seq_len=max_len)
    ref = mx.mod.Module(sym, context=mx.cpu(0))
    ref.bind(data_shapes=[("data", (n, seq))],
             label_shapes=[("softmax_label", (n, seq))], for_training=False)
    ref.set_params({k: mx.nd.array(v) for k, v in params.items()}, {})
    probs = ref.predict(mx.io.NDArrayIter(
        tokens.astype(np.float32), np.zeros((n, seq), np.float32),
        batch_size=n)).asnumpy().reshape(n, seq, VOCAB)
    return np.log(np.maximum(probs.astype(np.float64), 1e-300))


def test_token_pass_matches_full_forward_at_every_position(built):
    loop, seq = built["loop"], 24
    rs = np.random.RandomState(7)
    tokens = rs.randint(0, VOCAB, (SLOTS, seq))
    want = _reference_logp(built["params"], built["embed"],
                           built["max_len"], tokens)
    step = jax.jit(decode._build_token_pass(LAYERS, HEADS, mesh=loop._mesh))
    # copies: the loop's own (donated) state is left alone
    ck, cv = jnp.copy(loop._state["k"]), jnp.copy(loop._state["v"])
    put = loop._dev
    for p in range(seq):
        tok, pos = put([tokens[:, p].astype(np.int32),
                        np.full(SLOTS, p, np.int32)])
        ck, cv, logits = step(ck, cv, loop._params, tok, pos)
        got = np.asarray(jax.nn.log_softmax(logits, axis=-1), np.float64)
        np.testing.assert_allclose(got, want[:, p], atol=2e-4, rtol=0,
                                   err_msg="position %d" % p)
        assert (got.argmax(-1) == want[:, p].argmax(-1)).all()
    assert ck.shape == loop._state["k"].shape
    assert ck.sharding.is_equivalent_to(loop._state["k"].sharding, ck.ndim)


def test_prefix_slab_moves_between_slots(built):
    loop = built["loop"]
    shared = [3, 9, 4, 1, 7, 7, 2, 5, 11, 6]
    ask = shared + [8, 2]
    before = loop.health.report()
    cold = loop.generate(ask, 6, prefix_len=len(shared)).result(timeout=120)
    # slot 0 is taken by a longer stranger, so the hit lands in slot 1:
    # the slab harvested from slot 0 is implanted into the other slot
    other = loop.generate([5, 4, 3, 2, 1], 12)
    warm = loop.generate(ask, 6, prefix_len=len(shared))
    assert warm.result(timeout=120) == cold
    assert len(other.result(timeout=120)) == 12
    after = loop.health.report()
    assert after["prefix_prefills"] - before["prefix_prefills"] == 1
    assert after["prefix_hits"] - before["prefix_hits"] == 1
    (entry,) = [e for k, e in loop._prefix.items() if list(k) == shared]
    slab_shape = (LAYERS, loop._rows, built["embed"])
    assert entry["target"]["k"].shape == slab_shape
    assert (entry["draft"] is not None) == bool(built["spec_k"])


def test_cache_shape_and_sharding_are_as_documented(built):
    loop, embed, shards = built["loop"], built["embed"], built["shards"]
    rows = {"plain": 128, "spec": 48}[
        "spec" if built["spec_k"] else "plain"]
    assert loop._rows == rows and rows % 8 == 0
    assert rows >= built["max_len"] + (1 if built["spec_k"] else 0)
    states = [loop._state] + ([loop._draft_state] if built["spec_k"] else [])
    for state in states:
        for name in ("k", "v"):
            cache = state[name]
            assert cache.shape == (LAYERS, SLOTS, rows, embed)
            assert cache.dtype == np.float32
            assert {tuple(s.data.shape) for s in cache.addressable_shards} \
                == {(LAYERS, SLOTS, rows, embed // shards)}
            assert len(cache.sharding.device_set) == shards
    if shards > 1:
        from mxnet_tpu.parallel.mesh import AXIS_MODEL
        assert tuple(loop._state["k"].sharding.spec) \
            == (None, None, None, AXIS_MODEL)
    with open(os.path.join(ROOT, "docs", "serving.md")) as f:
        doc = " ".join(f.read().split())
    assert "`(layers, slots, rows, heads * head_dim)`" in doc
    assert "PartitionSpec(None, None, None, 'model')" in doc
