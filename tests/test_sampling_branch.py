"""A step pays for sampling only if a row samples (``serving/sampling.py``
rule 3; PERF.md, PR 30): ``sample_rows`` puts the sampler's value chain in
one branch of a ``lax.cond`` on ``any(temp > 0)``. For the single-token
program and the speculative verify program of ``OptArch`` and the
single-token program of ``DeepseekV3Arch``:

* the lowered program holds one ``conditional`` per sampled position, and
  no ``sort`` can be reached from the entry computation but through one;
* an all-greedy, a mixed and an all-sampled batch give, token for token,
  what the straight-line body gives that ``sample_rows`` had until PR 29
  (frozen below), built into the same program on the same parameters,
  state, seeds and knobs.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chip_smoke
from mxnet_tpu.serving import decode
from mxnet_tpu.serving import deepseek_v3 as ds

SLOTS, ROWS = 4, 24
OPT = dict(vocab=48, embed=128, heads=2, layers=2)
#: tests/test_deepseek_v3_decode.py's tiny block: 1 dense + 2 expert layers
TINY = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    intermediate_size=96, moe_intermediate_size=32, num_experts_per_tok=4,
    n_shared_experts=1, n_routed_experts=4, router_width=16, share_index=1,
    first_k_dense_replace=1, num_hidden_layers=3, vocab_size=97,
    rms_norm_eps=1e-6, rope_theta=50000, routed_scaling_factor=2.827,
    scoring_func="sigmoid", n_group=1, topk_group=1, norm_topk_prob=True,
    dtype="float32", init_std=0.2, router_std=0.2, router_bias_std=0.2,
    rope_scaling={"beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"})
#: case -> positions sampled in one dispatch
CASES = {"opt_single": 1, "opt_verify2": 2, "deepseek_single": 1}
TEMPS = {"all_greedy": [0.0, 0.0, 0.0, 0.0],
         "mixed": [0.0, 0.8, 0.0, 1.3],
         "all_sampled": [0.7, 0.8, 1.0, 1.3]}


# ---- sample_rows as it stood at PR 29 (commit b1ab6bd): do not edit -------
def _frozen_sample_rows(logits, u, temp, top_k, top_p):
    import jax
    import jax.numpy as jnp

    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    safe_t = jnp.where(temp > 0, temp, jnp.float32(1.0))
    scaled = logits / safe_t[:, None]
    order = jnp.argsort(-scaled, axis=-1)          # stable: ties by index
    probs = jax.nn.softmax(
        jnp.take_along_axis(scaled, order, axis=-1), axis=-1)

    ranks = jnp.arange(vocab, dtype=jnp.int32)[None, :]
    k_eff = jnp.where(top_k > 0, top_k, jnp.int32(vocab))[:, None]
    cum = jnp.cumsum(probs, axis=-1)
    keep = (ranks < k_eff) & ((cum - probs) < top_p[:, None])
    kept = jnp.where(keep, probs, jnp.float32(0.0))

    cdf = jnp.cumsum(kept, axis=-1)
    target = u[:, None] * cdf[:, -1:]
    hit = cdf > target
    # float-edge guard (u ~ 1.0): if no strict crossing, take the last
    # kept rank — ``keep`` is a prefix mask, so that is count-1
    rank = jnp.where(jnp.any(hit, axis=-1),
                     jnp.argmax(hit, axis=-1),
                     jnp.sum(keep.astype(jnp.int32), axis=-1) - 1)
    sampled = jnp.take_along_axis(order, rank[:, None],
                                  axis=-1)[:, 0].astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)


# ---- end of the frozen copy ------------------------------------------------


def _program(case):
    """``(build, state, params, live)`` of a case: ``build()`` makes the
    step function anew from the loop's own builders (so that it is traced
    with whatever ``decode.sample_rows`` is at that time)."""
    rs = np.random.RandomState(5)
    if case.startswith("opt"):
        arch = decode.OptArch(OPT["layers"], OPT["heads"])
        params = chip_smoke.lm_params(OPT["vocab"], OPT["embed"],
                                      OPT["heads"], OPT["layers"], ROWS,
                                      seed=1)
        cache = (OPT["layers"], SLOTS, ROWS, OPT["embed"])
        state = {"k": rs.randn(*cache).astype(np.float32),
                 "v": rs.randn(*cache).astype(np.float32)}
    else:
        arch = ds.DeepseekV3Arch(TINY)
        params = {k: (rs.randn(*shape) * 0.2).astype(np.float32)
                  for k, shape in sorted(arch.param_shapes().items())}
        state = {"latent": np.zeros((arch.num_layers, SLOTS, ROWS,
                                     arch.latent_width), np.float32)}
        state.update({k: np.zeros(shape, np.int32)
                      for k, shape in arch.counters().items()})
    state["seed"] = np.zeros(SLOTS, np.uint32)
    window = CASES[case]
    if window > 1:
        build = lambda: decode._build_verify_fn(arch, window)
    else:
        build = lambda: decode._build_decode_fn(arch)
        state["tok"] = np.zeros(SLOTS, np.int32)   # the fed-back token
    return build, state, params, [np.ones(SLOTS, np.bool_)] * arch.wants_live


def _feed(case, temps, vocab):
    rs = np.random.RandomState(9)
    window = CASES[case]
    shape = (SLOTS, window) if window > 1 else (SLOTS,)
    return [rs.randint(0, vocab, shape).astype(np.int32),       # tokens
            np.array([0, 3, 7, 11], np.int32),                  # pos
            np.array(temps, np.float32),
            np.array([0, 5, 0, 3], np.int32),                   # top_k
            np.array([1.0, 1.0, 0.9, 0.6], np.float32),         # top_p
            np.array([3, 14, 15, 92], np.uint32),               # fresh seeds
            np.ones(SLOTS, np.bool_)]                           # reseed


_HEAD = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
# the first ``opcode(`` after the result's type, which may be a tuple
_OPCODE = re.compile(r"=\s.*?\s([a-z][\w\-]*)\(")
_BRANCHES = re.compile(r"(?:branch_computations=\{([^}]*)\}"
                       r"|(?:true|false)_computation=%?([\w.\-]+))")
_CALLEE = re.compile(r"(?:to_apply|calls|body|condition)=%?([\w.\-]+)")


def _outside_conditionals(hlo_text):
    """Opcodes of every instruction the entry computation reaches WITHOUT
    entering a branch of a ``conditional``, and the number of conditionals
    met on the way."""
    comps, entry, cur = {}, None, None
    for line in hlo_text.splitlines():
        head = _HEAD.match(line)
        if head and " = " not in line:
            cur = comps.setdefault(head.group(2), [])
            entry = head.group(2) if head.group(1) else entry
        elif cur is not None and _OPCODE.search(line):
            cur.append(line)
    seen, todo, opcodes, conditionals = set(), [entry], [], 0
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            op = _OPCODE.search(line).group(1)
            opcodes.append(op)
            conditionals += op == "conditional"
            # a conditional's branches are not followed; what it (or any
            # other instruction) calls besides them is
            todo.extend(_CALLEE.findall(_BRANCHES.sub("", line)))
    return opcodes, conditionals


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_sampler_stands_inside_one_conditional_a_position(case):
    build, state, params, live = _program(case)
    feed = _feed(case, TEMPS["mixed"], 8)
    lowered = jax.jit(build(), donate_argnums=(0,)).lower(
        state, params, *feed, *live)
    assert lowered.as_text().count("stablehlo.case") == CASES[case]
    hlo = lowered.compiler_ir(dialect="hlo").as_hlo_text()
    assert " sort(" in hlo              # the sampler is there, inside:
    opcodes, conditionals = _outside_conditionals(hlo)
    assert conditionals == CASES[case]
    assert "sort" not in opcodes
    assert "reduce" in opcodes          # the greedy argmax stands outside


@pytest.mark.parametrize("batch", sorted(TEMPS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_tokens_are_the_straight_line_bodys(case, batch, monkeypatch):
    build, state, params, live = _program(case)
    vocab = TINY["vocab_size"] if case.startswith("deepseek") \
        else OPT["vocab"]
    feed = _feed(case, TEMPS[batch], vocab)
    new_state, new_toks = jax.jit(build())(state, params, *feed, *live)
    monkeypatch.setattr(decode, "sample_rows", _frozen_sample_rows)
    old_state, old_toks = jax.jit(build())(state, params, *feed, *live)
    new_toks, old_toks = np.asarray(new_toks), np.asarray(old_toks)
    assert new_toks.dtype == old_toks.dtype == np.int32
    assert np.array_equal(new_toks, old_toks)
    for k in old_state:
        assert np.array_equal(np.asarray(new_state[k]),
                              np.asarray(old_state[k])), k
    # and the batch is what its name says: a greedy row is the argmax row,
    # a sampled batch does not collapse onto it
    greedy = list(feed)
    greedy[2] = np.zeros(SLOTS, np.float32)
    _, arg = jax.jit(build())(state, params, *greedy, *live)
    rows = np.array(TEMPS[batch]) == 0
    assert np.array_equal(old_toks[rows], np.asarray(arg)[rows])
    if batch != "all_greedy":
        assert not np.array_equal(old_toks, np.asarray(arg))
