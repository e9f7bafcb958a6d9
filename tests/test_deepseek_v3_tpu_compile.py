"""The Kimi-K2 step program compiled for a DESCRIBED v5e at published
widths (no chip; docs/serving.md "Architectures", PERF.md PR 29): the
dense layer and one expert layer that holds 12 of 384 experts, 64 slots,
1024 rows, bfloat16. What the compiler shows and no interpreter can:

* the latent cache, 640 lanes wide, passes through the step with no
  cache-sized copy (at its bare 576 lanes the chip stores it rows minor and
  the step converts all of it on entry and on exit);
* no float32 copy of a weight matrix is made;
* the program fits the chip;
* the sampler (the sort and the gather over slots x vocabulary) stands
  inside ONE ``conditional``, so a step whose rows are all greedy does not
  run it (PERF.md, PR 30).

The LFM2 step (PERF.md PR 34) at published widths, its first six layers
(five conv and one attention; two dense and four expert layers that hold 8
of 64 experts), is compiled here too: K, V and the two-row conv state pass
through without a copy, and padded to a 16-row tile the conv state is
re-laid out on entry and on exit (why it is allocated as it is).

From PR 35 every architecture's attention covers a PREFIX of the cache's
rows, in one branch a rung of a ``conditional`` (``serving/blocks.py``):
held here for Kimi's latent rows, LFM2's K and V and, at two layers of its
published widths, OPT's, that the caches still pass through without a
cache-sized copy with the branches in, that there is ONE step executable,
that every branch but the last reads fewer rows than the array holds and
makes nothing larger than its prefix, and that LFM2's step stays under a
stated number of device-visible instructions (a traced run's cost is
quadratic in them, PERF.md PR 34).

From PR 36 the Mellum step at published widths, its first period (three
sliding-window layers over a RING of 1024 rows and one full layer over
4096 rows a position, 16 of 64 experts held, 32 slots): both K/V arrays
pass through without a copy though the ring is written at ``pos % 1024``,
and each kind's attention is a conditional over ITS OWN ladder.

From PR 37 OPT's PREFILL program (a chunk of one slot's prompt through
the layers as one batched forward) at three layers of its published
widths: no cache-sized copy, K and V donated in place, every weight read
once a chunk.

From PR 39 Kimi's PACKED prefill program (256 rows that each name their
slot and position, the dense layer and one expert layer): each row's
slot's rows reach it by a gather of ``rows x rung x 640`` a branch, the
latent cache is written in place and never copied, no float32 copy of a
weight is made, and the temporaries stay far under the chip's spare room.
LFM2's packed prefill program at the same six layers: K, V and
the conv state written in place and never copied, the rows gathered a
branch, the last layer stopped at its state, and the step's count of
device instructions under its one fetch ahead in flight.

The Laguna step at published widths, its first five layers (the dense
full layer, three window layers over a RING of 512 rows, a full expert
layer over 3584 rows a position; 72 and 48 gated heads; 32 of 256 experts
held beside a shared expert; 64 slots), compiled as the compiler leaves
it: both K/V arrays pass through without a copy, each kind attends by its
own ladder, and the step stays under its count of device instructions.

The Granite 4.0-H step at published widths, its first period (nine
Mamba-2 layers and one attention layer), 64 slots, 1024 rows: the float32
matrix state (2 MiB a slot and layer), the conv state and K and V pass
through without an array-sized copy and are written in place; the program
fits the chip at full depth; the state is stepped and read out by ONE
Pallas kernel a layer (``ops/pallas_ssm.py``), aliased into the donated
array and scoped ``layer/ssm``, and nothing else reads it (as XLA
compiles the jnp form, the read-out is a second pass over the state,
PERF.md); and the step's count of device instructions is held. From PR 45
its PACKED prefill program at full depth (256 rows): nothing
rematerialised, the matrix state read and written only by one Pallas call
a mamba layer aliased into the donated array, under 1 GB of temporaries.

The topology is described inside a fixture (only one process may load the
TPU's library; a worker that cannot skips), and this is the one file that
does so."""
import json
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chip_smoke
from mxnet_tpu.serving import (blocks, decode, deepseek_v3, granite_hybrid,
                               laguna, lfm2, mellum)

SLOTS, ROWS = 64, 1024
ITEM = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(")
_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) .*\{\s*$")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, latent_width=None):
    arch = deepseek_v3.DeepseekV3Arch(chip_smoke.KIMI_K2_DEPTH2)
    if latent_width is not None:
        arch.latent_width = latent_width

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {k: s(v, jnp.bfloat16) for k, v in arch.param_shapes().items()}
    state = {"latent": s((arch.num_layers, SLOTS, ROWS, arch.latent_width),
                         jnp.bfloat16),
             "seed": s((SLOTS,), np.uint32),
             "tok": s((SLOTS,), np.int32)}
    state.update({k: s(v, np.int32) for k, v in arch.counters().items()})
    feed = [s((SLOTS,), d) for d in (np.int32, np.int32, np.float32,
                                     np.int32, np.float32, np.uint32,
                                     np.bool_, np.bool_)]
    fn = jax.jit(decode._build_decode_fn(arch), donate_argnums=(0,))
    return arch, fn.lower(state, params, *feed).compile()


def _top_level(compiled):
    """``(name, dtype, bytes, opcode)`` of every instruction outside the
    fused computations."""
    out, fused = [], False
    for line in compiled.as_text().splitlines():
        m = _HEAD.match(line)
        if m and " = " not in line:
            fused = m.group(1).startswith("fused_")
            continue
        m = None if fused else _INSTR.match(line)
        if m:
            name, dtype, dims, op = m.groups()
            n = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
            out.append((name, dtype, n * ITEM.get(dtype, 4), op))
    return out


@pytest.fixture(scope="module")
def step(one_chip):
    return _compile(one_chip)


def test_the_latent_cache_passes_through_without_a_copy(step):
    arch, compiled = step
    cache = 2 * SLOTS * ROWS * arch.latent_width * 2      # two layers, bf16
    assert arch.latent_width == 640
    moved = [i for i in _top_level(compiled)
             if i[3] in ("copy", "transpose") and i[2] >= cache // 4]
    assert moved == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache               # donated in place
    assert mem.temp_size_in_bytes < 0.3e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9


def test_no_float32_copy_of_a_weight_matrix(step):
    _, compiled = step
    big = [i for i in _top_level(compiled)
           if i[1] == "f32" and i[2] >= 4 * 2048 * 7168]   # one expert matrix
    assert big == []


def test_the_sampler_stands_inside_the_conditional(step):
    """What the entry computation reaches without entering a branch of the
    conditional holds no sort and no gather over slots x vocabulary (the
    router's top-k sort over 64 x 384 stays, and the embedding's gather);
    the conditional's branches hold both."""
    from mxnet_tpu import flopcheck as fc
    arch, compiled = step
    comps, entry = fc._parse_computations(compiled.as_text())

    def walk(name, seen):
        """Instructions of ``name`` and of what they call, a conditional's
        branches left out."""
        if name in seen:
            return []
        seen.add(name)
        out = []
        for ins in comps[name]:
            out.append(ins)
            rest = fc._BRANCHES_RE.sub("", ins["rest"])
            for rx in (fc._CALLS_RE, fc._TO_APPLY_RE, fc._BODY_RE):
                m = rx.search(rest)
                if m:
                    out.extend(walk(m.group(1), seen))
        return out

    def wide(ins, opcode):
        return ins["opcode"] == opcode \
            and fc._type_elems(ins["type"]) >= SLOTS * arch.vocab_size

    outside = walk(entry, set())
    assert len(outside) > 300                      # it did walk the step
    # (the attention of each layer stands in a conditional of its own, one
    # branch a rung of the rows' ladder: held further down)
    cond, = [i for i in outside if i["opcode"] == "conditional"
             and (i["op_path"] or "").endswith("/sample/cond")]
    assert [i["instr"] for i in outside
            if wide(i, "sort") or wide(i, "gather")] == []
    branches = [n for m in fc._BRANCHES_RE.finditer(cond["rest"])
                for n in fc._BRANCH_NAME_RE.findall(m.group(0))]
    assert len(branches) == 2
    inside = [i for b in branches for i in walk(b, set())]
    assert any(wide(i, "sort") for i in inside)
    assert any(wide(i, "gather") for i in inside)
    # the greedy branch computes nothing: it hands the argmax through
    assert min(len(comps[b]) for b in branches) <= 2


def test_at_576_lanes_the_step_converts_the_cache(one_chip):
    """Why the cache is 640 wide: the finding, kept as a test."""
    arch, compiled = _compile(one_chip, latent_width=576)
    cache = 2 * SLOTS * ROWS * 576 * 2
    copies = [i for i in _top_level(compiled)
              if i[3] == "copy" and i[2] >= cache]
    assert len(copies) >= 2


# ---------------------------------------------------------------------------
# LFM2: two kinds of state
# ---------------------------------------------------------------------------

LFM2_DEPTH6 = {
    "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 8,
    "num_hidden_layers": 6, "vocab_size": 65536, "intermediate_size": 11776,
    "moe_intermediate_size": 1536, "num_experts": 8, "router_width": 64,
    "num_experts_per_tok": 4, "num_dense_layers": 2, "conv_L_cache": 3,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv"],
    "norm_eps": 1e-5, "routed_scaling_factor": 1,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}


def _compile_lfm2(one_chip, conv_rows=None, options="the architecture's"):
    arch = lfm2.Lfm2Arch(LFM2_DEPTH6)
    if options == "the architecture's":
        options = arch.compiler_options("tpu")

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {k: s(v, jnp.bfloat16) for k, v in arch.param_shapes().items()}
    state = {k: s((a.layers, SLOTS, a.depth(ROWS), a.width), a.dtype)
             for k, a in arch.slot_state(None, "bf16").items()}
    if conv_rows is not None:
        state["conv"] = s((5, SLOTS, conv_rows, 2048), jnp.bfloat16)
    state.update(seed=s((SLOTS,), np.uint32), tok=s((SLOTS,), np.int32))
    state.update({k: s(v, np.int32) for k, v in arch.counters().items()})
    feed = [s((SLOTS,), d) for d in (np.int32, np.int32, np.float32,
                                     np.int32, np.float32, np.uint32,
                                     np.bool_, np.bool_)]
    fn = jax.jit(decode._build_decode_fn(arch), donate_argnums=(0,))
    return state, fn.lower(state, params, *feed).compile(
        compiler_options=options or None)


def _state_copies(compiled, dims):
    """Top-level copies and transposes of an array of ``dims``."""
    want = int(np.prod(dims)) * 2
    return [i for i in _top_level(compiled)
            if i[3] in ("copy", "transpose") and i[1] == "bf16"
            and i[2] == want]


@pytest.fixture(scope="module")
def lfm2_step(one_chip):
    return _compile_lfm2(one_chip)


def test_lfm2_both_kinds_of_state_pass_through_without_a_copy(lfm2_step):
    state, compiled = lfm2_step
    assert state["k"].shape == (1, SLOTS, ROWS, 512)
    assert state["conv"].shape == (5, SLOTS, 2, 2048)
    cache = SLOTS * ROWS * 512 * 2                    # one layer's K, bf16
    moved = [i for i in _top_level(compiled)
             if i[3] in ("copy", "transpose") and i[1] == "bf16"
             and i[2] >= cache // 4]
    assert moved == []
    # no float32 copy of an expert stack or of the dense feed-forward
    big = [i for i in _top_level(compiled)
           if i[1] == "f32" and i[2] >= 4 * 1536 * 2048 * 4]
    assert [i for i in big if "copy" in i[3] or "convert" in i[3]] == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * cache       # donated in place
    assert mem.temp_size_in_bytes < 0.3e9


def test_lfm2_a_conv_state_padded_to_the_tile_is_re_laid_out(one_chip):
    """Why a fixed number of rows is allocated as it is
    (``SlotArray.depth``): the finding, kept as a test. At 2 rows the chip
    stores the state in tiles 2 rows deep and what the step moves of it is
    its 2.6 MB; at 16 rows it stores the padding too, and the step copies
    all 21 MB on entry and on exit."""
    _, padded = _compile_lfm2(one_chip, conv_rows=16)
    assert len(_state_copies(padded, (5, SLOTS, 16, 2048))) >= 2


def _fetches_ahead(compiled):
    """The asynchronous copies and slices of the entry computation: a
    weight fetched into fast memory ahead of the product that reads it."""
    return len(re.findall(r"^\s*%?(?:copy|slice)-start[\w.\-]* = ",
                          compiled.as_text(), re.M))


def test_lfm2_is_compiled_with_one_fetch_ahead_in_flight(one_chip,
                                                         lfm2_step):
    """Why ``Lfm2Arch.compiler_options`` says what it says on the chip:
    the finding, kept as a test. Left to itself the compiler fetches this
    model's matrices (50 MB expert stacks in slices, and smaller) into
    fast memory ahead of their products, as some 20 asynchronous pairs a
    layer that carry no scope: a device trace holds twice the events, and
    the time under a layer's scope leaves out part of its work. With one
    in flight a few whole matrices a layer are still fetched ahead
    (PERF.md, PR 34)."""
    _, asked = lfm2_step
    _, left_alone = _compile_lfm2(one_chip, options=None)
    assert 0 < _fetches_ahead(asked) <= 24            # six layers
    assert _fetches_ahead(left_alone) >= 3 * _fetches_ahead(asked)
    assert _fetches_ahead(left_alone) >= 60


# ---------------------------------------------------------------------------
# attention over the filled rows (PR 35)
# ---------------------------------------------------------------------------

def _opt_shapes(one_chip, layers, slots, rows):
    """``(state, params, s)`` of OPT-1.3b's published widths at ``layers``
    layers, float32, as shapes on the described chip."""
    e, f, v = 2048, 8192, 50272

    def s(shape, dtype=np.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {"tok_embed_weight": s((v, e)), "pos_embed_weight": s((2048, e)),
              "final_ln_gamma": s((e,)), "final_ln_beta": s((e,)),
              "lm_head_weight": s((v, e)), "lm_head_bias": s((v,))}
    for i in range(layers):
        for name, shape in (("ln1_gamma", (e,)), ("ln1_beta", (e,)),
                            ("attn_qkv_weight", (3 * e, e)),
                            ("attn_qkv_bias", (3 * e,)),
                            ("attn_out_weight", (e, e)),
                            ("attn_out_bias", (e,)), ("ln2_gamma", (e,)),
                            ("ln2_beta", (e,)), ("ffn_fc1_weight", (f, e)),
                            ("ffn_fc1_bias", (f,)), ("ffn_fc2_weight", (e, f)),
                            ("ffn_fc2_bias", (e,))):
            params["layer%d_%s" % (i, name)] = s(shape)
    cache = s((layers, slots, rows, e))
    state = {"k": cache, "v": cache, "seed": s((slots,), np.uint32),
             "tok": s((slots,), np.int32)}
    return state, params, s


def _compile_opt(one_chip, layers=2, slots=8, rows=768):
    state, params, s = _opt_shapes(one_chip, layers, slots, rows)
    feed = [s((slots,), d) for d in (np.int32, np.int32, np.float32,
                                     np.int32, np.float32, np.uint32,
                                     np.bool_)]
    fn = jax.jit(decode._build_decode_fn(decode.OptArch(layers, 32)),
                 donate_argnums=(0,))
    return fn.lower(state, params, *feed).compile()


def _compile_opt_prefill(one_chip, layers=3, slots=8, rows=768):
    state, params, s = _opt_shapes(one_chip, layers, slots, rows)
    feed = [s((decode.PREFILL_CHUNK,), np.int32)] + [s((), np.int32)] * 3
    fn = jax.jit(decode._build_prefill_fn(decode.OptArch(layers, 32)),
                 donate_argnums=(0,))
    return fn.lower(state, params, *feed).compile()


def _callees(comps, ins):
    from mxnet_tpu import flopcheck as fc
    m = fc._CALLS_RE.search(fc._BRANCHES_RE.sub("", ins["rest"]))
    return [m.group(1)] if m and m.group(1) in comps else []


def _reach(comps, name):
    """Every instruction a branch runs, its fusions' own included."""
    out = []
    for ins in comps[name]:
        out.append(ins)
        for callee in _callees(comps, ins):
            out.extend(_reach(comps, callee))
    return out


def _attention_branches(compiled, ladder):
    """``[[branch computation a rung]]`` of every conditional that has one
    branch a rung, and the parsed computations."""
    from mxnet_tpu import flopcheck as fc
    text = compiled.as_text()
    assert len(re.findall(r"^ENTRY ", text, re.M)) == 1   # ONE executable
    comps, _ = fc._parse_computations(text)
    found = []
    for ins in (i for c in comps.values() for i in c):
        if ins["opcode"] != "conditional":
            continue
        names = [n for m in fc._BRANCHES_RE.finditer(ins["rest"])
                 for n in fc._BRANCH_NAME_RE.findall(m.group(0))]
        if len(names) == len(ladder):
            found.append(names)
    return found, comps


def _holds_the_prefix_alone(compiled, ladder, slots, width, layers):
    """Branch k of every attention layer reads rows ``[:ladder[k]]`` of the
    cache by a static slice inside the product that takes it, and makes
    nothing as large as the next rung's rows would be."""
    from mxnet_tpu import flopcheck as fc
    found, comps = _attention_branches(compiled, ladder)
    assert len(found) == layers
    for names in found:
        for rung, name in zip(ladder, names):
            made = [i for i in _reach(comps, name)
                    if i["opcode"] not in ("parameter", "get-tuple-element",
                                           "bitcast")]     # handed in whole
            cut = {int(m.group(1)) for i in made if i["opcode"] == "slice"
                   for m in [re.match(r"\w+\[1,%d,(\d+),\d+\]" % slots,
                                      i["type"])] if m}
            # (the last rung is the array's own depth: no slice need show)
            assert cut == {rung} or (rung == ladder[-1] and not cut), \
                (name, cut)
            assert max(fc._type_elems(i["type"]) for i in made
                       if not i["type"].startswith("(")) \
                <= slots * rung * width, name


def test_kimi_attends_a_prefix_of_the_latent_rows(step):
    arch, compiled = step
    ladder = blocks.rows_ladder(ROWS)
    assert ladder == (256, 512, 1024)
    _holds_the_prefix_alone(compiled, ladder, SLOTS, arch.latent_width,
                            arch.num_layers)


def test_lfm2_attends_a_prefix_of_k_and_v(lfm2_step):
    _, compiled = lfm2_step
    _holds_the_prefix_alone(compiled, blocks.rows_ladder(ROWS), SLOTS, 512, 1)


def test_opt_attends_a_prefix_and_moves_no_cache(one_chip):
    """The claimed cells' program: 8 slots, 768 rows, float32."""
    compiled = _compile_opt(one_chip)
    ladder = blocks.rows_ladder(768)
    assert ladder == (192, 384, 768)
    _holds_the_prefix_alone(compiled, ladder, 8, 2048, 2)
    cache = 2 * 8 * 768 * 2048 * 4
    moved = [i for i in _top_level(compiled)
             if i[3] in ("copy", "transpose") and i[2] >= cache // 8]
    assert moved == []
    found, facts = chip_smoke.cache_relayouts(compiled, "opt/step", cache)
    assert found == [], facts
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * cache           # donated in place
    assert mem.temp_size_in_bytes < 0.05e9


def test_opt_prefill_moves_no_cache_and_reads_its_weights_once(one_chip):
    """The claimed cells' prefill program (PR 37) at three layers: a chunk
    of 128 positions of one slot, 8 slots, 768 rows, float32. K and V are
    written in place, the one slot's rows laid out per head are all that
    is copied (``rows x width`` float32: 6.3 MB a layer), every weight
    matrix is an operand of one instruction (of four where the compiler
    fetches it in quarters), and the head's are not operands at all."""
    compiled = _compile_opt_prefill(one_chip)
    cache = 3 * 8 * 768 * 2048 * 4
    moved = [i for i in _top_level(compiled)
             if i[3] in ("copy", "transpose") and i[2] > 768 * 2048 * 4]
    assert moved == []
    found, facts = chip_smoke.cache_relayouts(compiled, "opt/prefill", cache)
    assert found == [], facts
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * cache           # donated in place
    assert mem.temp_size_in_bytes < 0.2e9
    reads = chip_smoke.weight_reads(compiled)
    assert len(reads) == 4 * 2 + 1 + 2, reads
    assert max(reads.values()) <= chip_smoke.WEIGHT_PIECES, reads
    assert not any("lm_head" in name for name in reads)


@pytest.fixture(scope="module")
def kimi_prefill(one_chip):
    arch = deepseek_v3.DeepseekV3Arch(chip_smoke.KIMI_K2_DEPTH2)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {k: s(v, jnp.bfloat16) for k, v in arch.param_shapes().items()}
    state = {"latent": s((arch.num_layers, SLOTS, ROWS, arch.latent_width),
                         jnp.bfloat16),
             "seed": s((SLOTS,), np.uint32), "tok": s((SLOTS,), np.int32)}
    state.update({k: s(v, np.int32) for k, v in arch.counters().items()})
    rows = s((decode.PACKED_ROWS,), np.int32)
    fn = jax.jit(decode._build_prefill_fn(arch), donate_argnums=(0,))
    return arch, fn.lower(state, params, rows, rows, rows,
                          s((), np.int32)).compile()


def test_kimi_packed_prefill_gathers_rows_and_moves_no_cache(kimi_prefill):
    """The claimed cell's prefill program (PR 39) at two layers: 256 rows
    over 64 slots and 1024 latent rows, bfloat16. The cache is donated and
    written in place; what is made of it is, a branch, the rows of each
    pass row's own slot up to the rung (``256 x rung x 640`` at the most,
    by pieces of whole lanes), never a copy of the array; no float32 copy
    of an expert's matrix is made; the last layer's experts, its
    attention's later matrices and the head are no operands at all."""
    arch, compiled = kimi_prefill
    R = decode.PACKED_ROWS
    cache = 2 * SLOTS * ROWS * arch.latent_width * 2
    top = _top_level(compiled)
    moved = [i for i in top if i[3] in ("copy", "transpose")
             and i[2] >= cache // 4]
    assert moved == []
    found, facts = chip_smoke.cache_relayouts(compiled, "kimi/prefill", cache)
    assert found == [], facts
    # the gathers stand inside fusions: every one of cache rows by its dims
    gathered = [[int(d) for d in m.group(1).split(",")] for m in re.finditer(
        r" = bf16\[(\d+,\d+,\d+)\]\S* gather\(", compiled.as_text())]
    # (the compiler cuts a rung's gather into pieces of rows or of lanes)
    assert gathered and all(
        g[0] == R and g[1] <= ROWS and g[2] <= arch.latent_width
        for g in gathered), gathered
    assert sum(g[1] * g[2] for g in gathered) \
        == sum(blocks.rows_ladder(ROWS)) * arch.latent_width
    big = [i for i in top if i[1] == "f32" and i[2] >= 4 * 2048 * 7168]
    assert [i for i in big if "copy" in i[3] or "convert" in i[3]] == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= cache               # donated in place
    assert mem.temp_size_in_bytes < 0.8e9
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    for name in ("lm_head_weight", "layer1_experts_down_weight",
                 "layer1_attn_out_weight", "layer1_router_weight"):
        assert "params__%s__" % name not in entry, name
    assert "params__layer1_attn_kv_a_weight__" in entry


@pytest.fixture(scope="module")
def lfm2_prefill(one_chip):
    """LFM2's PACKED prefill program at the six layers of ``LFM2_DEPTH6``,
    compiled with the architecture's options as the loop compiles it."""
    arch = lfm2.Lfm2Arch(LFM2_DEPTH6)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {k: s(v, jnp.bfloat16) for k, v in arch.param_shapes().items()}
    state = {k: s((a.layers, SLOTS, a.depth(ROWS), a.width), a.dtype)
             for k, a in arch.slot_state(None, "bf16").items()}
    state.update(seed=s((SLOTS,), np.uint32), tok=s((SLOTS,), np.int32))
    state.update({k: s(v, np.int32) for k, v in arch.counters().items()})
    rows = s((decode.PACKED_ROWS,), np.int32)
    fn = jax.jit(decode._build_prefill_fn(arch), donate_argnums=(0,))
    return fn.lower(state, params, rows, rows, rows, s((), np.int32)).compile(
        compiler_options=arch.compiler_options("tpu"))


def test_lfm2_packed_prefill_gathers_rows_and_moves_no_state(lfm2_prefill):
    """LFM2's packed prefill program at six layers: 256 rows over 64
    slots, K and V 1024 rows deep, the two-row conv state, bfloat16. K, V
    and the conv state are donated and written in place, never copied;
    each row's slot's K and V rows reach it by a gather of ``256 x rung x
    512`` a branch; the last layer (a conv layer) stops at its state: its
    output projection, its experts and the head's norm are no operands;
    with one fetch ahead in flight it shows the step's count of device
    instructions (a traced run's cost, §7 j)."""
    from mxnet_tpu import flopcheck as fc
    compiled = lfm2_prefill
    R = decode.PACKED_ROWS
    cache = SLOTS * ROWS * 512 * 2                    # one layer's K, bf16
    top = _top_level(compiled)
    # (the held experts' float32 products, 8 x 256 rows x 2048, are copied
    # whole: 16 MB, as Kimi's are)
    assert [i for i in top if i[3] in ("copy", "transpose")
            and i[1] == "bf16" and i[2] >= cache // 4] == []
    assert _state_copies(compiled, (5, SLOTS, 2, 2048)) == []
    gathered = [[int(d) for d in m.group(1).split(",")] for m in re.finditer(
        r" = bf16\[(\d+,\d+,\d+)\]\S* gather\(", compiled.as_text())]
    assert gathered and all(g[0] == R and g[1] <= ROWS and g[2] == 512
                            for g in gathered), gathered
    # K and V, each at every rung (the compiler may cut one in pieces)
    assert sum(g[1] for g in gathered) == 2 * sum(blocks.rows_ladder(ROWS))
    big = [i for i in top if i[1] == "f32" and i[2] >= 4 * 1536 * 2048 * 4]
    assert [i for i in big if "copy" in i[3] or "convert" in i[3]] == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * cache + 5 * SLOTS * 2 * 2048 * 2
    assert mem.temp_size_in_bytes < 0.5e9
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    for name in ("final_norm_gamma", "layer5_conv_out_weight",
                 "layer5_router_weight", "layer5_experts_down_weight"):
        assert "params__%s__" % name not in entry, name
    assert "params__layer5_conv_in_weight__" in entry
    comps, top_entry = fc._parse_computations(text)
    assert 250 < _device_visible(comps, top_entry) <= 320
    assert 0 < _fetches_ahead(compiled) <= 40


#: what a traced run shows of a step: every instruction outside the fused
#: computations but these, and of a conditional its longest branch
_SILENT = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")


def _device_visible(comps, name):
    from mxnet_tpu import flopcheck as fc
    n = 0
    for ins in comps[name]:
        if ins["opcode"] in _SILENT:
            continue
        n += 1
        if ins["opcode"] == "conditional":
            n += max(_device_visible(comps, b)
                     for m in fc._BRANCHES_RE.finditer(ins["rest"])
                     for b in fc._BRANCH_NAME_RE.findall(m.group(0)))
    return n


def test_lfm2_step_stays_under_its_count_of_device_instructions(lfm2_step):
    """The harness's gap attribution is gaps x spans (PERF.md PR 34, §7 j):
    a traced run of the 40-layer step took 692 s of the driver's 1200 at
    1,737 device events. These six layers showed 287 before the branches
    and 300 with them (13 an attention layer: the conditional and what a
    branch computes again of the query's layout); 40 layers with 10
    attention layers stay under 1,900."""
    from mxnet_tpu import flopcheck as fc
    _, compiled = lfm2_step
    comps, entry = fc._parse_computations(compiled.as_text())
    assert 250 < _device_visible(comps, entry) <= 310


# ---------------------------------------------------------------------------
# a ring beside a per-position cache (PR 36)
# ---------------------------------------------------------------------------

MELLUM_ROPE = {
    "full_attention": {"rope_type": "yarn", "rope_theta": 500000,
                       "factor": 16, "beta_fast": 32, "beta_slow": 1,
                       "original_max_position_embeddings": 8192,
                       "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
#: Mellum2-12B-A2.5B's published widths, its first period of layers
MELLUM_DEPTH4 = dict(
    hidden_size=2304, num_attention_heads=32, num_key_value_heads=4,
    head_dim=128, num_hidden_layers=4, vocab_size=98304,
    moe_intermediate_size=896, num_experts=16, num_experts_per_tok=8,
    router_width=64, share_index=0, sliding_window=1024,
    rms_norm_eps=1e-6, norm_topk_prob=True, tie_word_embeddings=False,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    mlp_layer_types=["sparse"] * 4, rope_parameters=MELLUM_ROPE)
MELLUM_SLOTS, MELLUM_ROWS = 32, 4096


@pytest.fixture(scope="module")
def mellum_step(one_chip):
    arch = mellum.MellumArch(MELLUM_DEPTH4)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {k: s(v, jnp.bfloat16) for k, v in arch.param_shapes().items()}
    state = {k: s((a.layers, MELLUM_SLOTS, a.depth(MELLUM_ROWS), a.width),
                  a.dtype)
             for k, a in arch.slot_state(None, "bf16").items()}
    state.update(seed=s((MELLUM_SLOTS,), np.uint32),
                 tok=s((MELLUM_SLOTS,), np.int32))
    state.update({k: s(v, np.int32) for k, v in arch.counters().items()})
    feed = [s((MELLUM_SLOTS,), d) for d in (np.int32, np.int32, np.float32,
                                            np.int32, np.float32, np.uint32,
                                            np.bool_, np.bool_)]
    fn = jax.jit(decode._build_decode_fn(arch), donate_argnums=(0,))
    return state, fn.lower(state, params, *feed).compile(
        compiler_options=arch.compiler_options("tpu"))


def test_mellum_the_ring_and_the_rows_pass_through_without_a_copy(
        mellum_step):
    state, compiled = mellum_step
    assert state["k_win"].shape == (3, MELLUM_SLOTS, 1024, 512)
    assert state["k"].shape == (1, MELLUM_SLOTS, 4096, 512)
    ring = MELLUM_SLOTS * 1024 * 512 * 2          # one layer's ring, bf16
    moved = [i for i in _top_level(compiled)
             if i[3] in ("copy", "transpose") and i[1] == "bf16"
             and i[2] >= ring // 4]
    assert moved == []
    # no float32 copy of an expert stack
    big = [i for i in _top_level(compiled)
           if i[1] == "f32" and i[2] >= 4 * 896 * 2304 * 4]
    assert [i for i in big if "copy" in i[3] or "convert" in i[3]] == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * (3 * ring + 4 * ring)
    assert mem.temp_size_in_bytes < 0.1e9


def test_mellum_each_kind_attends_a_prefix_by_its_own_ladder(mellum_step):
    _, compiled = mellum_step
    ring, rows = blocks.rows_ladder(1024), blocks.rows_ladder(4096)
    assert (ring, rows) == ((256, 512, 1024), (256, 512, 1024, 2048, 4096))
    _holds_the_prefix_alone(compiled, ring, MELLUM_SLOTS, 512, 3)
    _holds_the_prefix_alone(compiled, rows, MELLUM_SLOTS, 512, 1)


def test_mellum_step_stays_under_its_count_of_device_instructions(
        mellum_step):
    """The harness's gap attribution is gaps x spans (PERF.md PR 34, §7 j):
    with no fetch ahead the 28-layer step showed 1,591 device events on the
    chip and its traced run is predicted at 850 s of the driver's 1200 (PR
    36); with one in flight 2,033 and 1100 s, left alone 3,501, and the
    run does not end. One period of four layers shows about 300 here."""
    from mxnet_tpu import flopcheck as fc
    _, compiled = mellum_step
    comps, entry = fc._parse_computations(compiled.as_text())
    assert 250 < _device_visible(comps, entry) <= 320


# ---------------------------------------------------------------------------
# the ring under gated heads of two counts (the Laguna block)
# ---------------------------------------------------------------------------

LAGUNA_SLOTS, LAGUNA_ROWS = 64, 3584


@pytest.fixture(scope="module")
def laguna_step(one_chip):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "laguna-s-2.1-ep8.json")) as f:
        arch = laguna.LagunaArch(dict(json.load(f), num_hidden_layers=5))

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {k: s(v, jnp.bfloat16) for k, v in arch.param_shapes().items()}
    state = {k: s((a.layers, LAGUNA_SLOTS, a.depth(LAGUNA_ROWS), a.width),
                  a.dtype)
             for k, a in arch.slot_state(None, "bf16").items()}
    state.update(seed=s((LAGUNA_SLOTS,), np.uint32),
                 tok=s((LAGUNA_SLOTS,), np.int32))
    state.update({k: s(v, np.int32) for k, v in arch.counters().items()})
    feed = [s((LAGUNA_SLOTS,), d) for d in (
        np.int32, np.int32, np.float32, np.int32, np.float32, np.uint32,
        np.bool_, np.bool_)]
    fn = jax.jit(decode._build_decode_fn(arch), donate_argnums=(0,))
    return state, fn.lower(state, params, *feed).compile(
        compiler_options=arch.compiler_options("tpu"))


def test_laguna_the_ring_and_the_rows_pass_through_without_a_copy(
        laguna_step):
    state, compiled = laguna_step
    assert state["k_win"].shape == (3, LAGUNA_SLOTS, 512, 1024)
    assert state["k"].shape == (2, LAGUNA_SLOTS, 3584, 1024)
    ring = LAGUNA_SLOTS * 512 * 1024 * 2          # one layer's ring, bf16
    rows = LAGUNA_SLOTS * 3584 * 1024 * 2
    moved = [i for i in _top_level(compiled)
             if i[3] in ("copy", "transpose") and i[1] == "bf16"
             and i[2] >= ring // 4]
    assert moved == []
    # no float32 copy of an expert stack
    big = [i for i in _top_level(compiled)
           if i[1] == "f32" and i[2] >= 4 * 1024 * 3072 * 4]
    assert [i for i in big if "copy" in i[3] or "convert" in i[3]] == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * (3 * ring + 2 * rows)
    assert mem.temp_size_in_bytes < 0.2e9


def test_laguna_each_kind_attends_a_prefix_by_its_own_ladder(laguna_step):
    _, compiled = laguna_step
    ring, rows = blocks.rows_ladder(512), blocks.rows_ladder(3584)
    assert (ring, rows) == ((256, 512), (224, 448, 896, 1792, 3584))
    _holds_the_prefix_alone(compiled, rows, LAGUNA_SLOTS, 1024, 2)
    # two rungs a window layer, and the sampler's two branches
    assert len(_attention_branches(compiled, ring)[0]) == 3 + 1


def test_laguna_step_stays_under_its_count_of_device_instructions(
        laguna_step):
    """The harness's gap attribution is gaps x spans (PERF.md §7 j): the
    12 layers the cell runs show 1,453 device instructions compiled here
    as the compiler leaves them, against Mellum's 1,591 events a step on
    the chip with no fetch ahead; these five show 638."""
    from mxnet_tpu import flopcheck as fc
    _, compiled = laguna_step
    comps, entry = fc._parse_computations(compiled.as_text())
    assert 580 < _device_visible(comps, entry) <= 700


# ---------------------------------------------------------------------------
# a float32 matrix state a head beside K/V rows (the Granite 4.0-H block)
# ---------------------------------------------------------------------------

def _granite_config(layers=None):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cfg = json.load(f)
    if layers is not None:
        cfg = dict(cfg, num_hidden_layers=layers,
                   layer_types=cfg["layer_types"][:layers])
    return cfg


def _granite_compile(one_chip, layers, prefill=False):
    """The step (or, with ``prefill``, the packed prefill program of
    ``decode.PACKED_ROWS`` rows) as the loop compiles it."""
    arch = granite_hybrid.GraniteHybridArch(_granite_config(layers))

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = {k: s(v, jnp.bfloat16) for k, v in arch.param_shapes().items()}
    state = {k: s((a.layers, SLOTS, a.depth(ROWS), a.width), a.dtype)
             for k, a in arch.slot_state(None, "bf16").items()}
    state.update(seed=s((SLOTS,), np.uint32), tok=s((SLOTS,), np.int32))
    if prefill:
        rows = s((decode.PACKED_ROWS,), np.int32)
        feed = [rows, rows, rows, s((), np.int32)]
        fn = jax.jit(decode._build_prefill_fn(arch), donate_argnums=(0,))
    else:
        feed = [s((SLOTS,), d) for d in (np.int32, np.int32, np.float32,
                                         np.int32, np.float32, np.uint32,
                                         np.bool_)]
        fn = jax.jit(decode._build_decode_fn(arch), donate_argnums=(0,))
    return state, fn.lower(state, params, *feed).compile(
        compiler_options=arch.compiler_options("tpu") or None)


@pytest.fixture(scope="module")
def granite_step(one_chip):
    return _granite_compile(one_chip, 10)


def _scope(op_path):
    """The scope path a device trace's reader gets for an instruction: its
    metadata's ``op_name`` less the jitted functions and the op itself
    (``DecodeLoop.program_scopes``)."""
    return "/".join(c for c in (op_path or "").split("/")[:-1]
                    if c and "(" not in c)


def _state_readers(compiled, layers):
    """``(opcode, scope, aliasing)`` of every top-level instruction that
    takes the float32 matrix state ``(layers, SLOTS, 128, 4096)`` as an
    operand, its views (tuple elements, bitcasts) and the result tuple seen
    through."""
    from mxnet_tpu import flopcheck as fc
    comps, entry = fc._parse_computations(compiled.as_text())
    shape = "f32[%d,%d,128,4096]" % (layers, SLOTS)
    arrays = {i["instr"] for i in comps[entry] if i["type"].startswith(shape)}
    out = []
    for i in comps[entry]:
        operands = set(re.findall(r"%([\w.\-]+)", i["rest"].split(")")[0]))
        if operands & arrays and i["opcode"] not in (
                "get-tuple-element", "bitcast", "tuple"):
            alias = re.search(r"output_to_operand_aliasing=\{\{0\}: \((\d+)",
                              i["rest"])
            out.append((i["opcode"], _scope(i["op_path"]),
                        alias and int(alias.group(1))))
    return out


def _assert_one_kernel_a_layer(compiled, layers):
    """Each mamba layer's state is stepped and read out by one Pallas call
    that writes its result into the state operand (the sixth, after the
    layer index and the four row operands), scoped ``layer/ssm``; no other
    instruction reads the state (as XLA compiled the jnp form, a fusion a
    layer read it a second time for the read-out)."""
    readers = _state_readers(compiled, layers)
    assert len(readers) == layers, readers
    for opcode, scope, alias in readers:
        assert (opcode, alias) == ("custom-call", 5), readers
        assert scope.startswith("layer/ssm"), readers
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == layers


def test_granite_every_kind_of_state_passes_through_in_place(granite_step):
    state, compiled = granite_step
    assert state["ssm"].shape == (9, SLOTS, 128, 4096)
    assert state["ssm"].dtype == np.float32
    assert state["conv"].shape == (9, SLOTS, 3, 4352)
    assert state["k"].shape == (1, SLOTS, ROWS, 512)
    layer = SLOTS * 4096 * 128 * 4                    # 128 MiB of state
    # no copy of a layer's conv state or K (bf16), none of a quarter of a
    # layer's matrix state (the sampler's f32 copies over slots x
    # vocabulary, 25.7 MB, stand in its conditional's branch)
    assert [i for i in _top_level(compiled) if i[3] in ("copy", "transpose")
            and ((i[1] == "bf16" and i[2] >= SLOTS * 3 * 4352 * 2)
                 or (i[1] == "f32" and i[2] >= layer // 4))] == []
    # the state written in place: a kernel a layer steps its 128 MiB in
    # the donated array, never a copy of the array's 1.2 GB
    writes = [i for i in _top_level(compiled)
              if i[1] == "f32" and i[2] == 9 * layer]
    assert writes and all(i[3] in ("parameter", "get-tuple-element",
                                   "bitcast")
                          for i in writes)
    _assert_one_kernel_a_layer(compiled, 9)
    mem = compiled.memory_analysis()
    donated = sum(int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
                  for k, v in state.items() if k in ("ssm", "conv", "k", "v"))
    assert mem.alias_size_in_bytes >= donated
    assert mem.temp_size_in_bytes < 0.1e9


def test_granite_fits_the_chip_at_full_depth(granite_step):
    """The period compiled here holds 3.26 GB of arguments; the 40 layers
    hold 11.81 GB (weights 6.38, the matrix state 4.83, K and V 0.54, the
    conv state 0.06), which with the period's temporaries fits the chip's
    15.75 GB (a compile of all 40 layers reads 11.81 GB of arguments and
    0.07 GB of temporaries for a described v5e, PERF.md)."""
    _, compiled = granite_step
    arch = granite_hybrid.GraniteHybridArch(_granite_config())
    weights = 2 * sum(int(np.prod(v)) for v in arch.param_shapes().values())
    state = sum(a.layers * SLOTS * a.depth(ROWS) * a.width
                * np.dtype(a.dtype).itemsize
                for a in arch.slot_state(None, "bf16").values())
    assert abs(weights + state - 11.81e9) < 0.01e9
    mem = compiled.memory_analysis()
    assert abs(mem.argument_size_in_bytes - 3.261e9) < 0.01e9
    assert weights + state + 4 * mem.temp_size_in_bytes < 15.5e9


def test_granite_step_stays_under_its_count_of_device_instructions(
        granite_step):
    """A traced run's cost is gaps x spans (PERF.md §7 j). As the compiler
    leaves the step, one period of ten layers shows 785 device-visible
    instructions, most of them the compiler's fetches of weight slices into
    fast memory ahead of their products, and the 40 layers 2,357; the
    state's step and read-out are one kernel a layer. (As XLA compiled the
    jnp form they were two fusions a layer that each read the layer's
    state: 963 and 2,855.)"""
    from mxnet_tpu import flopcheck as fc
    _, compiled = granite_step
    comps, entry = fc._parse_computations(compiled.as_text())
    assert 740 < _device_visible(comps, entry) <= 820
    _assert_one_kernel_a_layer(compiled, 9)


def test_granite_full_step_rematerialises_nothing_and_updates_in_place(
        one_chip):
    """At 40 layers and 64 slots, left to itself, the compiler
    rematerialises the first layer's in-place update of the matrix state:
    a second copy of it reads the donated array after the first has
    overwritten it, so that layer's state advances twice a step (every
    served token wrong on the chip, PERF.md). Under the block's compiler
    options nothing is rematerialised: each of the 36 layers' steps is one
    kernel into the donated array, nothing else reads the state, and the
    step fits."""
    state, compiled = _granite_compile(one_chip, None)
    assert state["ssm"].shape == (36, SLOTS, 128, 4096)
    assert ".remat" not in compiled.as_text()
    _assert_one_kernel_a_layer(compiled, 36)
    mem = compiled.memory_analysis()
    assert abs(mem.argument_size_in_bytes - 11.81e9) < 0.01e9
    assert mem.alias_size_in_bytes >= 5.42e9
    assert mem.temp_size_in_bytes < 0.1e9


def test_granite_full_prefill_pass_carries_the_state_in_place(one_chip):
    """The claimed cell's PACKED prefill program (PR 45) at full depth: 256
    rows over 64 slots, bfloat16 weights, the float32 matrix state of 36
    layers. Compiled with the block's options, as the loop compiles it,
    nothing is rematerialised; each mamba layer's state is read and
    written by ONE Pallas call (``ops/pallas_ssd.py::ssm_segments``) that
    writes into the donated array (its eleventh operand, after the layer,
    the segment table and the rows' operands), scoped ``layer/ssm``, and
    nothing else reads the 4.83 GB; every state array is donated in place;
    the temporaries (most of them each row's gathered K/V rows at the
    deepest rung) stay under 1 GB; the last layer stops at its states."""
    state, compiled = _granite_compile(one_chip, None, prefill=True)
    assert state["ssm"].shape == (36, SLOTS, 128, 4096)
    text = compiled.as_text()
    assert ".remat" not in text
    readers = _state_readers(compiled, 36)
    assert len(readers) == 36, readers
    for opcode, scope, alias in readers:
        assert (opcode, alias) == ("custom-call", 10), readers
        assert scope.startswith("layer/ssm"), readers
    assert text.count('custom_call_target="tpu_custom_call"') == 36
    mem = compiled.memory_analysis()
    donated = sum(int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
                  for k, v in state.items() if k in ("ssm", "conv", "k", "v"))
    assert abs(donated - 5.43e9) < 0.01e9
    assert mem.alias_size_in_bytes >= donated
    assert mem.temp_size_in_bytes < 1e9
    entry = text[text.index("ENTRY"):]
    for name in ("final_norm_gamma", "layer39_ssm_out_weight",
                 "layer39_ffn_down_weight"):
        assert "params__%s__" % name not in entry, name
    assert "params__layer39_ssm_in_weight__" in entry
