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

The topology is described inside a fixture (only one process may load the
TPU's library; a worker that cannot skips), and this is the one file that
does so."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import chip_smoke
from mxnet_tpu.serving import decode, deepseek_v3

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
    cond, = [i for i in outside if i["opcode"] == "conditional"]
    assert cond["op_path"].endswith("/sample/cond")
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
