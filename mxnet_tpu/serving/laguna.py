"""The Laguna block (``model_type: laguna``) for
:class:`~mxnet_tpu.serving.decode.DecodeLoop`: Mellum's window and full
attention over a ring and a per-position cache (:mod:`.mellum`, whose token
pass it runs), with what that block does not have (docs/serving.md
"Architectures"):

* **query heads by layer kind** (``num_attention_heads_per_layer``: 72 on
  the window layers and 48 on the full ones of Laguna-S-2.1) over K/V heads
  shared by every layer, so that ``attn_q_weight``, ``attn_out_weight`` and
  the gate are shaped by the layer;
* **a per-head output gate** (``gating: per-head``; Qiu et al., "Gated
  Attention for Large Language Models", 2025): after attention head ``h``'s
  output is multiplied by ``sigmoid(a . w_g[h])``, ``a`` the layer's normed
  input and ``attn_gate_weight`` ``w_g`` (heads, hidden), no bias;
* **partial rotary positions** (``partial_rotary_factor`` of a kind's
  ``rope_parameters`` entry): the leading ``head_dim * factor`` lanes of q
  and k rotated in halves, YaRN's blend over those lanes, the rest passed
  through (:func:`.blocks.rope_half`);
* **dense leading layers** (``mlp_layer_types`` ``dense``, or
  ``mlp_only_layers``): a SwiGLU of ``intermediate_size``
  (``ffn_{gate,up,down}_weight``) under ``layer/mlp``;
* **a shared expert beside the softmax router**
  (``shared_expert_intermediate_size``: ``shared_{gate,up,down}_weight``,
  ungated, under ``layer/moe/shared``), and the routed sum times
  ``moe_routed_scaling_factor`` (the shared expert is not scaled).

Layer ``i`` of kind ``t``: ``a = RMSNorm(x)``, ``h = x + W_o
Gate(Attn_t(a), a)``, ``y = h + SwiGLU(RMSNorm(h))`` on a dense layer and
``y = h + Shared(f) + s * Routed(f)``, ``f = RMSNorm(h)``, on the others.
The parameter names, the state, the scopes, the counters and what a ring
refuses are Mellum's (:mod:`.mellum`). On the chip the step is compiled
as the compiler leaves it (:meth:`.arch.Architecture.compiler_options`).
"""
from __future__ import annotations

from ..base import MXNetError
from .arch import Architecture
from .mellum import MellumArch

_KEYS = ("intermediate_size", "num_attention_heads_per_layer",
         "shared_expert_intermediate_size", "moe_routed_scaling_factor",
         "gating")


class LagunaArch(MellumArch):
    """The Laguna block from its ``config.json`` keys and Mellum's two of
    this repo's (``router_width``, ``share_index``): the keys that Mellum's
    config lacks are required, and dense layers are served."""

    name = "laguna"
    mlp_types = ("dense", "sparse")
    compiler_options = Architecture.compiler_options

    def __init__(self, config):
        missing = [k for k in _KEYS if k not in config]
        if missing:
            raise MXNetError("LagunaArch: config lacks %s"
                             % ", ".join(missing))
        super(LagunaArch, self).__init__(config)
