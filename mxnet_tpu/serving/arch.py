"""What :class:`~mxnet_tpu.serving.decode.DecodeLoop` asks of a model
(docs/serving.md "Architectures").

The loop owns everything a model does not: slots, admission, the feed, the
AOT step program, sampling, readback, the prefix cache, speculation. A
model is an :class:`Architecture`: how its parameters are validated, which
arrays hold a slot's state, and how ONE position per slot goes through its
layers. The default is :class:`~mxnet_tpu.serving.decode.OptArch`
(``models/transformer.py``: K and V rows);
:class:`~mxnet_tpu.serving.deepseek_v3.DeepseekV3Arch` keeps latent rows
and routing counters. This is the serving third of the per-layer-type state
protocol (ROADMAP D1): a slot's state is whatever arrays ``slot_state``
names, each ``(layers, slots, rows, width)``, and the loop allocates,
donates, extracts and implants them without knowing what they hold.
"""
from __future__ import annotations

from ..base import MXNetError
from .quantize import dequant_tree


class Architecture(object):
    """A model description. Subclasses set ``name`` and ``num_layers`` and
    supply the methods below; nothing here is a tuning knob."""

    name = "?"
    num_layers = 0
    num_heads = 0
    #: the step program takes an eighth per-slot array, ``live`` (bool):
    #: which slots carry a request. Only an architecture that COUNTS what
    #: it processes needs it
    wants_live = False

    def validate(self, host_params, max_len, mesh, quant_mode):
        """Raise :class:`MXNetError` for parameters, a cache length, a mesh
        or a quantization this architecture cannot serve; return the
        vocabulary size."""
        raise NotImplementedError

    def slot_state(self, host_params, quant_mode):
        """``{name: (width, dtype)}`` of the arrays that hold the slots'
        state. The loop allocates each as ``(num_layers, slots, rows,
        width)``: the prefix cache copies ``[:, slot]`` of every one out
        and in, and a speculative window runs its rows past ``max_len``."""
        raise NotImplementedError

    def counters(self):
        """``{name: shape}`` of int32 arrays in the donated state that the
        token pass adds to and the loop reads rarely (never once a step)."""
        return {}

    def slot_partition(self):
        """The partition of a slot-state array over a model mesh (four
        entries), for an architecture that serves over one."""
        raise MXNetError("%s: no model mesh over this architecture yet"
                         % self.name)

    def load(self, params):
        """In the step program: the stored parameter tree as the token pass
        takes it. The default up-casts every leaf to float32."""
        return dequant_tree(params)

    def build_token_pass(self, mesh=None):
        """``token_pass(state, params, tokens, pos[, live]) -> (state,
        logits)``: ONE position per slot through every layer. ``state``
        holds the arrays of ``slot_state`` and ``counters``; the pass
        writes position ``pos`` of each slot (clamped to the last row: rows
        past ``max_len`` are trash rows no live query attends) and returns
        float32 logits ``(slots, vocab)``. The single-token body runs it
        once, the speculative verify body unrolls it over the window."""
        raise NotImplementedError

    def record_counters(self, health, counts, before):
        """Bring ``health`` (:class:`ServingHealth`) up to date from the
        counters' host copies ``counts`` (cumulative since the loop was
        built); ``before`` holds them as of the last call (``{}`` at the
        first)."""
