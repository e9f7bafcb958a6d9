"""What :class:`~mxnet_tpu.serving.decode.DecodeLoop` asks of a model
(docs/serving.md "Architectures").

The loop owns everything a model does not: slots, admission, the feed, the
AOT step program, sampling, readback, the prefix cache, speculation. A
model is an :class:`Architecture`: how its parameters are validated, which
arrays hold a slot's state, how ONE position per slot goes through its
layers, and (where it can) how a CHUNK of one slot's prompt does. The
default is :class:`~mxnet_tpu.serving.decode.OptArch`
(``models/transformer.py``: K and V rows);
:class:`~mxnet_tpu.serving.deepseek_v3.DeepseekV3Arch` keeps latent rows
and routing counters; :class:`~mxnet_tpu.serving.lfm2.Lfm2Arch` keeps K and
V rows over its attention layers and a conv state two rows deep over the
others; :class:`~mxnet_tpu.serving.mellum.MellumArch` keeps a RING of
``sliding_window`` K and V rows over its window layers beside K and V rows
a position over its full ones. This is the serving third of the per-layer-type state protocol
(ROADMAP D1): a slot's state is whatever arrays ``slot_state`` names, each
a :class:`SlotArray` that says over how many layers it runs, how deep it is
and how wide, and the loop allocates, donates, extracts and implants them
without knowing what they hold.
"""
from __future__ import annotations

import collections

import numpy as np

from ..base import MXNetError
from .quantize import dequant_tree

#: ``SlotArray.rows`` of an array that keeps one row a POSITION: the loop
#: makes it ``max_len`` deep (one more under speculation)
PER_POSITION = None


class SlotArray(collections.namedtuple("SlotArray",
                                       "layers rows width dtype ring",
                                       defaults=(False,))):
    """One array of the slots' state, allocated ``(layers, slots, depth,
    width)``: ``layers`` of the model's layers keep it (an architecture
    with layers of several kinds numbers each kind's own), ``rows`` is
    :data:`PER_POSITION` for a cache addressed by position or a fixed
    number for a state that never grows, ``width`` is the minor dimension
    (whole 128-lane tiles, or the chip pads) and ``dtype`` what is stored.
    ``ring`` marks a fixed number of rows that IS addressed by position:
    position ``p`` lives in row ``p % rows`` and overwrites position ``p -
    rows``, so the array holds the last ``rows`` positions of a slot (a
    sliding window's K and V). The loop counts a ring's rows beside the
    per-position arrays' (``ring_rows``); an architecture with one refuses
    speculation and the prefix cache in ``validate``."""

    __slots__ = ()

    def depth(self, positions):
        """Rows allocated where a per-position array holds ``positions``:
        its rows lie on the chip's sublanes (8 of four bytes, 16 of two)
        and are allocated in whole tiles, so the chip pads nothing; the
        surplus rows are trash rows no live query attends. A fixed number
        of rows is allocated as it is: the chip stores a few rows in tiles
        that many rows deep, and padded to 16 it stores the padding too and
        the step program re-lays the whole array out on entry and on exit
        (PERF.md, PR 34; ``tests/test_deepseek_v3_tpu_compile.py``). A ring
        is never deeper than a per-position array would be: slots that
        hold fewer positions than its rows never wrap it."""
        if self.rows is not PER_POSITION and not self.ring:
            return int(self.rows)
        tile = 8 * 4 // np.dtype(self.dtype).itemsize
        whole = -(-int(positions) // tile) * tile
        return whole if self.rows is PER_POSITION \
            else min(int(self.rows), whole)


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
    #: the prefill pass takes rows of SEVERAL slots, each naming its slot
    #: and position (see :meth:`build_prefill_pass`); else one slot a pass
    packed_prefill = False

    def validate(self, host_params, max_len, mesh, quant_mode, spec_k=0,
                 prefix_cache=False):
        """Raise :class:`MXNetError` for parameters, a cache length, a
        mesh, a quantization, speculation (``spec_k`` > 0; the draft model
        is asked too) or a prefix cache this architecture cannot serve;
        return the vocabulary size. Speculation writes rows past ``pos``
        and abandons them, and the prefix cache implants a slab at a
        shorter length than it was cut at: both are sound only for state
        that keeps a row a position (a ring is addressed by position and
        still unsound for both: rows written past ``pos`` overwrite rows
        inside the window, and a slab holds the window of the length it
        was cut at)."""
        raise NotImplementedError

    def slot_state(self, host_params, quant_mode):
        """``{name: SlotArray}`` of the arrays that hold the slots' state.
        The loop allocates each as ``(layers, slots, depth, width)``; the
        prefix cache copies ``[:, slot]`` of every one out and in, and a
        speculative window runs a per-position array's rows past
        ``max_len``."""
        raise NotImplementedError

    def counters(self):
        """``{name: shape}`` of int32 arrays in the donated state that the
        token pass adds to and the loop reads rarely (never once a step)."""
        return {}

    def compiler_options(self, platform):
        """Options this architecture's step program is compiled with on
        ``platform`` (``jax.default_backend()``): ``{}`` leaves the
        compiler to itself, as every architecture but one does."""
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
        writes position ``pos`` of each slot into the per-position arrays
        (clamped to the last row: rows past ``max_len`` are trash rows no
        live query attends), writes row ``pos % rows`` of a ring, steps
        any other fixed-depth state once, and returns float32 logits
        ``(slots, vocab)``. Attention over a per-position array or a ring
        goes through :func:`.blocks.over_filled_rows` (one call a depth),
        so that a step reads the prefix of the rows its positions fill
        and not all ``max_len``. The single-token body runs it once, the speculative
        verify body unrolls it over the window."""
        raise NotImplementedError

    def build_prefill_pass(self, mesh=None):
        """``prefill_pass(state, params, tokens (C,), slot, pos0, n) ->
        state``: up to ``C`` prompt positions of ONE slot through the
        layers as one batched forward (the weights read once a chunk, not
        once a position), or ``None``: this architecture is fed one
        position a step. ``state`` holds the arrays of ``slot_state``;
        the pass writes rows ``pos0 .. pos0 + n - 1`` of slot ``slot`` in
        place and NOTHING else (rows ``n .. C - 1`` of ``tokens`` are
        padding, also where ``pos0 + C`` passes the arrays' depth), with
        causal attention inside the chunk and over the slot's rows ``<
        pos0`` (a prefix-cache hit starts past 0), at the token pass's
        precision. No head, no sampler, nothing to read back: the
        prompt's last token goes through the ordinary step.

        Where :attr:`packed_prefill` is true the pass is PACKED:
        ``prefill_pass(state, params, tokens (R,), slot (R,), pos (R,), n)
        -> state``, row ``r < n`` position ``pos[r]`` of slot ``slot[r]``
        (the rows of one slot consecutive and ascending, several slots a
        pass), rows ``n .. R - 1`` padding that moves nothing. It writes
        the named rows and nothing else, each row attending ITS slot's
        rows ``<= pos[r]`` as the arrays hold them once the pass's own
        rows are in; an architecture with counters counts the ``n`` live
        rows as the token pass would have. The loop fills such a pass from
        every slot that is due and holds it back until it pays
        (``decode.PASS_PAYS``): in a wide loop ONE read of the weights then
        carries the prompts of several slots."""
        return None

    def record_counters(self, health, counts, before):
        """Bring ``health`` (:class:`ServingHealth`) up to date from the
        counters' host copies ``counts`` (cumulative since the loop was
        built); ``before`` holds them as of the last call (``{}`` at the
        first)."""
