"""Serving-tier health counters (docs/serving.md).

The serving analog of :class:`mxnet_tpu.io.DataHealth` /
:class:`mxnet_tpu.guard.TrainingHealth`: every padded example, expired
deadline, back-pressure drop and shed in-flight request is counted here —
per batcher/loop AND mirrored into the process-global
``serving.SERVING_HEALTH`` aggregate — so an operator can tell "healthy"
from "limping on deadline misses" without scraping logs.
"""
from __future__ import annotations

import threading


class ServingHealth(object):
    """Thread-safe counters for inference-tier degradation."""

    def __init__(self, parent=None):
        self._lock = threading.Lock()
        self._parent = parent
        self.requests = 0          # accepted infer()/generate() submissions
        self.batches = 0           # engine dispatches issued by the batcher
        self.examples = 0          # real (unpadded) examples dispatched
        self.padded = 0            # pad rows added to reach a shape bucket
        self.expired = 0           # requests failed on a passed deadline
        self.dropped = 0           # rejected at enqueue (back-pressure/fault)
        self.shed = 0              # in-flight requests failed by a dying loop
        self.errors = 0            # dispatch errors propagated to callers
        self.decode_steps = 0      # continuous-batching decode iterations
        self.tokens_emitted = 0    # tokens decode steps handed to requests
        self.prompt_positions = 0  # cache positions committed that emitted
        #                            none (a prompt being fed, by a step or
        #                            by a prefill pass)
        self.prefill_passes = 0    # prefill passes dispatched: chunks of
        #                            prompts written into their slots' rows
        #                            between two decode steps
        self.prefill_slots = 0     # slots those passes carried, summed:
        #                            over prefill_passes, how many slots'
        #                            prompts met behind one read of the
        #                            weights (1 for a one-slot pass)
        self.prefill_positions = 0  # prompt positions those passes
        #                            committed: over prompt_positions, the
        #                            share of prompts fed through a chunk
        self.sampled_steps = 0     # decode steps in which some row sampled
        #                            (temperature > 0): the steps that paid
        #                            for the in-graph sampler
        self.steps_ahead = 0       # decode steps dispatched while the step
        #                            before was still unread (run-ahead)
        self.cache_rows_read = 0   # rows of a cache addressed by position
        #                            (a slot's, a layer's) that the steps'
        #                            attention covered: the prefix each
        #                            picked from the positions it was fed
        self.cache_rows_allocated = 0   # the same had each read every row:
        #                            read / allocated is the share of the
        #                            cache a loop's attention touches
        self.ring_rows_read = 0    # rows of a ring (a sliding window's K
        #                            and V: a slot's, a layer's) the steps'
        #                            attention covered; these three are
        #                            reported by a loop with a ring only
        self.ring_rows_allocated = 0    # the same had each read the whole
        #                            ring
        self.ring_wrapped_slot_steps = 0   # slot-steps dispatched at a
        #                            position at or past the ring's depth:
        #                            where a window layer reads fewer rows
        #                            than a full one would
        self.trash_slot_steps = 0  # slot-steps dispatched for a request
        #                            whose eos was learned a step late:
        #                            their tokens were dropped
        self.first_tokens = 0      # decode requests that got a first token:
        #                            the denominator of the two means below
        self.first_token_us_sum = 0   # submission -> first token on the
        #                            host, microseconds, summed over them
        self.queue_wait_us_sum = 0    # submission -> seated in a slot, of
        #                            the same requests
        self.token_gaps = 0        # gaps between consecutive tokens of one
        #                            request (its tokens less one)
        self.token_gap_us_sum = 0  # their lengths summed: over token_gaps,
        #                            the mean time a client waits for the
        #                            next token
        self.token_gap_us_max = 0  # the longest of them so far (a mark, not
        #                            a sum: a step behind a co-rider's
        #                            prefill pass, a stall)
        self.joined = 0            # sequences that entered a decode slot
        self.retired = 0           # sequences that left a decode slot
        self.requeued = 0          # requests moved off a dead/draining
        #                            replica back into the fleet queue
        #                            (NOT failed — the no-silent-shed path)
        self.prefix_hits = 0       # joins that implanted a cached prefix
        self.prefix_prefills = 0   # prefixes prefilled + stored for reuse
        self.spec_rounds = 0       # draft-K-then-verify rounds dispatched
        self.spec_drafted = 0      # draft proposals the target ruled on
        self.spec_accepted = 0     # draft tokens the target verified
        self.moe_pairs_routed = 0  # (token, choice) pairs expert layers
        #                            routed, over all the router's experts
        self.moe_pairs_here = 0    # of them, pairs that chose a HELD expert
        self.moe_busiest_expert = 0   # the busiest held expert's pairs
        self.last_error = None
        #: callables run at the top of report(): a decode loop whose
        #: counters live on the device brings them up to date here
        self._sources = []

    def add_source(self, fn):
        with self._lock:
            self._sources.append(fn)

    def remove_source(self, fn):
        with self._lock:
            if fn in self._sources:
                self._sources.remove(fn)

    def _bump(self, field, n=1, err=None):
        with self._lock:
            setattr(self, field, getattr(self, field) + n)
            if err is not None:
                self.last_error = str(err)
        if self._parent is not None:
            self._parent._bump(field, n, err)

    def record_request(self):
        self._bump("requests")

    def record_batch(self, examples, padded):
        with self._lock:
            self.batches += 1
            self.examples += int(examples)
            self.padded += int(padded)
        if self._parent is not None:
            self._parent.record_batch(examples, padded)

    def record_expired(self, err=None):
        self._bump("expired", err=err)

    def record_dropped(self, err=None):
        self._bump("dropped", err=err)

    def record_shed(self, n, err=None):
        self._bump("shed", n=n, err=err)

    def record_error(self, err=None):
        self._bump("errors", err=err)

    def record_decode_step(self, emitted=0, prompt=0, sampled=0, ahead=0,
                           rows=0, allocated=0):
        """One decode step (or speculative round) DISPATCHED: it handed
        ``emitted`` tokens to its requests (a run-ahead step hands its
        tokens over a step later, through :meth:`record_tokens`),
        processed ``prompt`` positions that emitted none and fed
        ``sampled`` rows with a temperature above 0 (any at all and the
        step ran the sampler); ``ahead`` is 1 where the step before was
        still unread; its attention covered ``rows`` of the ``allocated``
        rows a slot's cache holds in a layer (each summed over the passes
        of a speculative window). The counts move under one lock."""
        with self._lock:
            self.decode_steps += 1
            self.tokens_emitted += int(emitted)
            self.prompt_positions += int(prompt)
            self.sampled_steps += int(sampled > 0)
            self.steps_ahead += int(ahead)
            self.cache_rows_read += int(rows)
            self.cache_rows_allocated += int(allocated)
        if self._parent is not None:
            self._parent.record_decode_step(emitted, prompt, sampled, ahead,
                                            rows, allocated)

    def record_prefill(self, positions, slots=1):
        """One prefill pass DISPATCHED, which committed ``positions``
        prompt positions of ``slots`` slots (the step dispatched behind it
        counts them among its ``prompt`` positions too)."""
        with self._lock:
            self.prefill_passes += 1
            self.prefill_positions += int(positions)
            self.prefill_slots += int(slots)
        if self._parent is not None:
            self._parent.record_prefill(positions, slots)

    def record_ring_step(self, rows, allocated, wrapped):
        """One decode step DISPATCHED by a loop whose architecture keeps a
        ring: its window layers' attention covered ``rows`` of the
        ``allocated`` rows of a slot's ring in a layer, and ``wrapped`` of
        its live slots stood at a position past the ring's depth."""
        with self._lock:
            self.ring_rows_read += int(rows)
            self.ring_rows_allocated += int(allocated)
            self.ring_wrapped_slot_steps += int(wrapped)
        if self._parent is not None:
            self._parent.record_ring_step(rows, allocated, wrapped)

    def record_tokens(self, emitted, trash=0):
        """A run-ahead step read back: ``emitted`` tokens handed to their
        requests, ``trash`` slot-steps whose token was dropped."""
        with self._lock:
            self.tokens_emitted += int(emitted)
            self.trash_slot_steps += int(trash)
        if self._parent is not None:
            self._parent.record_tokens(emitted, trash)

    def record_request_latency(self, seat_us, token_us):
        """A decode request that leaves with at least one token, from the
        loop's own stamps (the ``decode_request`` record's ``seat_us`` and
        ``token_us``, microseconds from its submission): its time to first
        token, its queue wait, and the gaps between its tokens. Once a
        request, where the record is emitted; the counts move under one
        lock."""
        longest = max([b - a for a, b in zip(token_us, token_us[1:])],
                      default=0)
        health = self
        while health is not None:     # the gaps are worked out once
            with health._lock:
                health.first_tokens += 1
                health.first_token_us_sum += int(token_us[0])
                health.queue_wait_us_sum += int(seat_us)
                health.token_gaps += len(token_us) - 1
                health.token_gap_us_sum += int(token_us[-1] - token_us[0])
                health.token_gap_us_max = max(health.token_gap_us_max,
                                              longest)
            health = health._parent

    def record_join(self):
        self._bump("joined")

    def record_retire(self):
        self._bump("retired")

    def record_requeued(self, n=1):
        self._bump("requeued", n=n)

    def record_prefix_hit(self):
        self._bump("prefix_hits")

    def record_prefix_prefill(self):
        self._bump("prefix_prefills")

    def record_spec_round(self, drafted, accepted):
        with self._lock:
            self.spec_rounds += 1
            self.spec_drafted += int(drafted)
            self.spec_accepted += int(accepted)
        if self._parent is not None:
            self._parent.record_spec_round(drafted, accepted)

    def record_moe(self, routed, here, busiest):
        """Expert-layer routing since the last report: ``routed`` and
        ``here`` are increments, ``busiest`` the count of the busiest held
        expert so far (the largest is kept)."""
        with self._lock:
            self.moe_pairs_routed += int(routed)
            self.moe_pairs_here += int(here)
            self.moe_busiest_expert = max(self.moe_busiest_expert,
                                          int(busiest))
        if self._parent is not None:
            self._parent.record_moe(routed, here, busiest)

    def report(self):
        with self._lock:
            sources = list(self._sources)
        for fn in sources:
            fn()
        with self._lock:
            ring = {} if not self.ring_rows_allocated else {
                "ring_rows_read": self.ring_rows_read,
                "ring_rows_allocated": self.ring_rows_allocated,
                "ring_wrapped_slot_steps": self.ring_wrapped_slot_steps}
            return {
                "requests": self.requests, "batches": self.batches,
                "examples": self.examples, "padded": self.padded,
                "expired": self.expired, "dropped": self.dropped,
                "shed": self.shed, "errors": self.errors,
                "decode_steps": self.decode_steps,
                "tokens_emitted": self.tokens_emitted,
                "prompt_positions": self.prompt_positions,
                "prefill_passes": self.prefill_passes,
                "prefill_positions": self.prefill_positions,
                "prefill_slots": self.prefill_slots,
                "sampled_steps": self.sampled_steps,
                "steps_ahead": self.steps_ahead,
                "cache_rows_read": self.cache_rows_read,
                "cache_rows_allocated": self.cache_rows_allocated,
                "trash_slot_steps": self.trash_slot_steps, **ring,
                "first_tokens": self.first_tokens,
                "first_token_us_sum": self.first_token_us_sum,
                "queue_wait_us_sum": self.queue_wait_us_sum,
                "token_gaps": self.token_gaps,
                "token_gap_us_sum": self.token_gap_us_sum,
                "token_gap_us_max": self.token_gap_us_max,
                "joined": self.joined,
                "retired": self.retired, "requeued": self.requeued,
                "prefix_hits": self.prefix_hits,
                "prefix_prefills": self.prefix_prefills,
                "spec_rounds": self.spec_rounds,
                "spec_drafted": self.spec_drafted,
                "spec_accepted": self.spec_accepted,
                "moe_pairs_routed": self.moe_pairs_routed,
                "moe_pairs_here": self.moe_pairs_here,
                "moe_busiest_expert": self.moe_busiest_expert,
                "last_error": self.last_error,
            }

    def reset(self):
        with self._lock:
            self.requests = self.batches = self.examples = 0
            self.padded = self.expired = self.dropped = 0
            self.shed = self.errors = self.decode_steps = 0
            self.tokens_emitted = self.prompt_positions = 0
            self.prefill_passes = self.prefill_positions = 0
            self.prefill_slots = 0
            self.sampled_steps = self.steps_ahead = 0
            self.cache_rows_read = self.cache_rows_allocated = 0
            self.trash_slot_steps = 0
            self.ring_rows_read = self.ring_rows_allocated = 0
            self.ring_wrapped_slot_steps = 0
            self.first_tokens = self.first_token_us_sum = 0
            self.queue_wait_us_sum = 0
            self.token_gaps = self.token_gap_us_sum = 0
            self.token_gap_us_max = 0
            self.joined = self.retired = self.requeued = 0
            self.prefix_hits = self.prefix_prefills = 0
            self.spec_rounds = self.spec_drafted = self.spec_accepted = 0
            self.moe_pairs_routed = self.moe_pairs_here = 0
            self.moe_busiest_expert = 0
            self.last_error = None

    def __repr__(self):
        return "ServingHealth(%r)" % (self.report(),)


#: process-global aggregate every per-batcher/per-loop health mirrors into
SERVING_HEALTH = ServingHealth()
