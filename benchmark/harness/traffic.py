"""The one general traffic generator. A mix is a data file:

    {"loop": "open" | "closed" | "fit",
     "rate_per_s": 1.0,            # open loop: requests due per second
     "clients": 16,                # closed loop: requests always outstanding
     "lead_s": 8.0,                # open loop: unmeasured lead-in at the same rate
     "lead_completions": 8,        # closed loop: completions before the window opens
     "multiset": 32,               # closed loop: pairs in one cycle of the mix
     "order": "seeded" | "fixed",  # whether --seed permutes the pairs
     "prompt_len": [[0, 32], [0.5, 96], [1, 256]],   # quantile knots
     "new_tokens": [[0, 16], [0.5, 48], [1, 128]]}

Every run of a cell offers the SAME multiset of (prompt length, new tokens)
pairs: each length distribution is read at evenly spaced quantiles and the
two lists are paired by a fixed stride, never by the seed. ``--seed``
decides the order of the pairs (``order: seeded``), when each is due, and
the token ids. So a median or a tail is over the same requests under every
seed, and runs differ by arrival order only.
"""
import math

import numpy as np


def quantile(knots, q):
    """Piecewise-linear inverse CDF through ``[[q, value], ...]``."""
    qs = [float(k[0]) for k in knots]
    vs = [float(k[1]) for k in knots]
    return float(np.interp(q, qs, vs))


def _coprime_stride(n):
    """A stride near n * 0.618 that is coprime to n: a fixed permutation
    that spreads one list's quantiles over the other's."""
    s = max(1, int(round(n * 0.6180339887)))
    while math.gcd(s, n) != 1:
        s += 1
    return s


def length_pairs(mix, n):
    """The multiset: ``n`` (prompt_len, new_tokens) pairs, a function of
    the mix and ``n`` alone: each distribution at evenly spaced quantiles,
    the two lists paired by a fixed coprime stride."""
    qs = [(i + 0.5) / n for i in range(n)]
    prompts = [max(1, int(round(quantile(mix["prompt_len"], q)))) for q in qs]
    news = [max(1, int(round(quantile(mix["new_tokens"], q)))) for q in qs]
    stride = _coprime_stride(n)
    return [(prompts[i], news[(i * stride) % n]) for i in range(n)]


def ordered_pairs(mix, n, seed):
    pairs = length_pairs(mix, n)
    if mix.get("order", "seeded") == "seeded":
        perm = np.random.default_rng([int(seed), 1]).permutation(n)
    else:
        # a fixed interleave, so that neighbours in time differ in length
        stride = _coprime_stride(n)
        perm = [(i * stride) % n for i in range(n)]
    return [pairs[int(i)] for i in perm]


def open_schedule(mix, seconds, seed):
    """Open loop: ``(lead, measured)`` lists of ``(due_s, prompt_len,
    new_tokens)``, due times relative to the window's opening (lead-in
    requests are due before 0). The measured window holds exactly
    ``round(rate * seconds)`` arrivals, placed as a Poisson process
    conditioned on its count: sorted uniform times. The count, and so the
    multiset, is the same under every seed."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([int(seed), 2])
    due = np.sort(rng.uniform(0.0, float(seconds), n))
    measured = [(float(t), p, k)
                for t, (p, k) in zip(due, ordered_pairs(mix, n, seed))]
    lead_s = float(mix.get("lead_s", 0.0))
    n_lead = int(round(rate * lead_s))
    lead = []
    if n_lead:
        due_l = np.sort(rng.uniform(-lead_s, 0.0, n_lead))
        pairs_l = ordered_pairs(mix, n_lead, int(seed) + 1)
        lead = [(float(t), p, k) for t, (p, k) in zip(due_l, pairs_l)]
    return lead, measured


def closed_sequence(mix, seed):
    """Closed loop: one cycle of the mix, repeated by the driver for as long
    as the clock runs."""
    return ordered_pairs(mix, int(mix["multiset"]), seed)


def prompt_ids(seed, index, length, vocab):
    """Token ids of request ``index``: a function of the seed."""
    rng = np.random.default_rng([int(seed), 3, int(index) % 2 ** 32])
    return rng.integers(0, int(vocab), int(length)).tolist()
