"""Window arithmetic on the host's clock: the event-aligned rate of a
closed loop, latency per token of an open loop, percentiles."""
import math


def percentile(values, p):
    """Nearest-rank percentile of all values, failures included as +inf."""
    if not values:
        return None
    vs = sorted(values)
    k = max(0, min(len(vs) - 1, int(math.ceil(p / 100.0 * len(vs))) - 1))
    return vs[k]


def event_aligned_rate(completions, t_open, seconds):
    """Tokens per second over a window that opens and closes ON completion
    events. ``completions`` is ``[(t_done, tokens), ...]`` in time order and
    ``t_open`` the time of the completion that opened the window (itself
    not counted). The window closes at the last completion at or before
    ``t_open + seconds``; every token of every request completed in
    ``(t_open, t_close]`` counts, over all of ``t_close - t_open``. The
    count and the time end on the same event, so moving the clock's edge
    between two completions changes neither.
    Returns ``(rate, tokens, t_close, n_requests)``; rate None when no
    request completed."""
    inside = [(t, n) for t, n in completions
              if t_open < t <= t_open + seconds]
    if not inside:
        return None, 0, t_open, 0
    t_close = max(t for t, _ in inside)
    tokens = sum(n for _, n in inside)
    return tokens / (t_close - t_open), tokens, t_close, len(inside)


def ms_per_token(due, done, tokens):
    """(completion - time due) / tokens generated, in ms; +inf for a
    request that failed, was shed, or produced nothing."""
    if done is None or not tokens:
        return float("inf")
    return (done - due) * 1e3 / tokens
