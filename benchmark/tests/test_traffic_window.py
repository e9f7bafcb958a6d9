"""Traffic generation and window arithmetic: pure host code."""
import json
import os

from benchmark.harness import traffic, window

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_open_multiset_is_identical_under_two_seeds():
    mix = _mix("chat_steady")
    _, a = traffic.open_schedule(mix, 30, 1)
    _, b = traffic.open_schedule(mix, 30, 3000000019)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 30)
    assert sorted((p, k) for _, p, k in a) == sorted((p, k) for _, p, k in b)
    assert [(p, k) for _, p, k in a] != [(p, k) for _, p, k in b]
    assert [t for t, _, _ in a] != [t for t, _, _ in b]
    assert all(0 <= t < 30 for t, _, _ in a)


def test_closed_sequence_does_not_depend_on_the_seed():
    mix = _mix("batch_job")
    assert traffic.closed_sequence(mix, 1) == traffic.closed_sequence(mix, 2)
    pairs = traffic.closed_sequence(mix, 1)
    assert len(pairs) == mix["multiset"]
    assert min(p for p, _ in pairs) >= 16 and max(p for p, _ in pairs) <= 48
    assert min(k for _, k in pairs) >= 48 and max(k for _, k in pairs) <= 144


def test_lengths_fit_the_serving_cache():
    with open(os.path.join(BENCH, "configs", "opt-1.3b.json")) as f:
        cfg = json.load(f)
    for name in ("chat_steady", "batch_job"):
        pairs = traffic.length_pairs(_mix(name), 64)
        longest = max(p + k for p, k in pairs)
        assert longest <= cfg["serve"]["max_len"]
        assert longest <= cfg["check"]["pad_to"][name]


def test_prompt_ids_follow_the_seed():
    a = traffic.prompt_ids(3000000001, 5, 16, 50272)
    assert a == traffic.prompt_ids(3000000001, 5, 16, 50272)
    assert a != traffic.prompt_ids(3000000002, 5, 16, 50272)
    assert all(0 <= t < 50272 for t in a)
    assert traffic.prompt_ids(1, -3, 4, 100)       # warm-up indices


def test_event_aligned_rate_ignores_where_the_clock_edge_falls():
    done = [(1.0, 10), (2.5, 20), (4.0, 10), (7.0, 30)]
    # the edge anywhere between the completions at 4.0 and 7.0
    rates = {window.event_aligned_rate(done, 0.5, s)[0]
             for s in (3.6, 4.0, 5.0, 6.4)}
    assert rates == {40 / 3.5}
    rate, tokens, t_close, n = window.event_aligned_rate(done, 1.0, 6.0)
    assert (tokens, t_close, n) == (60, 7.0, 3)     # the opener is not counted
    assert rate == 60 / 6.0
    assert window.event_aligned_rate(done, 8.0, 5.0)[0] is None


def test_failed_requests_are_the_worst():
    vals = [window.ms_per_token(0.0, 1.0, 10), window.ms_per_token(0.0, None, 0),
            window.ms_per_token(0.0, 2.0, 10)]
    assert vals[0] == 100.0 and vals[1] == float("inf")
    assert window.percentile(vals, 50) == 200.0
    assert window.percentile(vals, 90) == float("inf")
    assert window.percentile([], 50) is None
