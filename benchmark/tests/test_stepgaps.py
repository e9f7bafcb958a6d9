"""The readers of what ``DecodeLoop`` says about its own step
(``harness/stepgaps.py``), on hand-made spans and device intervals: the
arithmetic of gap-and-span intersection, and nothing to read from a
program that lacks the spans. No value here is a device measurement."""
import os

import pytest

from benchmark.harness import cells, stepgaps, tracered

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
US = 1000


def reader(name):
    return cells.load_module(os.path.join(BENCH, "metrics", name + ".py"))


def trace_of(busy, programs=()):
    """A one-device trace whose operations run in ``busy`` and whose step
    program ran in ``programs`` (us pairs)."""
    ops = [("fusion.%d" % i, s * US, e * US) for i, (s, e) in enumerate(busy)]
    mods = [("jit_decode_fn(%d)" % i, s * US, e * US)
            for i, (s, e) in enumerate(programs)]
    return tracered.Trace({"/device:TPU:0": {"ops": ops, "modules": mods}})


def span(name, s, e, **args):
    return (name, s * US, e * US, args)


def step(start, end, phases, **args):
    """A ``decode_step`` span whose laps are ``phases``: ``(name, end_us)``
    pairs, each lap running from the end of the one before (the first
    from the span's start), as the tracer's ``span.lap`` writes them."""
    laps, at = [], start
    for name, until in phases:
        laps.append([name, at - start, until - at])
        at = until
    return span("decode_step", start, end, laps=laps, **args)


def ctx_of(busy, spans, window, programs=()):
    return {"trace": trace_of(busy, programs), "spans": spans,
            "cfg": {"program": "jit_decode_fn"},
            "window_ns": (window[0] * US, window[1] * US)}


@pytest.mark.parametrize("start,end,want", [
    (15, 35, 10),      # the tail of one gap and the head of the next
    (20, 30, 0),       # between two gaps
    (0, 10, 0),        # ends where the first gap begins
    (0, 100, 20),      # over every gap
    (12, 18, 6),       # inside one gap
    (40, 50, 0),       # begins where the last gap ends
    (39, 50, 1),
])
def test_gap_and_span_intersection(start, end, want):
    gaps = [(10, 20), (30, 40)]
    assert stepgaps.intersect_ns(gaps, start, end) == want
    assert stepgaps.intersect_ns([], start, end) == 0


def one_step():
    """The device is idle from 100 to 160 us; the loop reads step 1's
    tokens back, commits, seats a request, and feeds and dispatches step 2
    in that time."""
    busy = [(0, 100), (160, 300)]
    spans = [
        step(10, 132, [("decode_gather", 12), ("decode_h2d", 15),
                       ("decode_dispatch", 20), ("decode_readback", 110),
                       ("decode_commit", 130)],
             step=1, reqs=[7, 8], pos=[3, 0], n=[1, 1], emit=[1, 0],
             cpu_us=900),
        span("decode_admit", 133, 135, step=2, joined=1),
        step(136, 290, [("decode_gather", 140), ("decode_h2d", 150),
                        ("decode_dispatch", 170), ("decode_readback", 280),
                        ("decode_commit", 285)],
             step=2, reqs=[7, 8, 9], pos=[4, 1, 0], n=[1, 1, 1],
             emit=[1, 0, 0], cpu_us=1100),
    ]
    return busy, spans


def test_a_gap_across_the_leaves_is_split_among_them():
    busy, spans = one_step()
    ctx = ctx_of(busy, spans, (0, 300))
    under, idle, steps = stepgaps.idle_by_leaf(ctx)
    assert idle == 60 * US and steps == 2
    assert under == {"decode_admit": 2 * US, "decode_gather": 4 * US,
                     "decode_h2d": 10 * US, "decode_dispatch": 10 * US,
                     "decode_readback": 10 * US, "decode_commit": 20 * US}
    # per step, in ms
    assert reader("decode_gap_feed_ms").read(ctx) == pytest.approx(0.007)
    assert reader("decode_gap_dispatch_ms").read(ctx) == pytest.approx(0.005)
    assert reader("decode_gap_readback_ms").read(ctx) == pytest.approx(0.005)
    assert reader("decode_gap_commit_ms").read(ctx) == pytest.approx(0.011)
    # 4 of the 60 idle microseconds lie between two spans
    assert reader("decode_gap_covered").read(ctx) == pytest.approx(
        100.0 * 56 / 60)
    # attribute_gaps gives all 60 to one name
    assert tracered.Trace.attribute_gaps(
        ctx["trace"], [s[:3] for s in spans], 0, 300 * US) \
        == [("decode_step", pytest.approx(60e-6))]


def test_the_laps_of_a_step_are_its_leaves():
    _, spans = one_step()
    got = stepgaps.leaves(spans)
    assert [(n, s // US, e // US, st) for n, s, e, st in got[:6]] == [
        ("decode_gather", 10, 12, 1), ("decode_h2d", 12, 15, 1),
        ("decode_dispatch", 15, 20, 1), ("decode_readback", 20, 110, 1),
        ("decode_commit", 110, 130, 1), ("decode_admit", 133, 135, 2)]
    assert len(got) == 11


def test_the_window_clips_spans_and_counts_the_steps_that_start_in_it():
    busy, spans = one_step()
    ctx = ctx_of(busy, spans, (120, 200))
    under, idle, steps = stepgaps.idle_by_leaf(ctx)
    assert idle == 40 * US and steps == 1
    assert under["decode_commit"] == 10 * US
    assert under["decode_readback"] == 0


def test_an_uncovered_gap_lowers_the_covered_share():
    busy = [(0, 100), (160, 300)]
    spans = [step(10, 131, [("decode_dispatch", 20),
                            ("decode_readback", 130)], step=1, reqs=[1])]
    ctx = ctx_of(busy, spans, (0, 300))
    assert reader("decode_gap_covered").read(ctx) == pytest.approx(50.0)
    assert reader("decode_gap_readback_ms").read(ctx) == pytest.approx(0.03)
    assert reader("decode_gap_feed_ms").read(ctx) == 0.0
    # a device that was never idle has no share to give
    assert reader("decode_gap_covered").read(
        ctx_of([(0, 300)], spans, (0, 300))) is None


def steady_steps(late_us, n=6):
    """``n`` steps of 1000 us: the program runs from 100 to 900; the loop
    reads its tokens back until 940 and commits until 950; the next step
    begins at 960, gathers until 970, lands the arrays until 1020, and its
    dispatch (1020 to 1080) starts the next program at 1100. The spans are
    written ``late_us`` late, as a sync event that was off would leave
    them."""
    busy, programs, spans = [], [], []
    for k in range(n):
        b = k * 1000
        busy.append((b + 100, b + 900))
        programs.append((b + 100, b + 900))
        w = b + late_us
        spans.append(step(
            w - 40, w + 955,
            [("decode_gather", w - 30), ("decode_h2d", w + 20),
             ("decode_dispatch", w + 80), ("decode_readback", w + 940),
             ("decode_commit", w + 950)], step=k, reqs=[1]))
    return busy, programs, spans


@pytest.mark.parametrize("late_us", [0, 300, -200])
def test_spans_are_moved_onto_the_devices_clock_by_causality(late_us):
    """However late or early the sync event left the spans, the split
    comes out the same: the program starts 80 us after its dispatch began
    and its tokens are read 40 us after it ended, so the spans may be
    moved from 40 us earlier to 80 us later than the truth, and the
    midpoint is taken: 20 us late."""
    busy, programs, spans = steady_steps(late_us)
    ctx = ctx_of(busy, spans, (500, 5500), programs)
    assert stepgaps.causal_shift_ns(ctx) == (20 - late_us) * US
    under, idle, steps = stepgaps.idle_by_leaf(ctx)
    assert idle == 5 * 200 * US and steps == 5
    # per step, with every span 20 us late: of the 200 idle microseconds
    # the readback holds 60, the commit 10, the feed 10 + 50, the dispatch
    # 60; 10 lie between steps
    assert under == {"decode_admit": 0, "decode_gather": 50 * US,
                     "decode_h2d": 250 * US, "decode_dispatch": 300 * US,
                     "decode_readback": 300 * US, "decode_commit": 50 * US}


def test_no_shift_where_no_step_finds_its_program():
    busy, programs, spans = steady_steps(0)
    assert stepgaps.causal_shift_ns(ctx_of(busy, spans, (500, 5500))) == 0
    # a trace that ends before the steps: every pairing is refused
    far = [(s + 50000, e + 50000) for s, e in programs]
    assert stepgaps.causal_shift_ns(
        ctx_of(busy, spans, (500, 65000), far)) == 0
    # bounds that contradict each other (a readback that returns before
    # its program ended AND a program that starts before its dispatch)
    bad = [step(k * 1000 - 40, k * 1000 + 955,
                [("decode_dispatch", k * 1000 + 80),
                 ("decode_readback", k * 1000 + 700)], step=k, reqs=[1])
           for k in range(6)]
    early = [(s - 100, e) for s, e in programs]
    assert stepgaps.causal_shift_ns(
        ctx_of(busy, bad, (500, 5500), early)) == 0


def test_what_the_steps_say_they_did():
    busy, spans = one_step()
    ctx = ctx_of(busy, spans, (0, 300))
    # 2 tokens in 300 us
    assert reader("emitted_tok_per_s").read(ctx) == pytest.approx(2 / 300e-6)
    # 5 positions, 2 of them emitted
    assert reader("prompt_position_share").read(ctx) == pytest.approx(60.0)
    # the mean: the host's thread clock moves in ticks
    assert reader("loop_cpu_ms_per_step.batch").read(ctx) == 1.0
    assert reader("loop_cpu_ms_per_step.chat").read(ctx) == 1.0
    # only the steps that start in the window are read
    late = dict(ctx, window_ns=(100 * US, 300 * US))
    assert reader("emitted_tok_per_s").read(late) == pytest.approx(1 / 200e-6)
    assert reader("loop_cpu_ms_per_step.batch").read(late) == 1.1
    # a speculative round commits several positions a request
    spec = ctx_of(busy, [step(10, 132, [], step=1, reqs=[7, 8], pos=[3, 0],
                              n=[3, 2], emit=[3, 0], cpu_us=5)], (0, 300))
    assert reader("prompt_position_share").read(spec) == pytest.approx(40.0)


NEW = ("decode_gap_feed_ms", "decode_gap_dispatch_ms",
       "decode_gap_readback_ms", "decode_gap_commit_ms", "decode_gap_covered",
       "emitted_tok_per_s", "loop_cpu_ms_per_step.batch",
       "loop_cpu_ms_per_step.chat", "prompt_position_share")


@pytest.mark.parametrize("name", NEW)
def test_an_older_program_gives_nothing_to_read(name):
    """The parent commit's spans: ``decode_step`` with ``step`` and
    ``reqs`` alone, no lap. Every new reader returns None and raises
    nothing; so it does with no span at all."""
    busy, _ = one_step()
    old = [span("decode_step", 10, 132, step=1, reqs=[7, 8]),
           span("decode_step", 135, 290, step=2, reqs=[7, 8, 9])]
    assert reader(name).read(ctx_of(busy, old, (0, 300))) is None
    assert reader(name).read(ctx_of(busy, [], (0, 300))) is None
    assert reader(name).read(ctx_of([], [], (0, 300))) is None


def test_the_new_metrics_are_declared_for_the_cells_that_read_them():
    import json
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["source"] == "program_span" and m["layer"] == "Decode"
        chat = name in ("loop_cpu_ms_per_step.chat", "prompt_position_share")
        assert m["workloads"] == [
            "opt-1.3b.chat_steady" if chat else "opt-1.3b.batch_saturated"]
        assert m["moves"] == ("req_ms_per_token_p50" if chat
                              else "decode_tok_per_s")
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == list(NEW)
