"""What ``DecodeLoop`` says about its own step, shared by the readers of
``decode_gap_*``, ``emitted_tok_per_s``, ``loop_cpu_ms_per_step.*`` and
``prompt_position_share``.

Every ``decode_step`` span ends with what the step did: ``pos``, ``n``,
``emit`` (aligned with ``reqs``), ``cpu_us``, and ``laps``: the phases of
the step's host round trip (``LEAVES``), contiguous, as ``[name,
offset_us, dur_us]`` inside the one event (the tracer's ``span.lap``);
``decode_admit`` is a span of its own between two steps. An idle gap of
the device runs across several leaves, so it is not given whole to the
span that overlaps it most (``Trace.attribute_gaps``): each leaf gets the
length of the gap's overlap with it. A program without these arguments
(any commit before they were added) gives every reader here nothing to
read: ``None``.
"""
import bisect

from . import spans as _spans

#: the phases of one step, in the order the loop runs them; ``decode_admit``
#: runs between two ``decode_step`` spans, the others are laps of one
LEAVES = ("decode_admit", "decode_gather", "decode_h2d", "decode_dispatch",
          "decode_readback", "decode_commit")


def leaves(spans):
    """``[(name, start_ns, end_ns, step)]`` of every leaf: the laps of the
    ``decode_step`` spans, and the ``decode_admit`` spans."""
    out = []
    for name, s, e, args in spans:
        if name == "decode_admit":
            out.append((name, s, e, args.get("step")))
        elif name == "decode_step":
            for lap in args.get("laps", ()):
                at = s + lap[1] * 1000
                out.append((lap[0], at, at + lap[2] * 1000,
                            args.get("step")))
    return out


def intersect_ns(gaps, start, end):
    """Length of ``[start, end)`` that lies inside ``gaps``: sorted,
    disjoint ``(start_ns, end_ns)`` pairs."""
    i = bisect.bisect_right(gaps, (start, start)) - 1
    if i < 0 or gaps[i][1] <= start:
        i += 1
    total = 0
    while i < len(gaps) and gaps[i][0] < end:
        total += min(end, gaps[i][1]) - max(start, gaps[i][0])
        i += 1
    return total


def causal_shift_ns(ctx):
    """What to add to the loop's span times so that they sit on the
    DEVICE's clock, from the order that must hold in every step: the step
    program cannot start before the loop began its ``decode_dispatch``,
    and ``decode_readback`` cannot return before the program ended.

    The spans come onto the trace's clock through one sync event, and the
    device's own events through the profiler's clock conversion: chip runs
    (PR 27) put the two a millisecond apart in two runs of five, with
    programs that started before their dispatch did. That is nothing to a
    40 ms step and everything to the split of a 4 ms gap. Over the steps
    of the window, ``U`` = min(program start - dispatch start) and ``L`` =
    max(program end - readback end) bound the shift; nothing in the trace
    decides between them, so the midpoint is taken, which is wrong by half
    of ``U - L`` at the most and moves idle only between
    ``decode_dispatch`` and ``decode_readback``. ``0`` where no step can
    be paired with its program."""
    t0, t1 = ctx["window_ns"]
    runs = ctx["trace"].whole_runs(ctx["cfg"]["program"], t0, t1)
    starts = [s for s, _ in runs]
    # per step the last dispatch and the last readback: the step program's
    # (a speculative round's draft passes come before its verify pass)
    dispatch, readback = {}, {}
    for name, s, e, step in leaves(ctx["spans"]):
        if name == "decode_dispatch":
            dispatch[step] = (s, e)
        elif name == "decode_readback":
            readback[step] = e
    lo, hi = None, None
    for step, (ds, de) in dispatch.items():
        if step not in readback or not starts:
            continue
        # the program this dispatch started: the run that starts nearest
        # to where the dispatch returned (a step is ten gaps long)
        i = bisect.bisect_left(starts, de)
        ps, pe = min(runs[max(i - 1, 0):i + 1], key=lambda r: abs(r[0] - de))
        re = readback[step]
        if max(abs(ps - de), abs(pe - re)) > (pe - ps) // 2:
            continue    # its own program is not in the trace
        hi = ps - ds if hi is None else min(hi, ps - ds)
        lo = pe - re if lo is None else max(lo, pe - re)
    if lo is None or lo > hi:
        return 0
    return (lo + hi) // 2


def idle_by_leaf(ctx):
    """``({leaf: idle ns under it}, idle ns of the window, steps)`` over
    the traced window, or ``None`` where the program has no leaf span or
    the window no step. ``steps`` counts the ``decode_step`` spans that
    start in the window. The spans are first moved onto the device's
    clock (``causal_shift_ns``)."""
    t0, t1 = ctx["window_ns"]
    shift = causal_shift_ns(ctx)
    inside = [(n, max(s + shift, t0), min(e + shift, t1))
              for n, s, e, _ in leaves(ctx["spans"])
              if n in LEAVES and e + shift > t0 and s + shift < t1]
    steps = len(_spans.named(ctx["spans"], "decode_step", t0 - shift,
                             t1 - shift))
    if not inside or not steps:
        return None
    gaps = ctx["trace"].idle_gaps(t0, t1)
    under = dict.fromkeys(LEAVES, 0)
    for name, s, e in inside:
        under[name] += intersect_ns(gaps, s, e)
    return under, sum(e - s for s, e in gaps), steps


def gap_ms(ctx, names):
    """Device idle per step, in ms, while the loop was in one of ``names``."""
    found = idle_by_leaf(ctx)
    if found is None:
        return None
    under, _, steps = found
    return sum(under[n] for n in names) / 1e6 / steps


def covered(ctx):
    """Share of the window's device idle that lies under any leaf: how
    much of the idle the loop's own spans account for."""
    found = idle_by_leaf(ctx)
    if found is None or not found[1]:
        return None
    under, idle, _ = found
    return 100.0 * sum(under.values()) / idle


def step_args(ctx):
    """The arguments of the window's ``decode_step`` spans that say what
    the step did (``emit`` present): empty for an older program."""
    t0, t1 = ctx["window_ns"]
    return [a for _, _, _, a in _spans.named(ctx["spans"], "decode_step",
                                             t0, t1) if "emit" in a]


def emitted_per_s(ctx):
    args = step_args(ctx)
    if not args:
        return None
    t0, t1 = ctx["window_ns"]
    return sum(sum(a["emit"]) for a in args) / ((t1 - t0) / 1e9)


def loop_cpu_ms(ctx):
    """CPU time of the loop thread itself per step: the MEAN over the
    window's steps. The thread CPU clock of the chip's host moves in
    whole scheduler ticks (10 ms: chip run, PR 27), far above what the
    loop thread spends a step, so single steps read 0 or a tick and their
    median is 0; the mean of such samples is unbiased."""
    cpu = [a["cpu_us"] for a in step_args(ctx) if "cpu_us" in a]
    return sum(cpu) / 1e3 / len(cpu) if cpu else None


def prompt_position_share(ctx):
    """Cache positions committed that emitted no token, over all positions
    committed, by the steps' own count (``n`` and ``emit``)."""
    args = step_args(ctx)
    positions = sum(sum(a["n"]) for a in args)
    if not positions:
        return None
    return 100.0 * (positions - sum(sum(a["emit"]) for a in args)) / positions
