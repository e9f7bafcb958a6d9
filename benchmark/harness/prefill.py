"""What ``DecodeLoop`` says about its prefill passes in its own
``decode_step`` spans, for the readers of ``prefill_position_share`` and
``prefill_positions_per_pass``.

A span whose loop dispatched a prefill pass carries ``prefill``: one entry
``[req, slot, pos0, n]`` a slot the pass carried (a PACKED pass carries
several slots; a one-slot pass one), ``n`` the prompt positions it
committed for that slot. A program from before the packed pass wrote the
ONE entry bare, not in a list: both are read. The span's own ``n`` and
``emit`` (``harness/stepgaps.py``) count those positions among the
request's, so prompt positions are ``n - emit`` summed, by a pass or by a
step alike. A loop without a prefill program (its ``loop_program`` span
names none) gives every reader here nothing to read: ``None``.
"""
from . import stepgaps


def has_pass(ctx):
    """Whether the run's loop has a prefill program at all."""
    return any(name == "loop_program" and args.get("prefill_program")
               for name, _, _, args in ctx["spans"])


def passes(ctx):
    """``[[[req, slot, pos0, n], ...], ...]``: the entries of every pass
    the traced window's steps dispatched, one list a pass."""
    out = []
    for args in stepgaps.step_args(ctx):
        entries = args.get("prefill")
        if entries:
            bare = not isinstance(entries[0], (list, tuple))
            out.append([entries] if bare else entries)
    return out


def position_share(ctx):
    """Prompt positions the window's passes committed over all prompt
    positions the window committed, in %."""
    if not has_pass(ctx):
        return None
    args = stepgaps.step_args(ctx)
    prompt = sum(sum(a["n"]) - sum(a["emit"]) for a in args)
    if not prompt:
        return None
    return 100.0 * sum(e[3] for p in passes(ctx) for e in p) / prompt


def positions_per_pass(ctx):
    """Mean prompt positions a pass of the window carried."""
    found = passes(ctx)
    if not found:
        return None
    return sum(e[3] for p in found for e in p) / len(found)
