"""Decode: share of the prompt positions the traced window committed that
went in by a prefill pass (the rest rode a step, one token each), by the
``decode_step`` spans' own ``prefill``, ``n`` and ``emit``
(``harness/prefill.py``)."""
from benchmark.harness import prefill


def read(ctx):
    return prefill.position_share(ctx)
