"""Grouped-query attention: the least time the attention operators of the
traced steps could take (their matrices and norms once, the K/V rows each
position attends and the row it writes:
``reference/<config>.py::attn_layer_work``) over the device time of the
operations under the ``layer/attn`` and ``cache_write/kv`` scopes (see
``harness/scopes.py``)."""
from benchmark.harness import scopes


def read(ctx):
    ref, cfg = ctx["ref"], ctx["cfg"]
    if not hasattr(ref, "attn_layer_work"):
        return None
    return scopes.layer_roofline(
        ctx, ("layer/attn", "cache_write/kv"),
        lambda row: ref.attn_layer_work(cfg, [pos + 1 for _, pos in row]))
