"""Full attention: the least time the attention operators of the
full-attention layers of the traced steps could take, in a model that has
window layers beside them (their matrices and norms once, every K/V row
each position attends and the row it writes:
``reference/<config>.py::full_attn_layer_work``) over the device time of
the operations under the ``layer/attn/full`` and ``cache_write/kv/full``
scopes (see ``harness/scopes.py``)."""
from benchmark.harness import scopes


def read(ctx):
    ref, cfg = ctx["ref"], ctx["cfg"]
    if not hasattr(ref, "full_attn_layer_work"):
        return None
    return scopes.layer_roofline(
        ctx, ("layer/attn/full", "cache_write/kv/full"),
        lambda row: ref.full_attn_layer_work(
            cfg, [pos + 1 for _, pos in row]))
