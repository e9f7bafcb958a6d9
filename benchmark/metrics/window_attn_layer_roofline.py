"""Window attention: the least time the attention operators of the
sliding-window layers of the traced steps could take (their matrices and
norms once, the ``min(context, sliding_window)`` K/V rows each position
attends and the row it writes:
``reference/<config>.py::window_attn_layer_work``) over the device time of
the operations under the ``layer/attn/window`` and
``cache_write/kv/window`` scopes (see ``harness/scopes.py``)."""
from benchmark.harness import scopes


def read(ctx):
    ref, cfg = ctx["ref"], ctx["cfg"]
    if not hasattr(ref, "window_attn_layer_work"):
        return None
    return scopes.layer_roofline(
        ctx, ("layer/attn/window", "cache_write/kv/window"),
        lambda row: ref.window_attn_layer_work(
            cfg, [pos + 1 for _, pos in row]))
