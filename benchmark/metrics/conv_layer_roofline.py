"""Short convolution: the least time the conv operators of the traced steps
could take (both projections, the kernel and the norm once, each position's
state of ``conv_L_cache - 1`` rows read and one written:
``reference/<config>.py::conv_layer_work``) over the device time of the
operations under the ``layer/conv`` and ``cache_write/conv`` scopes (see
``harness/scopes.py``)."""
from benchmark.harness import scopes


def read(ctx):
    ref, cfg = ctx["ref"], ctx["cfg"]
    if not hasattr(ref, "conv_layer_work"):
        return None
    return scopes.layer_roofline(
        ctx, ("layer/conv", "cache_write/conv"),
        lambda row: ref.conv_layer_work(cfg, len(row)))
