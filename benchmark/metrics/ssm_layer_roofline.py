"""State-space layer: the least time the Mamba-2 mixers of the traced steps
could take (both projections, the kernel and the norms once, each
position's float32 state read and written whole and its conv rows:
``reference/<config>.py::ssm_layer_work``) over the device time of the
operations under the ``layer/ssm`` and ``cache_write/ssm`` scopes (see
``harness/scopes.py``)."""
from benchmark.harness import scopes


def read(ctx):
    ref, cfg = ctx["ref"], ctx["cfg"]
    if not hasattr(ref, "ssm_layer_work"):
        return None
    return scopes.layer_roofline(
        ctx, ("layer/ssm", "cache_write/ssm"),
        lambda row: ref.ssm_layer_work(cfg, len(row)))
