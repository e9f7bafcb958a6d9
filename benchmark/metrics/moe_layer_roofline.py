"""Expert layer: the least time the expert layers of the traced steps could
take (router, shared expert and every held expert's weights once:
``reference/<config>.py::moe_layer_work``) over the device time of the
operations under the ``layer/moe/*`` scopes (see ``harness/scopes.py``)."""
from benchmark.harness import scopes


def read(ctx):
    ref, cfg = ctx["ref"], ctx["cfg"]
    return scopes.layer_roofline(
        ctx, ("layer/moe",), lambda row: ref.moe_layer_work(cfg, len(row)))
