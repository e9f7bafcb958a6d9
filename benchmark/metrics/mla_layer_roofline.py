"""Latent attention: the least time the MLA layers of the traced steps
could take (the attention matrices once and each position's latent rows:
``reference/<config>.py::mla_layer_work``) over the device time of the
operations under the ``layer/mla`` and ``cache_write`` scopes (see
``harness/scopes.py``)."""
from benchmark.harness import scopes


def read(ctx):
    ref, cfg = ctx["ref"], ctx["cfg"]
    return scopes.layer_roofline(
        ctx, ("layer/mla", "cache_write"),
        lambda row: ref.mla_layer_work(cfg, [pos + 1 for _, pos in row]))
