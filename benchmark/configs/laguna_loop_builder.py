"""Builds the system under test for ``entry: decode_loop`` configurations of
the Laguna block (``model_type: laguna``): ``serving.DecodeLoop`` with
``arch=serving.LagunaArch(cfg)``, which reads the configuration's own
``config.json`` keys, with only the programs the cells use (this
architecture's ring over the sliding-window layers refuses the prefix cache
and speculation)."""


def build(cfg, params, contexts=None):
    from mxnet_tpu import serving
    serve = cfg["serve"]
    return serving.DecodeLoop(
        params, max_len=int(serve["max_len"]), slots=int(serve["slots"]),
        quantize=serve["quantize"], prefix_cache=False, spec_k=0,
        contexts=contexts, arch=serving.LagunaArch(cfg))
