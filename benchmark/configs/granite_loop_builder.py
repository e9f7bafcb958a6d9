"""Builds the system under test for ``entry: decode_loop`` configurations of
the Granite 4.0-H hybrid block (``model_type: granitemoehybrid``, no
experts): ``serving.DecodeLoop`` with ``arch=serving.GraniteHybridArch(cfg)``,
which reads the configuration's own ``config.json`` keys, with only the
programs the cells use (this architecture's recurrent state refuses the
prefix cache and speculation)."""


def build(cfg, params, contexts=None):
    from mxnet_tpu import serving
    serve = cfg["serve"]
    return serving.DecodeLoop(
        params, max_len=int(serve["max_len"]), slots=int(serve["slots"]),
        quantize=serve["quantize"], prefix_cache=False, spec_k=0,
        contexts=contexts, arch=serving.GraniteHybridArch(cfg))
