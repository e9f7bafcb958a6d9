"""Builds the system under test for ``entry: decode_loop`` configurations of
the DeepSeek-V3 block (``model_type: kimi_k2`` among them):
``serving.DecodeLoop`` with ``arch=serving.DeepseekV3Arch(cfg)``, which reads
the configuration's own ``config.json`` keys, with only the programs the
cells use (no prefix-cache programs, no speculation)."""


def build(cfg, params, contexts=None):
    from mxnet_tpu import serving
    serve = cfg["serve"]
    return serving.DecodeLoop(
        params, max_len=int(serve["max_len"]), slots=int(serve["slots"]),
        quantize=serve["quantize"], prefix_cache=False, spec_k=0,
        contexts=contexts, arch=serving.DeepseekV3Arch(cfg))
