"""Builds the system under test for ``entry: decode_loop`` configurations:
``serving.DecodeLoop`` over the configuration's sizes, with only the
programs the cells use (no prefix-cache programs, no speculation)."""


def build(cfg, params, contexts=None):
    from mxnet_tpu import serving
    serve = cfg["serve"]
    return serving.DecodeLoop(
        params, int(cfg["num_hidden_layers"]),
        int(cfg["num_attention_heads"]), int(serve["max_len"]),
        slots=int(serve["slots"]), quantize=serve["quantize"],
        prefix_cache=False, spec_k=0, contexts=contexts)
