"""mxnet_tpu.serving — the production inference tier (docs/serving.md).

Three layers over the standalone :class:`~mxnet_tpu.predictor.Predictor`:

* :class:`ServingEngine` — the stripped-head forward AOT-compiled for a
  fixed set of batch-size buckets at load time, with serialized-executable
  export/import for cold-start-free deploys; every program registers with
  :mod:`mxnet_tpu.tracecheck`.
* :class:`Batcher` — a request queue + batching thread coalescing
  concurrent ``infer()`` calls into the smallest covering bucket, with
  max-latency / max-batch / deadline / back-pressure knobs
  (``MXTPU_SERVE_*``).
* :class:`DecodeLoop` — slot-based continuous batching for a language
  model: the slots' state (K and V rows, or whatever arrays the model's
  :class:`Architecture` names: :class:`OptArch` is the default,
  :class:`DeepseekV3Arch` keeps latent rows and holds a share of a routed
  expert layer, :class:`Lfm2Arch` keeps K and V rows over its attention
  layers and a two-row conv state over the others, :class:`MellumArch` a
  ring of K and V rows over its sliding-window layers beside K and V rows a
  position over its full ones, :class:`LagunaArch` the same state under
  gated heads of two counts, partial rotary positions and a shared expert,
  :class:`GraniteHybridArch` K and V rows over its attention layers beside
  a conv state and a float32 matrix state a head over its Mamba-2 layers)
  is donated device state
  stepped by one compiled decode
  body; sequences join and leave mid-stream. The
  production decode path layers four separate legs on top,
  each behind a knob (docs/serving.md):

  - **in-graph sampling** (temperature/top-k/top-p, per-slot seed
    streams riding the donated state; ``temperature=0`` is bitwise the
    greedy path),
  - **weight quantization** (``quantize="bf16"|"int8"``, per-channel
    scales, dequant inside the body, quality-gated via
    :func:`check_quality`),
  - **prefix/KV-cache reuse** (shared prompts prefilled once,
    slot-cloned on join; LRU ``MXTPU_SERVE_PREFIX_MAX``),
  - **speculative decoding** (``spec_k`` draft tokens per round from a
    co-resident draft model, verified by ONE batched target pass;
    token-identical to target-only decoding under the same seeds).
* :class:`FleetRouter` — N data-parallel replicas (each its own engine +
  batcher, single-chip or model-axis-sharded via
  ``ServingEngine(contexts=...)``) behind priority-aware least-loaded
  dispatch with elastic drain/join and death re-queue (``MXTPU_FLEET_*``).

Degradation is counted in :class:`ServingHealth` (process-global aggregate
``serving.SERVING_HEALTH``), mirroring ``io.DATA_HEALTH`` /
``guard.TRAINING_HEALTH``.
"""
from .health import ServingHealth, SERVING_HEALTH
from .engine import ServingEngine, default_buckets
from .batcher import (Batcher, ServingError, ServingDeadlineError,
                      ServingOverloadedError, ServingClosedError)
from .arch import Architecture
from .decode import DecodeLoop, GenerateFuture, OptArch
from .deepseek_v3 import DeepseekV3Arch
from .lfm2 import Lfm2Arch
from .mellum import MellumArch
from .laguna import LagunaArch
from .granite_hybrid import GraniteHybridArch
from .fleet import FleetRouter, FleetRequest, CLASSES as FLEET_CLASSES
from .quantize import (QUANT_MODES, check_quality, quality_report,
                       quantize_tree, tree_bytes)

__all__ = [
    "ServingEngine", "Batcher", "DecodeLoop", "GenerateFuture",
    "Architecture", "OptArch", "DeepseekV3Arch", "Lfm2Arch", "MellumArch",
    "LagunaArch", "GraniteHybridArch",
    "FleetRouter", "FleetRequest", "FLEET_CLASSES",
    "ServingHealth", "SERVING_HEALTH", "default_buckets",
    "ServingError", "ServingDeadlineError", "ServingOverloadedError",
    "ServingClosedError",
    "QUANT_MODES", "check_quality", "quality_report", "quantize_tree",
    "tree_bytes",
]
