"""paddle_tpu.inference — the inference engine (SURVEY.md L9).

Reference surface: `paddle.inference` (Config/Predictor over
`paddle/fluid/inference/api/analysis_predictor.h:105`) plus the serving
decode stack (paged KV cache + fused multi-transformer, §2.3 fusion kernels).

Components:
- `Config` / `create_predictor` / `Predictor`: handle-based execution of
  jit-saved StableHLO programs (predictor.py).
- `BlockCacheManager`: paged KV-cache block tables with refcounted
  copy-on-write sharing (cache.py).
- `RadixPrefixCache`: shared-prefix radix tree over the paged pool —
  committed KV reused across requests/sessions (prefix_cache.py).
- `LlamaInferenceEngine` / `GenerationConfig`: ONE fused
  scan-over-layers ragged step with the Pallas paged-attention kernel
  (llama_runner.py); `generate()` is a host loop over it (generate.py).
"""
from .cache import BlockCacheManager, KVCacheExhausted, SequenceTooLong
from .prefix_cache import RadixPrefixCache
from .llama_runner import GenerationConfig, LlamaInferenceEngine
from .predictor import (Config, DataType, PlaceType, Predictor,
                        PredictorTensor, create_predictor, get_version)

__all__ = [
    "Config", "DataType", "PlaceType", "Predictor", "PredictorTensor",
    "create_predictor", "get_version", "BlockCacheManager",
    "KVCacheExhausted", "RadixPrefixCache", "SequenceTooLong",
    "GenerationConfig", "LlamaInferenceEngine",
]
