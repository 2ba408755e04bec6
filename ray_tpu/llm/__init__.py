"""ray_tpu.llm: LLM batch inference + serving (reference: ``python/ray/llm/``).

The engine is TPU-native jax (slot-based continuous batching over a static
KV cache — ``engine.py``) instead of a vLLM delegation; batch inference
rides ``ray_tpu.data`` actor pools and serving rides ``ray_tpu.serve``.
"""

from ray_tpu.llm.batch import LLMPredictor, build_llm_processor
from ray_tpu.llm.engine import ByteTokenizer, GenerationOutput, LLMEngine
from ray_tpu.llm.kv_transfer import KVBlockShipper, KVLandingStrip
from ray_tpu.llm.serving import (
    LLMDecodeServer,
    LLMDisaggIngress,
    LLMPrefillServer,
    LLMServer,
    build_disaggregated_llm_deployment,
    build_llm_deployment,
    disaggregated_handle,
)
from ray_tpu.models.paged_generation import SamplingParams

__all__ = [
    "ByteTokenizer", "GenerationOutput", "KVBlockShipper",
    "KVLandingStrip", "LLMDecodeServer", "LLMDisaggIngress", "LLMEngine",
    "LLMPredictor", "LLMPrefillServer", "LLMServer", "SamplingParams",
    "build_disaggregated_llm_deployment", "build_llm_deployment",
    "build_llm_processor", "disaggregated_handle",
]
