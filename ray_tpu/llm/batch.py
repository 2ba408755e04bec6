"""Batch LLM inference over ray_tpu.data Datasets.

Reference: ``python/ray/llm/_internal/batch/`` (vLLM engine stages driven by
``Dataset.map_batches`` with an actor pool).  Same shape here: a stateful
``LLMPredictor`` callable (one engine per actor, constructed once) applied
via ``map_batches(compute=ActorPoolStrategy)``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np


class LLMPredictor:
    """Stateful map_batches callable: holds one LLMEngine per actor."""

    def __init__(self, engine_kwargs: Optional[Dict[str, Any]] = None,
                 prompt_column: str = "prompt", output_column: str = "generated",
                 sampling: Optional[Dict[str, Any]] = None):
        from ray_tpu.models.paged_generation import SamplingParams
        from ray_tpu.models.llama import LlamaConfig
        from ray_tpu.llm.engine import LLMEngine

        kw = dict(engine_kwargs or {})
        cfg = kw.pop("cfg", None) or LlamaConfig.tiny()
        self.engine = LLMEngine(cfg, **kw)
        self.prompt_column = prompt_column
        self.output_column = output_column
        sp = dict(sampling or {})
        sp.setdefault("stop_token_id", self.engine.tokenizer.eos_id)
        self.sampling = SamplingParams(**sp)

    def __call__(self, batch: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        prompts = [str(p) for p in batch[self.prompt_column]]
        outs = self.engine.generate(prompts, self.sampling)
        batch[self.output_column] = np.array([o.text for o in outs],
                                             dtype=object)
        return batch


def build_llm_processor(dataset, *, engine_kwargs: Optional[Dict] = None,
                        concurrency: int = 1, batch_size: int = 16,
                        prompt_column: str = "prompt",
                        output_column: str = "generated",
                        sampling: Optional[Dict[str, Any]] = None,
                        num_tpus: float = 0):
    """dataset -> dataset with ``output_column`` of generations
    (reference: ``ray.data.llm.build_llm_processor``)."""
    from ray_tpu.data import ActorPoolStrategy

    return dataset.map_batches(
        LLMPredictor,
        fn_args=(engine_kwargs, prompt_column, output_column, sampling),
        batch_size=batch_size,
        compute=ActorPoolStrategy(size=concurrency,
                                  max_tasks_in_flight_per_actor=1),
        num_tpus=num_tpus,
    )
