"""Model zoo: pure-functional JAX models with logical-axis sharding specs.

The reference keeps model math outside the framework (torch user code in
Train workers; vLLM behind ray.llm — SURVEY.md §2.3/§2.4).  A TPU-native
framework must own it: every model here is (a) a pure ``apply(params, batch)``
function safe under jit/pjit/scan/remat, and (b) a parameter *spec tree* of
logical axis names that ``ray_tpu.parallel`` maps onto any mesh — so DP,
FSDP, TP and SP are configuration, not code.
"""

from ray_tpu.models.llama import (  # noqa: F401
    LlamaConfig,
    llama_init,
    llama_apply,
    llama_loss,
    llama_param_specs,
)
from ray_tpu.models.moe import (  # noqa: F401
    MoEConfig,
    make_moe_trainer,
    moe_apply,
    moe_init,
    moe_loss,
    moe_param_specs,
)
from ray_tpu.models.paged_generation import SamplingParams  # noqa: F401
from ray_tpu.models.generation import (  # noqa: F401
    decode_step,
    generate,
    init_kv_cache,
    prefill,
)
from ray_tpu.models.vit import (  # noqa: F401
    ViTConfig,
    make_vit_trainer,
    vit_apply,
    vit_init,
    vit_loss,
    vit_param_specs,
)
