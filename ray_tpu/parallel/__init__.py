"""TPU parallelism substrate: device meshes, logical-axis sharding, shard_map.

This is the layer the reference delegates to torch DDP/FSDP + NCCL
(``python/ray/train/torch/config.py:153``, ``train_loop_utils.py:170-178``)
and to vLLM for TP/PP (``python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_models.py:123-127``).  Here all parallel strategies — DP, FSDP/ZeRO, TP,
SP (sequence/context), EP — are sharding specifications over a single
``jax.sharding.Mesh``; XLA inserts the collectives (psum/all_gather/
reduce_scatter/ppermute) over ICI/DCN.
"""

from ray_tpu.parallel.mesh import (  # noqa: F401
    MESH_PRESETS,
    MeshConfig,
    create_mesh,
    create_hybrid_mesh,
    mesh_shape_for,
    local_mesh,
    resolve_mesh_config,
)
from ray_tpu.parallel.pipeline import (  # noqa: F401
    pipeline_apply,
    pp_size,
)
from ray_tpu.parallel.sharding import (  # noqa: F401
    LogicalAxisRules,
    DEFAULT_RULES,
    logical_to_pspec,
    spec_tree_to_shardings,
    shard_tree,
    with_logical_constraint,
    with_named_sharding,
)
from ray_tpu.parallel.xla_warnings import (  # noqa: F401
    count_sharding_warnings,
    sharding_warning_capture,
)
