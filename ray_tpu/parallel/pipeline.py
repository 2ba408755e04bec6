"""In-graph pipeline parallelism over the ``pp`` mesh axis.

The reference delegates pipeline parallelism to vLLM
(``python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_models.py:127``
``pipeline_parallel_size`` → placement-group bundles) and provides only the
channel substrate for inter-actor pipelining
(``python/ray/dag/dag_node_operation.py``).  Here PP is a first-class mesh
axis like dp/fsdp/tp/sp, implemented the TPU way:

- layer-stacked params are sharded over ``pp`` (each stage holds
  ``L / pp_size`` contiguous layers);
- the microbatch schedule is a ``lax.scan`` of compute+rotate ticks in
  PLAIN GSPMD: per-stage activation buffers ride a leading stage dim
  sharded over ``pp``, the per-tick stage compute is a ``vmap`` over
  that dim (each pp shard runs its own stage), and the inter-stage hop
  is ``jnp.roll`` on the sharded dim — which XLA lowers to exactly the
  ``collective-permute`` ring a manual ``ppermute`` would issue.  No
  ``shard_map`` at all: sharding annotations alone express the
  program, and dp/fsdp/tp stay auto-partitioned inside each stage for
  free;
- reverse-mode AD transposes the roll (a roll the other way), so the
  backward pass is the mirrored pipeline schedule for free.  With
  per-layer remat the live state per stage is one microbatch activation
  + the output buffer, which is the 1F1B memory profile (activations
  for at most the in-flight microbatches, not all of them).

Bubble fraction is ``(S-1) / (M + S - 1)`` for S stages and M microbatches;
raise ``num_microbatches`` to amortize.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pp_size(mesh: Optional[Mesh], axis: str = "pp") -> int:
    """Number of pipeline stages in the mesh (1 when no pp axis)."""
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return mesh.shape[axis]


def pipeline_apply(
    layer_fn: Callable[[jnp.ndarray, Any], jnp.ndarray],
    stacked_params: Any,
    x: jnp.ndarray,
    *,
    mesh: Mesh,
    num_microbatches: Optional[int] = None,
    axis: str = "pp",
) -> jnp.ndarray:
    """Run ``x`` through L stacked layers pipelined over the ``axis`` stages.

    ``layer_fn(x, layer_params) -> x`` is the per-layer body (already
    remat-wrapped by the caller if desired).  ``stacked_params`` is a pytree
    whose leaves have a leading layer dimension L, sharded over ``axis``
    (each stage owns a contiguous block of L/S layers).  ``x`` is
    ``[batch, ...]`` and must be divisible into ``num_microbatches``.

    Returns the activations after all L layers, same shape as ``x``.
    """
    S = pp_size(mesh, axis)
    if S == 1:
        def body(carry, lp):
            return layer_fn(carry, lp), None
        out, _ = jax.lax.scan(body, x, stacked_params)
        return out

    M = num_microbatches or S
    b = x.shape[0]
    if b % M != 0:
        raise ValueError(f"batch {b} not divisible by {M} microbatches")
    n_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_layers % S != 0:
        raise ValueError(f"{n_layers} layers not divisible by {S} stages")

    def _pp_constrain(v):
        # leading stage dim over `axis`, everything else auto (GSPMD
        # keeps partitioning the dp/fsdp/tp dims inside each stage)
        return jax.lax.with_sharding_constraint(
            v, jax.sharding.NamedSharding(mesh, P(axis)))

    micro = x.reshape((M, b // M) + x.shape[1:])
    # [L, ...] -> [S, L/S, ...]: stage s owns the contiguous layer block
    # [s*L/S, (s+1)*L/S), stage dim sharded over `axis`.
    staged_params = jax.tree.map(
        lambda p: _pp_constrain(
            p.reshape((S, n_layers // S) + p.shape[1:])),
        stacked_params)

    def stage_body(state, layers_shard):
        def body(carry, lp):
            return layer_fn(carry, lp), None
        out, _ = jax.lax.scan(body, state, layers_shard)
        return out

    # buf[i] = the activation currently sitting at stage i.
    buf = jnp.zeros((S,) + micro.shape[1:], micro.dtype)
    outputs = jnp.zeros_like(micro)

    def tick(carry, t):
        buf, outputs = carry
        # Stage 0 ingests microbatch t (clamped; masked off past M).
        inp = jax.lax.dynamic_index_in_dim(
            micro, jnp.minimum(t, M - 1), axis=0, keepdims=False)
        buf = jax.lax.dynamic_update_index_in_dim(buf, inp, 0, axis=0)
        # One tick of every stage: vmap over the sharded stage dim puts
        # each stage's layer scan on its own pp shard.
        buf = _pp_constrain(buf)
        buf = jax.vmap(stage_body)(buf, staged_params)
        buf = _pp_constrain(buf)
        # Last stage emits microbatch t-(S-1) once the fill completes.
        out_idx = t - (S - 1)
        emitted = jax.lax.dynamic_index_in_dim(
            buf, S - 1, axis=0, keepdims=False)
        updated = jax.lax.dynamic_update_index_in_dim(
            outputs, emitted, jnp.maximum(out_idx, 0), axis=0)
        outputs = jnp.where(out_idx >= 0, updated, outputs)
        # Rotate activations one stage down the ring (roll on the
        # pp-sharded dim == XLA collective-permute).
        buf = _pp_constrain(jnp.roll(buf, 1, axis=0))
        return (buf, outputs), None

    (buf, outputs), _ = jax.lax.scan(
        tick, (buf, outputs), jnp.arange(M + S - 1))
    return outputs.reshape(x.shape)


def pipeline_microbatches(cfg_microbatches: Optional[int], mesh: Mesh,
                          axis: str = "pp") -> int:
    """Default microbatch count: 2*stages (25%→~14% bubble vs M=S)."""
    return cfg_microbatches or 2 * pp_size(mesh, axis)


def reject_pp(mesh: Optional[Mesh], family: str, rules=None):
    """Guard for model families without a pipeline apply path.

    Raises on pp>1 meshes, and — only when the caller supplied no rule
    table of their own — replicates stacked layers over pp instead of
    stage-sharding them (a stage-sharded stack under a plain lax.scan
    would all-gather every layer, every step).  Returns the rule table to
    use.
    """
    if pp_size(mesh) > 1:
        raise ValueError(
            f"{family} has no pipeline (pp) apply path; use dp/fsdp/tp/sp "
            "axes (pp is llama-only for now)"
        )
    if rules is None:
        from ray_tpu.parallel.sharding import DEFAULT_RULES

        return {**DEFAULT_RULES, "layers": None}
    return rules
