"""Logical-axis → mesh-axis sharding rules (GSPMD partitioning).

Model code annotates arrays with *logical* axis names ("batch", "embed",
"mlp", "heads", "seq", "vocab"); a rule table maps those to mesh axes.
Switching parallelism strategy = switching the rule table, not the model.

This replaces the reference's per-strategy engines (DDP wrap at
``torch_learner.py:432``, FSDP at ``train_loop_utils.py:176``, vLLM TP/PP)
with one declarative mechanism.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# A logical axis maps to one mesh axis, a tuple of mesh axes, or None
# (replicated).
LogicalAxisRules = Dict[str, Union[str, Tuple[str, ...], None]]

# Default rules: batch over (dp, fsdp); weights sharded over fsdp on their
# largest dim and over tp Megatron-style; sequence over sp for ring attention.
# Every logical axis any models/ spec tree uses MUST appear here — an
# explicit None records a deliberate replication decision; a *missing*
# name would replicate silently, which the tooling guard
# (tests/test_sharded_train.py) rejects.
DEFAULT_RULES: LogicalAxisRules = {
    "batch": ("dp", "fsdp"),
    "seq": "sp",
    "embed": "fsdp",
    "mlp": "tp",
    "heads": "tp",
    "kv_heads": "tp",
    "qkv": None,
    "head_dim": None,
    "vocab": "tp",
    "expert": "tp",
    "layers": "pp",
    # norm scales / biases / cls tokens: O(hidden) vectors — sharding
    # them saves nothing and costs an all-gather per use
    "norm": None,
}

# Rules for inference-style TP-only sharding (no fsdp axis in use).
TP_INFERENCE_RULES: LogicalAxisRules = {
    **DEFAULT_RULES,
    "embed": None,
    "batch": "dp",
}


def logical_to_pspec(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[LogicalAxisRules] = None,
    *,
    mesh: Optional[Mesh] = None,
) -> P:
    """Map a tuple of logical axis names to a PartitionSpec.

    Axes not in the rule table (or mapped to None) are replicated.  A mesh
    axis may be consumed at most once per spec; later conflicting uses are
    replicated instead (GSPMD requires distinct mesh axes per dim).
    """
    rules = DEFAULT_RULES if rules is None else rules
    used: set = set()
    out = []
    for ax in logical_axes:
        mapped = rules.get(ax) if ax is not None else None
        if mapped is None:
            out.append(None)
            continue
        axes = (mapped,) if isinstance(mapped, str) else tuple(mapped)
        if mesh is not None:
            axes = tuple(a for a in axes if a in mesh.axis_names)
        axes = tuple(a for a in axes if a not in used)
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def spec_tree_to_shardings(
    spec_tree: Any, mesh: Mesh, rules: Optional[LogicalAxisRules] = None
) -> Any:
    """Convert a pytree of logical-axis tuples into NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(
            mesh, logical_to_pspec(axes, rules, mesh=mesh)
        ),
        spec_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x),
    )


def shard_tree(
    tree: Any,
    spec_tree: Any,
    mesh: Mesh,
    rules: Optional[LogicalAxisRules] = None,
) -> Any:
    """Device-put a pytree according to its logical-axis spec tree."""
    shardings = spec_tree_to_shardings(spec_tree, mesh, rules)
    return jax.tree.map(jax.device_put, tree, shardings)


def with_logical_constraint(
    x: jax.Array,
    mesh: Optional[Mesh],
    *axes: Optional[str],
    rules: Optional[LogicalAxisRules] = None,
) -> Any:
    """Constrain an intermediate value's sharding inside jit, by
    LOGICAL axis names resolved through the rule table.

    This is the one sanctioned way for model code to pin a layout: the
    same rule table that shards the params decides the activation
    layout, so a rules override (``ScalingConfig.logical_axis_rules``,
    ``ShardedTrainer(rules=...)``) moves params *and* activations
    together — mismatched halves are exactly what XLA's involuntary
    full rematerializations punished.  ``mesh=None`` is a no-op so
    model code stays mesh-optional.  The raylint ``sharding-discipline``
    rule rejects raw device-axis ``PartitionSpec`` literals in
    ``models/`` in favor of this helper.
    """
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, logical_to_pspec(axes, rules, mesh=mesh))
    )


def with_named_sharding(x: jax.Array, mesh: Mesh, *axes: Optional[str]) -> Any:
    """Back-compat alias: :func:`with_logical_constraint` under
    :data:`DEFAULT_RULES` (no rule-table override)."""
    return with_logical_constraint(x, mesh, *axes)
