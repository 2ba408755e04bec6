"""Device-mesh construction for TPU pod slices.

The canonical mesh has five named axes, outermost to innermost:

    ("dp", "fsdp", "pp", "tp", "sp")

- ``dp``:   pure data parallelism (gradients psum'd; params replicated)
- ``fsdp``: ZeRO-style sharded data parallelism (params/opt-state sharded,
            all-gathered for compute) — the reference reaches this via torch
            FSDP (``train_loop_utils.py:176-178``); here it is an axis.
- ``pp``:   pipeline parallelism (layer-stacked params sharded by stage;
            microbatch ppermute schedule in ``parallel/pipeline.py``) — the
            reference delegates PP to vLLM (``vllm_models.py:127``).
- ``tp``:   tensor parallelism (Megatron-style column/row sharding)
- ``sp``:   sequence/context parallelism (ring attention) — absent from the
            reference entirely (SURVEY.md §2.4); first-class here.

Axis ordering matters on hardware: innermost axes get ICI-adjacent devices
(jax device order follows the torus), so tp/sp ride ICI while dp can span
slices over DCN.  ``create_hybrid_mesh`` makes that split explicit.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

MESH_AXES: Tuple[str, ...] = ("dp", "fsdp", "pp", "tp", "sp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes for each mesh axis; -1 on at most one axis means "infer".

    ``MeshConfig(dp=-1, tp=4)`` on 16 devices → (4, 1, 1, 4, 1).
    """

    dp: int = -1
    fsdp: int = 1
    pp: int = 1
    tp: int = 1
    sp: int = 1

    def _sizes(self) -> Dict[str, int]:
        return {"dp": self.dp, "fsdp": self.fsdp, "pp": self.pp,
                "tp": self.tp, "sp": self.sp}

    def _named(self, only_fixed: bool = False) -> str:
        """Human-readable axis sizes, e.g. "dp=2, tp=4"."""
        items = [(a, s) for a, s in self._sizes().items()
                 if not (only_fixed and s in (1, -1))]
        return ", ".join(f"{a}={s}" for a, s in items) or "all axes = 1"

    def resolve(self, n_devices: int) -> Tuple[int, int, int, int, int]:
        sizes = self._sizes()
        for axis, s in sizes.items():
            if s != -1 and s < 1:
                raise ValueError(
                    f"mesh axis {axis!r}={s} is invalid: sizes must be a "
                    "positive int, or -1 on at most one axis to infer it")
        infer = [a for a, s in sizes.items() if s == -1]
        if len(infer) > 1:
            raise ValueError(
                "at most one mesh axis may be -1 (inferred), got "
                + ", ".join(f"{a}=-1" for a in infer))
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if infer:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"cannot infer mesh axis {infer[0]!r}: {n_devices} "
                    f"devices not divisible by the fixed axes "
                    f"({self._named(only_fixed=True)}; product {fixed}); "
                    f"use MeshConfig.clamp_to({n_devices}) to degrade "
                    "gracefully")
            sizes[infer[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh ({self._named()}) needs {fixed} devices, have "
                f"{n_devices}; use MeshConfig.clamp_to({n_devices}) to "
                "degrade gracefully")
        return tuple(sizes[a] for a in MESH_AXES)  # type: ignore[return-value]

    def clamp_to(self, n_devices: int) -> "MeshConfig":
        """Degrade this mesh request to fit ``n_devices``, never raising
        on divisibility: the concrete config it returns always resolves.

        Model axes keep their requested size preferentially (clamp order
        tp → sp → pp → fsdp → dp, innermost first — the axes that ride
        ICI shrink last); each fixed axis is reduced to the largest size
        ≤ its request that divides the remaining device budget.  An
        inferred (-1) axis absorbs whatever remains; with no inferred
        axis, leftover devices fold into ``dp`` (data parallelism is the
        one axis that scales a training run without resharding params).

        This is what elastic re-mesh uses: a drain that shrinks the
        worker group re-forms a valid smaller mesh from the same
        *requested* config instead of dying on an axis-divisibility
        error.
        """
        if n_devices < 1:
            raise ValueError(f"clamp_to needs >= 1 device, got {n_devices}")
        sizes = self._sizes()
        infer = [a for a, s in sizes.items() if s == -1]
        if len(infer) > 1:
            raise ValueError(
                "at most one mesh axis may be -1 (inferred), got "
                + ", ".join(f"{a}=-1" for a in infer))
        budget = n_devices
        for axis in ("tp", "sp", "pp", "fsdp", "dp"):
            s = sizes[axis]
            if s == -1:
                continue
            s = max(1, min(s, budget))
            while budget % s:
                s -= 1
            sizes[axis] = s
            budget //= s
        if infer:
            sizes[infer[0]] = budget
        elif budget > 1:
            sizes["dp"] *= budget
        return MeshConfig(**sizes)


# Named mesh presets for ``train.ScalingConfig(mesh=...)``.  Fixed axes
# (e.g. tp=2) are degraded by ``clamp_to`` on smaller hardware, so every
# preset forms a valid mesh on any device count (guard-tested on
# 1/2/4/8 devices in tests/test_sharded_train.py).
MESH_PRESETS: Dict[str, MeshConfig] = {
    # pure data parallelism: params replicated, batch sharded
    "dp": MeshConfig(dp=-1),
    # ZeRO-style sharded data parallelism: params/opt-state sharded over
    # every chip, all-gathered for compute
    "fsdp": MeshConfig(dp=1, fsdp=-1),
    # FSDP across hosts/outer axis + Megatron tensor parallelism on the
    # 2 ICI-adjacent chips
    "fsdp_tp": MeshConfig(dp=1, fsdp=-1, tp=2),
}


def resolve_mesh_config(
    mesh: Union[str, MeshConfig, None]) -> Optional[MeshConfig]:
    """Normalize a ``ScalingConfig.mesh`` value: a preset name from
    :data:`MESH_PRESETS`, a :class:`MeshConfig`, or None (caller's
    default)."""
    if mesh is None or isinstance(mesh, MeshConfig):
        return mesh
    if isinstance(mesh, str):
        try:
            return MESH_PRESETS[mesh]
        except KeyError:
            raise ValueError(
                f"unknown mesh preset {mesh!r}; valid presets: "
                f"{sorted(MESH_PRESETS)} (or pass a MeshConfig)") from None
    raise TypeError(
        f"mesh must be a preset name, MeshConfig, or None; got "
        f"{type(mesh).__name__}")


def mesh_shape_for(n_devices: int, config: Optional[MeshConfig] = None):
    return (config or MeshConfig()).resolve(n_devices)


def create_mesh(
    config: Optional[MeshConfig] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
    axis_names: Tuple[str, ...] = MESH_AXES,
) -> Mesh:
    """Build a Mesh over ``devices`` (default: all visible devices).

    Over the full device set, ``mesh_utils`` lays the logical mesh out
    on the physical ICI torus (contiguous inner axes) — and raises for a
    shape the topology cannot carry, rather than handing back an
    arbitrary order.  An explicit subset is taken in the order given.
    """
    if devices is None:
        devices = jax.devices()
    shape = mesh_shape_for(len(devices), config)
    if list(devices) == list(jax.devices()):
        dev_array = mesh_utils.create_device_mesh(shape)
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axis_names)


def create_hybrid_mesh(
    *,
    ici_config: Optional[MeshConfig] = None,
    num_slices: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Mesh spanning multiple pod slices: ``dp`` over DCN, rest over ICI.

    For a multi-slice (multi-host DCN-connected) topology the outermost axis
    must map to the slice boundary so only DP gradient reductions cross DCN.
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if n % num_slices != 0:
        raise ValueError(f"{n} devices not divisible into {num_slices} slices")
    per_slice = n // num_slices
    cfg = ici_config or MeshConfig(dp=1, fsdp=-1)
    ici_shape = cfg.resolve(per_slice)
    if cfg.dp != 1 and num_slices > 1:
        raise ValueError("dp must be 1 in ici_config for hybrid meshes")
    # create_hybrid_device_mesh takes same-rank ICI and DCN shapes; the
    # result shape is their elementwise product, so dp == num_slices lands
    # on the DCN boundary and fsdp/pp/tp/sp stay within a slice's ICI torus.
    dcn_shape = (num_slices,) + (1,) * (len(MESH_AXES) - 1)
    if hasattr(devices[0], "slice_index"):
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices
        )
    else:
        # devices that carry no slice identity (the virtual CPU devices
        # of the tests): consecutive runs of the list stand in for slices
        dev_array = np.asarray(devices).reshape(
            (num_slices,) + ici_shape[1:]
        )
    return Mesh(dev_array, MESH_AXES)


def local_mesh(n: int = 1) -> Mesh:
    """A trivial mesh over the first n local devices (single-host dev/test)."""
    return create_mesh(MeshConfig(dp=-1), devices=jax.devices()[:n])
