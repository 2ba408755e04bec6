"""Accelerator detection & isolation: TPU-first.

Reference: ``python/ray/_private/accelerators/`` — ``AcceleratorManager``
ABC (``accelerator.py``) and ``tpu.py:109 TPUAcceleratorManager`` (chip
detection via /dev/accel* and /dev/vfio at ``tpu.py:134-154``, pod-type →
``TPU-v4`` accelerator_type labels ``:352-361``, the ``TPU-{type}-head``
resource for slice gang-scheduling ``:326-372``, and per-worker chip
isolation via ``TPU_VISIBLE_CHIPS``).

One process per chip.  libtpu gives a chip to the first process that
initializes a backend on it, so the ``TPU`` resource is also the device
binding:

- a worker's JAX platform follows its lease, not the environment.  Every
  worker starts pinned to ``cpu`` (:func:`pin_jax_platform`); a worker
  granted ``TPU: k`` is re-pinned to ``tpu`` with exactly the k chips the
  raylet took off its free list visible (:func:`bind_tpu_chips`), so a
  busy or missing chip raises instead of falling back to the host;
  a zero-TPU worker on a TPU node that touches JAX gets the CPU;
- the binding happens before the grant reaches the lease's owner and
  only in a process with no live backend (a backend cannot be re-pointed)
  — the raylet skips such workers for TPU leases;
- a worker bound to chips dies with its lease, and the chips return to
  the free list once it is gone.
"""

from __future__ import annotations

import glob
import os
import sys
import time
from typing import Dict, List, Optional

from ray_tpu._private import tracing

# when this worker process began (``worker_proc.main`` stamps it), and
# (chips, when) a lease bound it to chips: ``worker.chip_acquire``'s stamps
worker_started_at: Optional[float] = None
_bound: Optional[tuple] = None


class TPUAcceleratorManager:
    """Detects local TPU chips and slice topology from the VM metadata env."""

    # gke/gce metadata env vars (reference tpu.py)
    ENV_TYPE = "TPU_ACCELERATOR_TYPE"      # e.g. "v5litepod-16"
    ENV_WORKER_ID = "TPU_WORKER_ID"
    ENV_NAME = "TPU_NAME"
    ENV_WORKER_HOSTNAMES = "TPU_WORKER_HOSTNAMES"
    ENV_VISIBLE = "TPU_VISIBLE_CHIPS"

    @staticmethod
    def get_current_node_num_accelerators() -> int:
        """Count chips via device files (works without jax init)."""
        try:
            accel = glob.glob("/dev/accel*")
            if accel:
                return len(accel)
            vfio = glob.glob("/dev/vfio/[0-9]*")
            if vfio:
                return len(vfio)
        except OSError:
            pass
        return 0

    @staticmethod
    def get_current_node_accelerator_type() -> Optional[str]:
        """'TPU-v5litepod-16' style label from the metadata env."""
        t = os.environ.get(TPUAcceleratorManager.ENV_TYPE)
        if not t:
            return None
        gen = t.split("-")[0]  # v4, v5litepod, v5p, v6e...
        return f"TPU-{gen}"

    @staticmethod
    def get_current_pod_name() -> Optional[str]:
        return os.environ.get(TPUAcceleratorManager.ENV_NAME) or None

    @staticmethod
    def get_current_pod_worker_count() -> int:
        hosts = os.environ.get(TPUAcceleratorManager.ENV_WORKER_HOSTNAMES, "")
        return len([h for h in hosts.split(",") if h]) or 1

    @staticmethod
    def get_current_pod_worker_id() -> int:
        try:
            return int(os.environ.get(TPUAcceleratorManager.ENV_WORKER_ID, 0))
        except ValueError:
            return 0

    @staticmethod
    def slice_resources() -> Dict[str, float]:
        """Extra resources for slice-aware gang scheduling.

        Worker 0 of a slice advertises ``TPU-{type}-head: 1`` (the
        reference's trick, ``tpu.py:326-372``) so a trainer can reserve one
        bundle per slice; every worker advertises its slice name as a label
        resource for affinity.
        """
        out: Dict[str, float] = {}
        t = os.environ.get(TPUAcceleratorManager.ENV_TYPE)
        pod = TPUAcceleratorManager.get_current_pod_name()
        if t and pod and TPUAcceleratorManager.get_current_pod_worker_id() == 0:
            out[f"TPU-{t}-head"] = 1.0
        return out

    @staticmethod
    def slice_topology_labels() -> Dict[str, str]:
        """Node labels advertising pod-slice topology for the scheduler's
        slice table (GCS) and ``STRICT_PACK_SLICE`` packing.

        - ``tpu-slice-name``: the slice this host belongs to (TPU_NAME);
        - ``tpu-pod-type``: e.g. ``v5litepod-16``;
        - ``tpu-worker-index``: this host's position along the slice's
          torus — consecutive indexes are ICI neighbors, which is what
          the adjacency-preferring pack order keys on;
        - ``tpu-chip-coords``: this host's first-chip coordinate hint
          (linear offset = worker_index * chips_per_host) so the GCS
          slice table can render physical adjacency;
        - ``tpu-ici-neighbors``: comma-joined worker indexes of this
          host's ICI-adjacent peers (ring hint: index ± 1 mod hosts).
        """
        out: Dict[str, str] = {}
        pod = TPUAcceleratorManager.get_current_pod_name()
        t = os.environ.get(TPUAcceleratorManager.ENV_TYPE)
        if not pod or not t:
            return out
        idx = TPUAcceleratorManager.get_current_pod_worker_id()
        hosts = TPUAcceleratorManager.get_current_pod_worker_count()
        chips = TPUAcceleratorManager.get_current_node_num_accelerators()
        out["tpu-slice-name"] = pod
        out["tpu-pod-type"] = t
        out["tpu-worker-index"] = str(idx)
        out.update(topology_hint_labels(idx, hosts, chips))
        return out

    @staticmethod
    def set_visible_chips(env: Dict[str, str], chip_ids: List[int]) -> None:
        """Restrict a process to ``chip_ids`` of this host's chips
        (reference: CUDA_VISIBLE_DEVICES analog for TPU).  The process
        forms its own single-process topology over them — 1 or 2 chips
        in a row, 4 as the host's 2x2."""
        n = len(chip_ids)
        env[TPUAcceleratorManager.ENV_VISIBLE] = ",".join(
            str(i) for i in chip_ids)
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = \
            "2,2,1" if n == 4 else f"1,{n},1"
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"


def jax_backend_initialized() -> bool:
    """True only when this process ALREADY brought a jax backend up.
    Passive by construction: forcing backend init from a probe would
    claim a chip for a process that holds no TPU lease, and break actors
    that need ``jax.distributed.initialize()`` before any computation."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)


def pin_jax_platform(platform: str) -> None:
    """Pin this process's JAX platform list to exactly ``platform``: no
    fallback to another backend when it cannot initialize.  The env var
    covers a later ``import jax`` (and child processes); the config
    update covers a zygote-forked worker, where jax was imported — and
    read the variable — before the fork."""
    os.environ["JAX_PLATFORMS"] = platform
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_platforms", platform)


def bind_tpu_chips(chip_ids: List[int], node_chips: int) -> bool:
    """Point this process at ``chip_ids`` of the node's ``node_chips``
    and pin JAX to ``tpu``.  A lease for the whole host leaves libtpu's
    own topology discovery alone (the host may be one of a multi-host
    slice).  False when a backend is already live here: it can no
    longer be bound."""
    global _bound
    if jax_backend_initialized():
        return False
    if len(chip_ids) < node_chips:
        TPUAcceleratorManager.set_visible_chips(os.environ, chip_ids)
    pin_jax_platform("tpu")
    _bound = (len(chip_ids), time.time())
    return True


def record_chip_acquire() -> None:
    """Record ``worker.chip_acquire`` [``chips``, ``bound_s``], once: from
    this worker's start to its first backend initialisation returning (of
    it ``bound_s`` before the lease bound it to its chips).  Called by the
    code that first touches the backend, right after its first
    ``jax.devices()`` (``LLMEngine.__init__``, ``train.session.get_mesh``):
    forcing the backend from :func:`bind_tpu_chips` would break
    ``jax.distributed.initialize()``.  Nothing in a process that was not
    bound to chips."""
    global _bound
    if _bound is None:
        return
    (chips, bound), _bound = _bound, None
    start = worker_started_at or bound
    tracing.watch_builds()  # jax is imported by now, whoever imported it
    if tracing.is_enabled():
        tracing.record_span(
            "worker.chip_acquire", start, time.time(),
            tracing.current_or_root().child(), kind="startup",
            attrs={"chips": chips, "bound_s": round(bound - start, 3)})


def detect_resources() -> Dict[str, float]:
    """Auto-detected accelerator resources for this node."""
    out: Dict[str, float] = {}
    n = TPUAcceleratorManager.get_current_node_num_accelerators()
    if n:
        out["TPU"] = float(n)
        at = TPUAcceleratorManager.get_current_node_accelerator_type()
        if at:
            out[at] = float(n)
        out.update(TPUAcceleratorManager.slice_resources())
    return out


def topology_hint_labels(worker_index: int, num_hosts: int,
                         chips_per_host: int) -> Dict[str, str]:
    """Adjacency-hint labels for one slice host — THE formula, shared by
    metadata detection (above) and the slice provider, so emulated and
    real hosts group/order identically: chip coords as a linear offset
    along the worker chain, ICI neighbors as the ring ``index ± 1``."""
    out = {"tpu-chip-coords": str(worker_index * max(chips_per_host, 1))}
    if num_hosts > 1:
        neighbors = sorted({(worker_index - 1) % num_hosts,
                            (worker_index + 1) % num_hosts}
                           - {worker_index})
        out["tpu-ici-neighbors"] = ",".join(str(n) for n in neighbors)
    return out


def detect_labels() -> Dict[str, str]:
    """Auto-detected topology labels for this node (empty off-TPU)."""
    return TPUAcceleratorManager.slice_topology_labels()
