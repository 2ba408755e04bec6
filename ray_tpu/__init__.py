"""ray_tpu: a TPU-native distributed compute framework.

A brand-new framework with the capabilities of Ray (reference snapshot at
/root/reference, see SURVEY.md): tasks, actors, objects with ownership,
placement groups, collectives, compiled graphs, and the AI-library tier
(train/data/tune/serve/rl) — architected TPU-first: the accelerator plane is
XLA collectives over ICI/DCN via jax/pjit/shard_map/Pallas instead of
NCCL/CUDA.

Public core API parity target: ``python/ray/_private/worker.py`` (init :1286,
get :2716, put :2852, wait :2917, remote :3405).
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict, List, Optional, Sequence, Union

from ray_tpu import exceptions  # noqa: F401
from ray_tpu._private.ids import JobID
from ray_tpu._private.object_ref import ObjectRef  # noqa: F401
from ray_tpu.actor import ActorHandle, get_actor  # noqa: F401
from ray_tpu.remote_function import remote_decorator as remote  # noqa: F401
from ray_tpu.runtime_context import get_runtime_context  # noqa: F401

__version__ = "0.1.0"

logger = logging.getLogger(__name__)

_init_lock = threading.Lock()
_node_services = None


def init(
    address: Optional[str] = None,
    *,
    num_cpus: Optional[float] = None,
    num_tpus: Optional[float] = None,
    resources: Optional[Dict[str, float]] = None,
    labels: Optional[Dict[str, str]] = None,
    namespace: str = "",
    ignore_reinit_error: bool = False,
    log_to_driver: bool = True,
    _system_config: Optional[Dict[str, Any]] = None,
) -> "RuntimeInfo":
    """Start (or connect to) a cluster and connect this process as a driver.

    Reference: ``ray.init`` (``python/ray/_private/worker.py:1286``) →
    ``Node.start_ray_processes`` (``node.py:1467``).
    """
    global _node_services
    from ray_tpu._private import tracing
    from ray_tpu._private import worker as worker_mod
    from ray_tpu._private.node import NodeServices, default_resources
    from ray_tpu._private.worker import CoreWorker, WorkerMode

    # one ``init`` span with a child for each phase this thread blocks in
    # (docs/observability.md).  There is no GCS yet to publish to: they
    # stay in the local buffer, which outlives ``shutdown()``.
    with _init_lock, tracing.span("init",
                                  attrs={"address": address or "local"}):
        if worker_mod.global_worker is not None:
            if ignore_reinit_error:
                return RuntimeInfo(_node_services.gcs_addr if _node_services else address or "")
            raise RuntimeError("ray_tpu.init() called twice; use ignore_reinit_error=True")

        if address is not None and address.startswith("ray_tpu://"):
            # remote interactive driver: proxy all ops through the cluster's
            # client server (reference Ray Client, python/ray/util/client/)
            from ray_tpu.util.client import connect as _client_connect

            if num_cpus or num_tpus or resources or labels or _system_config:
                raise ValueError(
                    "resource/config arguments are ignored with a "
                    "ray_tpu:// address — the cluster is already running; "
                    "pass them where the cluster is started")
            worker_mod.global_worker = _client_connect(
                address, namespace=namespace or None)
            _node_services = None
            return RuntimeInfo(address)
        if address is None or address == "local":
            base = default_resources(num_cpus=num_cpus, num_tpus=num_tpus)
            if resources:
                base.update({k: float(v) for k, v in resources.items()})
            _node_services = NodeServices()
            # the head process (GCS + raylet + zygote) from its spawn to
            # its address file; its own parts are this span's children
            with tracing.span("init.start_head"):
                gcs_addr = _node_services.start_head(
                    base, labels, _system_config)
            session_dir = _node_services.session_dir
        else:
            gcs_addr = address
            _node_services = None
            session_dir = None

        # discover the local raylet through the GCS node table
        from ray_tpu._private.rpc import RpcClient, run_sync

        async def _discover():
            c = RpcClient(gcs_addr)
            try:
                from ray_tpu._private.rpc import mint_mid

                with tracing.span("init.gcs"):  # the GCS answers
                    job_id = await c.call("next_job_id", _mid=mint_mid())
                # the raylet stands in the node table with what it detected
                with tracing.span("init.raylet"):
                    nodes = await c.call("get_all_nodes")
                return nodes, job_id
            finally:
                await c.close()

        nodes, job_no = run_sync(_discover())
        if not nodes:
            raise RuntimeError("no nodes registered in the cluster")
        head = next((n for n in nodes if n.get("node_name") == "head"), nodes[0])
        raylet_addr = head["addr"]
        if session_dir is None:
            # join an existing cluster: learn session dir from the raylet
            async def _info():
                c = RpcClient(raylet_addr)
                try:
                    return await c.call("get_node_info")
                finally:
                    await c.close()

            info = run_sync(_info())
            session_dir = info["session_dir"]

        with tracing.span("init.connect"):  # the driver's core worker
            core = CoreWorker(
                mode=WorkerMode.DRIVER,
                session_dir=session_dir,
                gcs_addr=gcs_addr,
                raylet_addr=raylet_addr,
                node_id=head["node_id"],
                job_id=JobID.from_int(job_no),
            )
            core.start()
            core.namespace = namespace or ""
            worker_mod.global_worker = core
            core.run_coro(core.gcs.call(
                "add_job", job_id=job_no,
                info={"driver_pid": _pid(), "driver_addr": core.serve_addr}))
        if log_to_driver:
            # worker prints stream back to this process's stdout
            core.start_log_streaming()
        return RuntimeInfo(gcs_addr)


def _pid() -> int:
    import os

    return os.getpid()


class RuntimeInfo:
    def __init__(self, address: str):
        self.address_info = {"address": address, "gcs_address": address}

    def __getitem__(self, k):
        return self.address_info[k]


def is_initialized() -> bool:
    from ray_tpu._private import worker as worker_mod

    return worker_mod.global_worker is not None


def shutdown():
    """Disconnect the driver and stop the cluster if this driver started it."""
    global _node_services
    from ray_tpu._private import tracing
    from ray_tpu._private import worker as worker_mod

    with _init_lock:
        if worker_mod.global_worker is None and _node_services is None:
            return
        # closes after the last publish: like ``init`` it stays in the
        # local buffer, for whoever reads it in this process afterwards
        with tracing.span("shutdown"):
            if worker_mod.global_worker is not None:
                try:
                    worker_mod.global_worker.shutdown()
                except Exception:
                    pass
                worker_mod.global_worker = None
            if _node_services is not None:
                _node_services.stop()
                _node_services = None


def get(refs: Union[ObjectRef, Sequence[ObjectRef]], *, timeout: Optional[float] = None):
    """Fetch object values (reference ``worker.py:2716``)."""
    from ray_tpu._private.worker import get_global_worker

    return get_global_worker().get(refs, timeout=timeout)


def put(value: Any) -> ObjectRef:
    """Store a value in the object store (reference ``worker.py:2852``)."""
    from ray_tpu._private.worker import get_global_worker

    return get_global_worker().put(value)


def wait(refs: List[ObjectRef], *, num_returns: int = 1,
         timeout: Optional[float] = None, fetch_local: bool = True):
    """Wait for objects to become ready (reference ``worker.py:2917``)."""
    from ray_tpu._private.worker import get_global_worker

    if isinstance(refs, ObjectRef):
        raise TypeError("wait() expects a list of ObjectRefs")
    return get_global_worker().wait(refs, num_returns=num_returns, timeout=timeout,
                                    fetch_local=fetch_local)


def kill(actor: ActorHandle, *, no_restart: bool = True):
    """Forcefully kill an actor (reference ``python/ray/_private/worker.py`` kill)."""
    from ray_tpu._private.worker import get_global_worker

    worker = get_global_worker()
    worker.run_coro(
        worker.gcs.call("kill_actor", actor_id=actor._ray_actor_id.binary(),
                        no_restart=no_restart)
    )


def cancel(ref: ObjectRef, *, force: bool = False, recursive: bool = True):
    """Cancel the task producing ``ref`` (reference
    ``python/ray/_private/worker.py:3128``).

    Queued tasks are failed with ``TaskCancelledError`` without running.
    Running tasks get a cancellation raised at their next bytecode boundary
    (``force=False``) or their worker process killed (``force=True``).
    ``recursive=True`` also cancels tasks the target submitted.  Cancelling
    a finished task is a no-op; ``get`` on a cancelled ref raises
    ``TaskCancelledError``.
    """
    from ray_tpu._private.worker import get_global_worker

    get_global_worker().cancel_task(ref, force=force, recursive=recursive)


def nodes() -> List[Dict[str, Any]]:
    from ray_tpu._private.worker import get_global_worker

    worker = get_global_worker()
    out = worker.run_coro(worker.gcs.call("get_all_nodes"))
    for n in out:
        n["NodeID"] = n["node_id"]
        n["Alive"] = n["alive"]
        n["Resources"] = n["total"]
        # drain state machine: ALIVE -> DRAINING -> DEAD
        n.setdefault("state", "ALIVE" if n.get("alive") else "DEAD")
    return out


def cluster_resources() -> Dict[str, float]:
    from ray_tpu._private.worker import get_global_worker

    worker = get_global_worker()
    return worker.run_coro(worker.gcs.call("cluster_resources"))


def available_resources() -> Dict[str, float]:
    from ray_tpu._private.worker import get_global_worker

    worker = get_global_worker()
    return worker.run_coro(worker.gcs.call("available_resources"))


def timeline(filename: Optional[str] = None):
    from ray_tpu.util.state import timeline as _timeline

    return _timeline(filename)


def method(**kwargs):
    """Decorator for actor methods carrying default options (reference
    ``ray.method``)."""

    def _wrap(fn):
        fn.__ray_tpu_method_options__ = kwargs
        return fn

    return _wrap


def dashboard_url() -> Optional[str]:
    """HTTP address of this cluster's dashboard (None if disabled).

    No polling needed: the head writes dashboard_address BEFORE the
    gcs_address marker that init() waits on, so by the time a driver is
    connected the file either exists or the dashboard is off/failed.
    """
    import os

    if os.environ.get("RAY_TPU_DASHBOARD", "1") == "0":
        return None
    if _node_services is None or not _node_services.session_dir:
        return None
    path = os.path.join(_node_services.session_dir, "dashboard_address")
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return None


from ray_tpu import internal  # noqa: F401,E402  (owner-driven free, stats)

__all__ = [
    "ObjectRef", "ActorHandle", "init", "shutdown", "is_initialized", "get", "put",
    "wait", "remote", "kill", "cancel", "get_actor", "nodes", "cluster_resources",
    "available_resources", "dashboard_url", "get_runtime_context", "method",
    "exceptions", "internal", "timeline", "__version__",
]
