"""ray_tpu.serve: online model serving (reference: ``python/ray/serve/``).

``serve.run(app)`` deploys a bound deployment graph behind the singleton
controller; ``DeploymentHandle.remote()`` routes via pow-2 choices; an
optional HTTP proxy exposes route prefixes (``serve.start(http_options=...)``).
"""

from __future__ import annotations

import dataclasses
import uuid
from typing import Any, Dict, Optional

import ray_tpu
from ray_tpu.serve import context
from ray_tpu.serve.context import RequestContext, request_scope
from ray_tpu.serve.deployment import (
    Application,
    AutoscalingConfig,
    Deployment,
    DeploymentConfig,
    deployment,
)
from ray_tpu.serve.context import ReplicaContext, get_replica_context
from ray_tpu.serve.multiplex import get_multiplexed_model_id, multiplexed
from ray_tpu.serve.replica import batch
from ray_tpu.serve.router import (
    DeploymentHandle,
    DeploymentResponse,
    StreamBatch,
    TwoStageHandle,
)

__all__ = [
    "Application", "AutoscalingConfig", "Deployment", "DeploymentConfig",
    "DeploymentHandle", "DeploymentResponse", "ReplicaContext",
    "RequestContext", "StreamBatch", "TwoStageHandle", "batch",
    "context", "delete", "deployment",
    "get_app_handle", "get_deployment_handle", "get_multiplexed_model_id",
    "get_replica_context",
    "grpc_proxy_port", "multiplexed", "request_scope", "run",
    "shutdown", "start",
    "status",
]

_proxy = None
_grpc_proxy = None


def start(http_options: Optional[Dict[str, Any]] = None,
          grpc_options: Optional[Dict[str, Any]] = None):
    """Start serve (controller + optional HTTP and/or gRPC proxies).

    Reference runs both proxy flavors per node (``proxy.py:750`` HTTP,
    ``:530`` gRPC); here each is opt-in via its options dict.
    """
    from ray_tpu.serve.controller import get_controller

    get_controller()
    global _proxy, _grpc_proxy
    if http_options and _proxy is None:
        from ray_tpu.serve.proxy import ProxyActor

        host = http_options.get("host", "127.0.0.1")
        port = http_options.get("port", 8000)
        _proxy = ProxyActor.remote(
            host, port, http_options.get("request_timeout_s", 120.0),
            http_options.get("max_concurrent_requests", 256))
        ray_tpu.get(_proxy.ready.remote(), timeout=60)
    if grpc_options and _grpc_proxy is None:
        from ray_tpu.serve.grpc_proxy import GrpcProxyActor

        host = grpc_options.get("host", "127.0.0.1")
        port = grpc_options.get("port", 9000)
        _grpc_proxy = GrpcProxyActor.remote(host, port)
        ray_tpu.get(_grpc_proxy.ready.remote(), timeout=60)
    return _proxy


def grpc_proxy_port() -> int:
    """Bound port of the gRPC proxy (resolves port=0 ephemeral binds)."""
    if _grpc_proxy is None:
        raise RuntimeError("gRPC proxy not started; pass grpc_options to "
                           "serve.start()")
    return ray_tpu.get(_grpc_proxy.ready.remote(), timeout=30)


def run(target: Application | Deployment, *, name: str = "default",
        route_prefix: Optional[str] = "/", _blocking: bool = False
        ) -> DeploymentHandle:
    """Deploy an application graph; returns the ingress handle
    (reference ``serve.run`` at ``python/ray/serve/api.py:660``)."""
    from ray_tpu._private import serialization
    from ray_tpu.serve.controller import get_controller

    if isinstance(target, Deployment):
        target = target.bind()
    if not isinstance(target, Application):
        raise TypeError("serve.run expects a Deployment or bound Application")

    controller = get_controller()
    apps = target._collect()  # dependencies first
    handles: Dict[int, DeploymentHandle] = {}
    for app in apps:
        dep = app.deployment
        # replace Application args with handles to the deployed dependency
        init_args = tuple(handles[id(a)] if isinstance(a, Application) else a
                          for a in app.args)
        init_kwargs = {k: handles[id(v)] if isinstance(v, Application) else v
                       for k, v in app.kwargs.items()}
        is_ingress = app is apps[-1]
        cfg = dep.config
        config_dict = {
            "num_replicas": cfg.num_replicas,
            "max_ongoing_requests": cfg.max_ongoing_requests,
            "max_queued_requests": cfg.max_queued_requests,
            "autoscaling_config": (
                None if cfg.autoscaling_config is None
                else dataclasses.asdict(cfg.autoscaling_config)),
            "user_config": cfg.user_config,
            "ray_actor_options": cfg.ray_actor_options,
        }
        prefix = (dep.route_prefix or route_prefix) if is_ingress else None
        ray_tpu.get(controller.deploy.remote(
            dep.name, serialization.dumps(dep._target), init_args,
            init_kwargs, config_dict, prefix,
            name if is_ingress else None), timeout=120)
        handles[id(app)] = DeploymentHandle(dep.name)
    return handles[id(apps[-1])]


def get_deployment_handle(deployment_name: str, app_name: str = "default"
                          ) -> DeploymentHandle:
    return DeploymentHandle(deployment_name)


def get_app_handle(name: str = "default") -> DeploymentHandle:
    from ray_tpu.serve.controller import get_controller

    ingress = ray_tpu.get(get_controller().get_app_ingress.remote(name),
                          timeout=30)
    if ingress is None:
        raise RuntimeError(f"no application named {name!r}")
    return DeploymentHandle(ingress)


def status() -> Dict[str, Any]:
    from ray_tpu.serve.controller import get_controller

    return ray_tpu.get(get_controller().list_deployments.remote(),
                       timeout=30)


def delete(deployment_name: str):
    from ray_tpu.serve.controller import get_controller

    ray_tpu.get(get_controller().delete_deployment.remote(deployment_name),
                timeout=60)


def shutdown():
    global _proxy, _grpc_proxy
    from ray_tpu.actor import get_actor_or_none
    from ray_tpu.serve.controller import CONTROLLER_NAME

    controller = get_actor_or_none(CONTROLLER_NAME)
    if controller is not None:
        try:
            ray_tpu.get(controller.shutdown.remote(), timeout=60)
            ray_tpu.kill(controller)
        except Exception:
            pass
    for proxy in (_proxy, _grpc_proxy):
        if proxy is not None:
            try:
                ray_tpu.kill(proxy)
            except Exception:
                pass
    _proxy = None
    _grpc_proxy = None
    # drop cached per-deployment routers: they hold handles to the dead
    # controller/replicas and would poison the next serve session (stop
    # settles each router's completion-watcher thread first)
    with DeploymentHandle._routers_lock:
        for router in DeploymentHandle._routers.values():
            router.stop()
        DeploymentHandle._routers.clear()
