"""Driver-side session bootstrap: start/connect/stop the cluster processes.

Equivalent of the reference's ``python/ray/_private/node.py`` +
``services.py`` (``start_ray_processes`` at ``node.py:1467``,
``start_gcs_server`` at ``:1203``, ``start_raylet`` at ``:1237``): spawn the
head process (GCS + head raylet), wait for readiness, connect the driver's
CoreWorker, and tear everything down on shutdown.
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import shutil
import subprocess
import sys
import time
from typing import Any, Dict, Optional

from ray_tpu._private import tracing

logger = logging.getLogger(__name__)

_SESSION_ROOT = "/tmp/ray_tpu"


def default_resources(num_cpus: Optional[float] = None,
                      num_tpus: Optional[float] = None) -> Dict[str, float]:
    """Auto-detected node resources.  Chips are counted from the device
    files alone: the driver must never initialize a JAX backend to find
    them, or it holds the chips its own workers are about to need."""
    if num_cpus is None:
        num_cpus = float(max(os.cpu_count() or 1, 4))
    resources = {"CPU": float(num_cpus)}
    if num_tpus is None:
        from ray_tpu._private.accelerators import TPUAcceleratorManager

        num_tpus = float(
            TPUAcceleratorManager.get_current_node_num_accelerators())
    if num_tpus:
        resources["TPU"] = float(num_tpus)
    resources["memory"] = float(_detect_memory_bytes())
    return resources


def ensure_compile_cache_env() -> str:
    """Decide where XLA's persistent compile cache lives, for this process
    and everything it starts (head -> raylet -> zygote -> workers inherit
    the environment).  A path set from outside wins untouched; otherwise
    it is ONE fixed directory in the checkout — the path is part of the
    cache key, so nothing derives it from a pid, a clock or a session
    directory.  JAX reads the variable at import: call this before the
    first ``import jax`` of a process that compiles.

    The cache's key takes the programs' metadata in (JAX's default leaves
    it out): a program's name scopes (``tracing.scope``) are metadata, and
    an executable loaded under a key that ignores them carries whatever
    scopes the process that compiled it had.  Seen on the v5e (PERF.md
    section 6, PR 37): the dense prefill programs, the same HLO as the
    parent commit's but for their scopes, came out of a warm cache without
    a single scope in the profiler's trace."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY",
                          "true")
    return os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache"))


def _detect_memory_bytes() -> int:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal"):
                    return int(line.split()[1]) * 1024 // 2
    except Exception:
        pass
    return 4 * 1024**3


class NodeServices:
    """Owns the head subprocess + session directory for one driver."""

    def __init__(self):
        self.session_dir: str = ""
        self.gcs_addr: str = ""
        self.head_proc: Optional[subprocess.Popen] = None
        self._owns_cluster = False

    def start_head(
        self,
        resources: Dict[str, float],
        labels: Optional[Dict[str, str]] = None,
        system_config: Optional[Dict[str, Any]] = None,
    ) -> str:
        ts = time.strftime("%Y-%m-%d_%H-%M-%S")
        self.session_dir = os.path.join(_SESSION_ROOT, f"session_{ts}_{os.getpid()}_{time.time_ns() % 10**6}")
        os.makedirs(os.path.join(self.session_dir, "logs"), exist_ok=True)
        os.makedirs(os.path.join(self.session_dir, "sockets"), exist_ok=True)
        ensure_compile_cache_env()
        env = dict(os.environ)
        if system_config:
            for k, v in system_config.items():
                env[f"RAY_TPU_{k.upper()}"] = str(v)
        log = open(os.path.join(self.session_dir, "logs", "head.log"), "ab")
        spawned = time.time()
        self.head_proc = subprocess.Popen(
            [
                sys.executable, "-m", "ray_tpu._private.head_proc",
                "--session-dir", self.session_dir,
                "--resources", json.dumps(resources),
                "--labels", json.dumps(labels or {}),
            ],
            env=env,
            stdout=log,
            stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        self._owns_cluster = True
        addr_file = os.path.join(self.session_dir, "gcs_address")
        deadline = time.time() + 60
        while time.time() < deadline:
            if os.path.exists(addr_file):
                with open(addr_file) as f:
                    self.gcs_addr = f.read().strip()
                atexit.register(self.stop)
                self._record_head_parts(spawned)
                return self.gcs_addr
            if self.head_proc.poll() is not None:
                log_path = os.path.join(self.session_dir, "logs", "head.log")
                tail = ""
                try:
                    with open(log_path) as f:
                        tail = f.read()[-4000:]
                except Exception:
                    pass
                raise RuntimeError(
                    f"head process exited rc={self.head_proc.returncode}\n{tail}")
            time.sleep(0.05)
        raise TimeoutError("timed out waiting for head to start")

    def _record_head_parts(self, spawned: float) -> None:
        """The head's own stamps (``head_proc`` leaves them beside its
        address file) as ``init.start_head.<part>`` spans, back to back
        from the spawn, under the span that waited for the head."""
        if not tracing.is_enabled():
            return
        try:
            with open(os.path.join(self.session_dir,
                                   "head_startup.json")) as f:
                stamps = json.load(f)
        except (OSError, ValueError):
            return
        parent = tracing.current_or_root()
        for part, at in stamps:
            tracing.record_span(f"init.start_head.{part}", spawned, at,
                                parent.child(), kind="startup")
            spawned = at

    def stop(self):
        if not self._owns_cluster:
            return
        self._owns_cluster = False
        # graceful cluster shutdown via GCS, then hard-kill
        try:
            import asyncio

            from ray_tpu._private.rpc import RpcClient

            async def _down():
                c = RpcClient(self.gcs_addr)
                try:
                    await asyncio.wait_for(c.call("shutdown_cluster"), 3.0)
                finally:
                    await c.close()

            loop = asyncio.new_event_loop()
            try:
                loop.run_until_complete(_down())
            finally:
                for t in asyncio.all_tasks(loop):
                    t.cancel()
                loop.run_until_complete(asyncio.sleep(0))
                loop.close()
        except Exception:
            pass
        if self.head_proc is not None:
            with tracing.span("shutdown.wait", attrs={
                    "what": "head", "pid": self.head_proc.pid}):
                try:
                    self.head_proc.wait(timeout=3)
                except Exception:
                    try:
                        self.head_proc.kill()
                    except Exception:
                        pass
            self.head_proc = None
        self._cleanup_shm()

    def _cleanup_shm(self):
        # Always unlink this session's arena (its name is session-keyed).
        try:
            from ray_tpu._private.object_store import arena_name_for

            os.unlink("/dev/shm" + arena_name_for(self.session_dir))
        except OSError:
            pass
        # Per-object segments are not session-keyed, so sweep them ONLY when
        # no other live session exists on this host — a concurrent cluster's
        # objects and channels must not be unlinked out from under it.  A
        # session dir counts as live only if its creator pid (embedded in
        # the name: session_<ts>_<pid>_<ns>) is still running; crashed
        # sessions are reaped here so they can't block cleanup forever.
        others = []
        try:
            for d in os.listdir(_SESSION_ROOT):
                path = os.path.join(_SESSION_ROOT, d)
                if not d.startswith("session_") or path == self.session_dir:
                    continue
                # name: session_<strftime(%Y-%m-%d_%H-%M-%S)>_<pid>_<ns>
                # → pid is the second-to-last token.  Unparseable names are
                # treated as LIVE (never sweep shm under an unknown session).
                alive = True
                try:
                    pid = int(d.split("_")[-2])
                    os.kill(pid, 0)
                except (IndexError, ValueError, PermissionError):
                    pass
                except ProcessLookupError:
                    alive = False
                if alive:
                    others.append(d)
                else:
                    shutil.rmtree(path, ignore_errors=True)
        except OSError:
            pass
        if not others:
            try:
                for name in os.listdir("/dev/shm"):
                    if name.startswith("rtpu_"):
                        try:
                            os.unlink(os.path.join("/dev/shm", name))
                        except OSError:
                            pass
            except OSError:
                pass
        if self.session_dir and os.path.isdir(self.session_dir):
            shutil.rmtree(self.session_dir, ignore_errors=True)
