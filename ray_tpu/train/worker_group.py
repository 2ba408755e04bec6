"""Train worker group: N actors gang-scheduled on a placement group.

Parity: Train-v2 worker group
(``python/ray/train/v2/_internal/execution/worker_group/worker_group.py``)
and v1 ``WorkerGroup`` (``python/ray/train/_internal/worker_group.py:102``).
The controller polls workers for status instead of blocking on futures —
that is what makes failure handling and elastic resize possible between
control-loop steps.
"""

from __future__ import annotations

import dataclasses
import threading
import traceback
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.train.checkpoint import Checkpoint


@dataclasses.dataclass
class WorkerStatus:
    """One worker's poll snapshot."""

    rank: int
    running: bool
    finished: bool
    error: Optional[str]
    results: List[Dict[str, Any]]  # drained (metrics, checkpoint) rows
    dead: bool = False  # actor unreachable
    # tiered-checkpoint status of this rank's AsyncCheckpointer (None in
    # sync mode): {"index", "tier", "ram_acked", "committed_path"} — the
    # background persist lands AFTER the report row drained, so tier
    # progress travels on every poll, not on the one-shot row
    ckpt: Optional[Dict[str, Any]] = None


class TrainWorker:
    """Actor hosting one training process; runs the user loop in a thread.

    One process per chip (``_private/accelerators.py``): with
    ``ScalingConfig(use_tpu=True, chips_per_worker=k)`` the worker's lease
    carries ``TPU: k``, and before the actor is created its raylet binds
    the process to exactly k of the node's free chips and pins JAX to
    ``tpu`` — the loop gets those chips or an error, never the host.
    Without ``use_tpu`` the worker holds no chip and JAX in it is the
    CPU.  The jax process inside forms (or joins) the mesh.  On
    multi-host slices the controller passes coordinator address/process
    ids so workers can call ``jax.distributed.initialize`` (GSPMD mesh
    over the pod slice).
    """

    def __init__(self):
        self._thread: Optional[threading.Thread] = None
        self._session = None

    def get_metadata(self) -> Dict[str, Any]:
        import os
        import socket

        from ray_tpu._private.net import local_ip

        ctx = ray_tpu.get_runtime_context()
        return {
            "node_id": ctx.get_node_id(),
            "pid": os.getpid(),
            "hostname": socket.gethostname(),
            "ip": local_ip(),
        }

    def find_free_port(self) -> int:
        """A free port on THIS worker's host (for the rank-0 jax
        coordinator — the bind happens in this process later, so this is
        best-effort but races only with unrelated local processes)."""
        from ray_tpu._private.net import free_port

        return free_port()

    def setup_distributed(self, env: Dict[str, str]) -> None:
        """Install coordination env vars (before any jax import in the loop)."""
        import os

        os.environ.update(env)

    def start_loop(
        self,
        fn_payload: bytes,
        config: Dict[str, Any],
        rank: int,
        world_size: int,
        group_name: str,
        checkpoint_path: Optional[str],
        dataset_shard: Any = None,
        mesh_config: Any = None,
        axis_rules: Any = None,
        ckpt_plane: Optional[Dict[str, Any]] = None,
    ) -> None:
        from ray_tpu._private import serialization, tracing
        from ray_tpu.train import session as session_mod

        # the session's programs, from the first one on, in the process's
        # build ledger (a worker forked from the zygote has jax imported)
        tracing.watch_builds()
        fn = serialization.loads(fn_payload)
        ckpt = Checkpoint(checkpoint_path) if checkpoint_path else None
        sess = session_mod._start_session(
            rank=rank,
            world_size=world_size,
            group_name=group_name,
            config=config,
            checkpoint=ckpt,
            mesh_config=mesh_config,
            axis_rules=axis_rules,
            ckpt_plane=ckpt_plane,
        )
        sess.dataset_shard = dataset_shard
        self._session = sess

        def _run():
            try:
                if _takes_config(fn):
                    fn(config)
                else:
                    fn()
            except BaseException as e:  # noqa: BLE001 — reported to controller
                sess.error = e
                sess.error_tb = traceback.format_exc()
            finally:
                sess.finished.set()

        self._thread = threading.Thread(target=_run, daemon=True, name="train-loop")
        self._thread.start()

    def request_checkpoint(self, tier: str = "any",
                           avoid_nodes: Optional[List[str]] = None) -> bool:
        """Drain-notice leg: ask the loop to checkpoint at its next step
        boundary (``get_context().drain_requested()`` flips true).
        ``tier="memory"`` marks the deadline too short for the disk
        tier: the loop should ``commit_ram()`` and report as soon as
        the peer-RAM replica acks.  ``avoid_nodes`` are the draining
        node ids — the emergency push must not land its replica on a
        node the drain protocol is about to shut down."""
        sess = self._session
        if sess is None:
            return False
        sess.checkpoint_request_avoid = set(avoid_nodes or ())
        sess.checkpoint_request_tier = tier
        sess.checkpoint_requested.set()
        return True

    def poll(self) -> Dict[str, Any]:
        sess = self._session
        if sess is None:
            return {"running": False, "finished": False, "error": None, "results": []}
        rows = []
        while True:
            try:
                rows.append(sess.results.get_nowait())
            except Exception:
                break
        # Checkpoints travel as paths (directories are node-local; the
        # controller re-wraps them).  Tiered handles travel as their
        # generation index — durability progress rides the poll-level
        # ``ckpt`` status below, since the background persist usually
        # finishes after the row drains.
        out_rows = []
        for r in rows:
            ck = r.get("checkpoint")
            row = {"metrics": r["metrics"], "checkpoint_path": None}
            if ck is not None:
                if hasattr(ck, "ram_acked"):  # TieredCheckpoint handle
                    row["checkpoint_index"] = ck.index
                    row["checkpoint_path"] = ck.committed_path
                else:
                    row["checkpoint_path"] = ck.path
            out_rows.append(row)
        err = None
        if sess.error is not None:
            err = getattr(sess, "error_tb", None) or repr(sess.error)
        ckpt_status = None
        cp = sess._checkpointer
        last = cp.last if cp is not None else None
        if last is not None:
            ckpt_status = {
                "index": last.index,
                "tier": last.tier,
                "ram_acked": last.ram_acked,
                "committed_path": last.committed_path,
                "world": last.world,
            }
        return {
            "running": self._thread is not None and self._thread.is_alive(),
            "finished": sess.finished.is_set(),
            "error": err,
            "results": out_rows,
            "ckpt": ckpt_status,
        }

    def shutdown(self) -> bool:
        return True


def _takes_config(fn: Callable) -> bool:
    import inspect

    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    params = [p for p in sig.parameters.values()
              if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]
    return len(params) >= 1


class WorkerGroup:
    """Lifecycle of the N train-worker actors + their placement group."""

    def __init__(self, scaling_config, group_name: str):
        self.scaling_config = scaling_config
        self.group_name = group_name
        self.workers: List[Any] = []
        self.worker_metadata: List[Dict[str, Any]] = []
        self.pg = None
        self._started = False

    def start(self) -> None:
        from ray_tpu.util.placement_group import placement_group
        from ray_tpu.util.scheduling_strategies import (
            PlacementGroupSchedulingStrategy,
        )

        sc = self.scaling_config
        res = sc.worker_resources()
        bundles = [dict(res) for _ in range(sc.num_workers)]
        # Gang-reserve: one bundle per worker.  A requested topology
        # gang-schedules a contiguous pod slice (all bundles on nodes
        # sharing one slice label, ICI-adjacency order) when the cluster
        # advertises slice labels; PACK otherwise (reference:
        # BackendExecutor._create_placement_group,
        # python/ray/train/_internal/backend_executor.py:230).
        # restartable=True is the train controller's mode: a node death
        # inside the gang fate-shares it and the GCS re-runs atomic
        # reservation while the controller checkpoint-restarts.
        strategy = "STRICT_PACK_SLICE" if sc.topology else "PACK"
        self.pg = placement_group(bundles, strategy=strategy,
                                  name=f"train-{self.group_name}",
                                  priority=getattr(sc, "priority", 0),
                                  restartable=True)
        if not self.pg.wait(timeout_seconds=60):
            raise RuntimeError(
                f"placement group for {self.group_name} not placed in 60s "
                f"(bundles={bundles})")

        worker_cls = ray_tpu.remote(TrainWorker)
        # Predefined resources go through their dedicated options; only
        # custom keys ride the resources= dict (api_utils rejects CPU/TPU
        # there, mirroring the reference's option validation).
        opts: Dict[str, Any] = {
            "num_cpus": res.get("CPU", 0.0),
        }
        if res.get("GPU"):
            opts["num_gpus"] = res["GPU"]
        if res.get("TPU"):
            opts["num_tpus"] = res["TPU"]
        if res.get("memory"):
            opts["memory"] = res["memory"]
        custom = {k: v for k, v in res.items()
                  if k not in ("CPU", "GPU", "TPU", "memory")}
        if custom:
            opts["resources"] = custom
        self.workers = [
            worker_cls.options(
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    placement_group=self.pg, placement_group_bundle_index=i),
                **opts,
            ).remote()
            for i in range(sc.num_workers)
        ]
        # barrier: all actors alive
        self.worker_metadata = ray_tpu.get(
            [w.get_metadata.remote() for w in self.workers], timeout=60)
        self._started = True

    def worker_node_ids(self) -> List[str]:
        """Node hosting each rank (the drain watcher intersects this
        with the cluster's DRAINING set)."""
        return [m.get("node_id", "") for m in self.worker_metadata]

    def request_checkpoint(self, tier: str = "any",
                           avoid_nodes: Optional[List[str]] = None) -> None:
        """Best-effort fan-out of the drain notice to every rank
        (``tier="memory"`` when the deadline can't fit the disk tier;
        ``avoid_nodes`` = the draining nodes, so emergency replicas
        steer clear of hardware about to disappear)."""
        refs = []
        for w in self.workers:
            try:
                refs.append(w.request_checkpoint.remote(tier, avoid_nodes))
            except Exception:  # noqa: BLE001 — dying worker
                pass
        for r in refs:
            try:
                ray_tpu.get(r, timeout=5)
            except Exception:  # noqa: BLE001
                pass

    def run_train_fn(
        self,
        fn_payload: bytes,
        config: Dict[str, Any],
        checkpoint: Optional[Checkpoint],
        dataset_shards: Optional[List[Any]] = None,
        dist_env: Optional[List[Dict[str, str]]] = None,
        mesh_config: Any = None,
        axis_rules: Any = None,
        ckpt_planes: Optional[List[Dict[str, Any]]] = None,
    ) -> None:
        n = len(self.workers)
        if dist_env is not None:
            ray_tpu.get([
                w.setup_distributed.remote(dist_env[i])
                for i, w in enumerate(self.workers)
            ], timeout=30)
        refs = []
        for rank, w in enumerate(self.workers):
            shard = dataset_shards[rank] if dataset_shards else None
            refs.append(w.start_loop.remote(
                fn_payload, config, rank, n, self.group_name,
                checkpoint.path if checkpoint else None, shard,
                mesh_config, axis_rules,
                ckpt_planes[rank] if ckpt_planes else None,
            ))
        ray_tpu.get(refs, timeout=60)

    def poll(self, timeout: float = 30.0) -> List[WorkerStatus]:
        """Poll every worker; a dead actor yields ``dead=True`` status."""
        statuses: List[WorkerStatus] = []
        refs = [w.poll.remote() for w in self.workers]
        for rank, ref in enumerate(refs):
            try:
                st = ray_tpu.get(ref, timeout=timeout)
                statuses.append(WorkerStatus(
                    rank=rank, running=st["running"], finished=st["finished"],
                    error=st["error"], results=st["results"],
                    ckpt=st.get("ckpt")))
            except Exception as e:  # actor died / unreachable
                statuses.append(WorkerStatus(
                    rank=rank, running=False, finished=False,
                    error=f"worker {rank} unreachable: {e!r}", results=[],
                    dead=True))
        return statuses

    def shutdown(self) -> None:
        from ray_tpu.util.placement_group import remove_placement_group

        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        self.workers = []
        if self.pg is not None:
            try:
                remove_placement_group(self.pg)
            except Exception:
                pass
            self.pg = None
        self._started = False
