"""Train controller: the explicit control loop driving a worker group.

Parity: Train-v2 ``TrainController``
(``python/ray/train/v2/_internal/execution/controller/controller.py:91`` —
loop ``_run_control_loop_iteration :423``, step ``:332``): poll the group,
collect reported (metrics, checkpoint) rows, consult the FailurePolicy on
errors and the ScalingPolicy when (re)starting the group.  Recovery is
checkpoint-restore with a fresh group — on TPU that is also how elastic
resize works (the GSPMD mesh is re-formed by the new group).
"""

from __future__ import annotations

import logging
import time
import uuid
from typing import Any, Callable, Dict, List, Optional

from ray_tpu._private import tracing
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.checkpoint_manager import CheckpointManager
from ray_tpu.train.config import Result, RunConfig, ScalingConfig
from ray_tpu.train.policies import (
    DefaultFailurePolicy,
    FailureDecision,
    FailurePolicy,
    FixedScalingPolicy,
    ResizeDecision,
    ScalingPolicy,
    TrainRunContext,
)
from ray_tpu.train.worker_group import WorkerGroup, WorkerStatus

logger = logging.getLogger(__name__)


def _drain_caused_collective_abort(error: Optional[str]) -> bool:
    """True when a worker's failure is the collective watchdog aborting
    on a node DRAIN event.  Matched on the watchdog's exact abort
    phrasing (supervision.Watchdog._check_membership), NOT a bare
    "drain" substring — the error text embeds the group name (which
    contains the run name), so a run literally named "drain-..." must
    not turn every collective abort into a free restart.  Such a failure
    is a planned migration, not a fault: restart from the latest
    checkpoint with no failure-budget charge — the same contract as the
    advance-notice drain path in ``_maybe_handle_drain``."""
    if not error or "CollectiveAbortError" not in error:
        return False
    return ("lost to node drain" in error
            or "drain deadline expired" in error)


class TrainController:
    def __init__(
        self,
        fn_payload: bytes,
        train_loop_config: Dict[str, Any],
        scaling_config: ScalingConfig,
        run_config: RunConfig,
        failure_policy: Optional[FailurePolicy] = None,
        scaling_policy: Optional[ScalingPolicy] = None,
        datasets: Optional[Dict[str, Any]] = None,
        dist_env_fn: Optional[Callable[[WorkerGroup], Optional[List[Dict[str, str]]]]] = None,
        poll_interval_s: float = 0.05,
        resume_from_checkpoint: Optional[Checkpoint] = None,
    ):
        self.fn_payload = fn_payload
        self.train_loop_config = dict(train_loop_config or {})
        self.scaling_config = scaling_config
        self.run_config = run_config
        self.failure_policy = failure_policy or DefaultFailurePolicy(
            run_config.failure_config.max_failures)
        self.scaling_policy = scaling_policy or FixedScalingPolicy()
        self.datasets = datasets or {}
        self.dist_env_fn = dist_env_fn
        self.poll_interval_s = poll_interval_s
        self.name = run_config.name or f"train-{uuid.uuid4().hex[:8]}"

        ckpt_cfg = run_config.checkpoint_config
        storage = None
        if run_config.storage_path:
            import os

            storage = os.path.join(run_config.storage_path, self.name)
        self.checkpoint_manager = CheckpointManager(
            storage_dir=storage,
            num_to_keep=ckpt_cfg.num_to_keep,
            score_attribute=ckpt_cfg.checkpoint_score_attribute,
            score_order=ckpt_cfg.checkpoint_score_order,
        )
        if resume_from_checkpoint is not None:
            self.checkpoint_manager.register(resume_from_checkpoint, {})
        # tiered checkpoint plane (CheckpointConfig.mode == "tiered"):
        # per-node peer-RAM replica servers owned HERE — outside the
        # worker placement group — so the emergency tier survives the
        # group restarts it exists to serve
        self._tiered_mode = getattr(ckpt_cfg, "mode", "sync") == "tiered"
        self._peer_replication = getattr(ckpt_cfg, "peer_replication", True)
        self._replica_plane = None
        # per-generation-index durability tracking from poll-time
        # checkpointer status: index -> {"ranks_ram": set, "world": int,
        # "path": str|None, "registered": bool}
        self._tiered: Dict[int, Dict[str, Any]] = {}
        self.metrics_history: List[Dict[str, Any]] = []
        self._ctx = TrainRunContext()
        # report-row bookkeeping: rows are aligned by per-rank *absolute*
        # index within a group generation, not by poll-window position (a
        # rank's row can straddle poll boundaries)
        self._generation = 0
        self._rank_row_counts: Dict[int, int] = {}
        self._step_buffer: Dict[tuple, Dict[int, Dict[str, Any]]] = {}
        self._emitted: Dict[tuple, Dict[str, Any]] = {}
        self._ckpt_registered: set = set()
        # drain (preemption-notice) watching: node ids whose drain this
        # controller already reacted to — a restarted group that can only
        # re-land on the draining node (single-node cluster) must not
        # restart-loop on the same notice
        self._drains_handled: set = set()
        self._last_drain_check = 0.0
        self._draining_cache: Dict[str, float] = {}

    # -- group lifecycle ---------------------------------------------------
    def _start_group(self) -> WorkerGroup:
        decision = self.scaling_policy.make_decision_for_non_running_worker_group(
            self.scaling_config)
        sc = self.scaling_config
        if isinstance(decision, ResizeDecision) and \
                decision.num_workers != sc.num_workers:
            import dataclasses

            sc = dataclasses.replace(sc, num_workers=decision.num_workers)
            logger.info("train %s: elastic resize to %d workers",
                        self.name, sc.num_workers)
        # Generation-scoped name: collective groups and report indices from
        # a previous (possibly abruptly killed) group can never alias the
        # new one's.
        self._generation += 1
        self._rank_row_counts = {}
        group = WorkerGroup(sc, f"{self.name}/g{self._generation}")
        # a generation's start, as fit() waits for it: the workers placed
        # and alive, then the mesh's coordination wired and every loop
        # started with the mesh it is to form (a worker's own side of it,
        # where it holds chips, is its ``worker.chip_acquire`` span)
        with tracing.span("train.startup", kind="startup", attrs={
                "workers": sc.num_workers,
                "generation": self._generation}):
            with tracing.span("train.startup.workers", kind="startup"):
                group.start()
            with tracing.span("train.startup.mesh", kind="startup"):
                shards = self._split_datasets(sc.num_workers, group)
                dist_env = (self.dist_env_fn(group) if self.dist_env_fn
                            else None)
                # the REQUESTED mesh ships to every generation unchanged;
                # workers resolve it against the devices they actually see
                # (clamp_to), so mesh shape is a runtime decision — an
                # elastic restart onto fewer chips re-forms a valid
                # smaller mesh from the same request
                group.run_train_fn(
                    self.fn_payload, self.train_loop_config,
                    self.checkpoint_manager.latest, shards, dist_env,
                    mesh_config=sc.mesh_config(),
                    axis_rules=sc.logical_axis_rules,
                    ckpt_planes=self._wire_replica_plane(group))
        return group

    def _wire_replica_plane(self, group: WorkerGroup):
        """Tiered mode: (re)build the per-node replica-server plane for
        this generation's nodes and return each rank's plane wiring
        (storage dir, run name, its peer server, all server names).
        Servers are reused across generations — that is the whole point
        — but servers whose node died are dropped so a replacement gets
        pinned to live hardware."""
        if not self._tiered_mode:
            return None
        from ray_tpu.util.checkpoint_replica import ReplicaPlane

        if self._replica_plane is None:
            self._replica_plane = ReplicaPlane(self.name)
        plane = self._replica_plane
        node_ids = group.worker_node_ids()
        try:
            import ray_tpu

            alive = {n["node_id"] for n in ray_tpu.nodes() if n.get("alive")}
            for nid in list(plane.node_ids):
                if nid not in alive:
                    plane.drop_node(nid)
        except Exception:  # noqa: BLE001 — pruning is an optimization
            pass
        plane.ensure_for_nodes(node_ids)
        servers = plane.server_names()
        peers = plane.peer_assignment(node_ids) if self._peer_replication \
            else [None] * len(node_ids)
        return [{
            "mode": "tiered",
            "run": self.name,
            "storage_dir": self.checkpoint_manager.storage_dir,
            "peer": peers[rank],
            "servers": servers,
        } for rank in range(len(node_ids))]

    def _restart_group(self) -> WorkerGroup:
        """Start a replacement group, treating start-time failures (e.g.
        a placement group that cannot place because the cluster view
        still includes a just-dead node) as ordinary failures: consult
        the FailurePolicy and retry — the next attempt re-runs the
        ScalingPolicy against the updated cluster."""
        while True:
            try:
                return self._start_group()
            except Exception as e:  # noqa: BLE001 — placement/start errors
                self._ctx.errors_seen += 1
                decision = self.failure_policy.make_decision(
                    self._ctx, str(e))
                if decision != FailureDecision.RETRY:
                    raise
                logger.warning(
                    "train %s: group start failed (%d so far), retrying "
                    "with a fresh scaling decision:\n%s",
                    self.name, self._ctx.errors_seen, e)
                time.sleep(1.0)

    def _split_datasets(self, n: int,
                        group: Optional[WorkerGroup] = None
                        ) -> Optional[List[Any]]:
        if not self.datasets:
            return None
        # locality hints: the node each rank runs on, so the split
        # coordinator routes bundles to the co-located consumer instead of
        # forcing a cross-node pull per misrouted block
        hints: Optional[List[Optional[str]]] = None
        if group is not None:
            try:
                ids = group.worker_node_ids()
                if len(ids) == n and any(ids):
                    hints = [i or None for i in ids]
            except Exception:  # noqa: BLE001 — hints are an optimization
                pass
        # one shard dict per rank; Dataset objects are streaming_split,
        # plain iterables replicated
        per_rank: List[Dict[str, Any]] = [dict() for _ in range(n)]
        for name, ds in self.datasets.items():
            splitter = getattr(ds, "streaming_split", None)
            if callable(splitter):
                kw = {"locality_hints": hints} if hints else {}
                parts = splitter(n, equal=True, **kw)
                for r in range(n):
                    per_rank[r][name] = parts[r]
            else:
                for r in range(n):
                    per_rank[r][name] = ds
        return per_rank

    # -- dashboard status ---------------------------------------------------
    def _publish_status(self, group, status: str) -> None:
        """Best-effort run snapshot into the GCS KV (namespace "train")
        for the dashboard's train view (reference:
        ``dashboard/modules/train``).  Throttled to ~1/s and deduped so
        an idle poll loop doesn't re-dirty GCS persistence."""
        import json

        now = time.time()
        if status == "RUNNING" and \
                now - getattr(self, "_last_status_t", 0.0) < 1.0:
            return
        latest = self.metrics_history[-1] if self.metrics_history else {}
        # terminal publishes run after group.shutdown() emptied .workers:
        # report the last LIVE world size, not 0
        world = len(group.workers) if group and group.workers else \
            getattr(self, "_last_world_size", 0)
        snap = {
            "name": self.name, "status": status,
            "world_size": world,
            "iteration": latest.get("training_iteration"),
            "latest_metrics": {
                k: v for k, v in latest.items()
                if isinstance(v, (int, float, str))},
            "restarts": self._ctx.errors_seen,
            "started_at": getattr(self, "_started_at", 0.0),
        }
        blob = json.dumps(snap, default=str).encode()
        if status == "RUNNING" and \
                blob == getattr(self, "_last_status_blob", None):
            return
        try:
            from ray_tpu.experimental import internal_kv

            internal_kv._internal_kv_put(
                self.name.encode(), blob, namespace="train")
            self._last_status_t = now
            self._last_status_blob = blob
        except Exception:  # noqa: BLE001 — dashboard view is best-effort
            pass

    # -- drain / preemption handling ---------------------------------------
    def _poll_draining_nodes(self) -> Dict[str, float]:
        """node_id -> drain deadline for every DRAINING node, polled from
        the GCS node table at most twice a second (the drain event is
        also on the pubsub feed; polling the table keeps this loop
        single-threaded and restart-safe)."""
        now = time.time()
        if now - self._last_drain_check < 0.5:
            return self._draining_cache
        self._last_drain_check = now
        try:
            import ray_tpu

            self._draining_cache = {
                n["node_id"]: n.get("drain_deadline") or 0.0
                for n in ray_tpu.nodes() if n.get("state") == "DRAINING"}
        except Exception:  # noqa: BLE001 — control plane hiccup
            pass
        return self._draining_cache

    def _maybe_handle_drain(self, group: WorkerGroup) -> bool:
        """React to a drain notice covering any node hosting this group:
        ask every rank for an immediate checkpoint, wait (bounded by the
        drain deadline) for one to be reported and committed, and tell
        the caller to restart the group — the scheduler soft-avoids
        DRAINING nodes, so the replacement lands elsewhere whenever the
        cluster has anywhere else to be.  This is the before-the-corpse
        half of preemption recovery; the after-the-corpse half (worker
        death -> FailurePolicy -> restore) stays as the fallback."""
        from ray_tpu._private.config import config

        draining = self._poll_draining_nodes()
        if not draining:
            return False
        overlap = {nid: dl for nid, dl in draining.items()
                   if nid in set(group.worker_node_ids())
                   and nid not in self._drains_handled}
        if not overlap:
            return False
        self._drains_handled.update(overlap)
        deadline = min(overlap.values()) or (
            time.time() + config.train_drain_checkpoint_wait_s)
        window = max(0.0, deadline - time.time())
        # tier decision: a window too short for serialize+fsync cannot
        # complete the disk tier — ask for a memory-tier checkpoint (the
        # peer-RAM ack is the commit; the restarted group restores from
        # the replica plane with zero disk reads for those shards)
        tier = "any"
        if self._tiered_mode and \
                window < config.train_drain_memory_tier_floor_s:
            tier = "memory"
        logger.warning(
            "train %s: drain notice for node(s) %s hosting workers "
            "(%.1fs to deadline); requesting immediate %s-tier checkpoint "
            "and restarting off the draining node(s)",
            self.name, [n[:8] for n in overlap], window,
            "memory" if tier == "memory" else "best")
        pre_ckpts = len(self._ckpt_registered) + self._tiered_durable_count()
        # the draining nodes ride along: an emergency replica pushed to
        # hardware the drain protocol shuts down at the deadline is no
        # replica at all — ranks whose ring peer is doomed re-target
        group.request_checkpoint(tier=tier, avoid_nodes=list(overlap))
        # leave a margin before the deadline for group teardown + restart
        wait_until = min(deadline - 1.0,
                         time.time() + config.train_drain_checkpoint_wait_s)
        while time.time() < wait_until:
            statuses = group.poll()
            self._collect_results(statuses)
            # finished beats checkpointed: a run completing during the
            # wait (its last step's checkpoint counts as "new") must not
            # be torn down and pointlessly re-run from that checkpoint
            if all(s.finished for s in statuses):
                return False  # the run beat the drain: nothing to migrate
            if len(self._ckpt_registered) + self._tiered_durable_count() \
                    > pre_ckpts:
                break  # the pre-drain checkpoint is durable (some tier)
            if any(s.error for s in statuses):
                break  # deadline beat us; restart from what we have
            time.sleep(self.poll_interval_s)
        return True

    def _tiered_durable_count(self) -> int:
        """How many tiered checkpoint generations are durable at ANY
        tier: disk-registered, or RAM-complete (every rank's shard acked
        by a peer server — the ``memory``-tier commit)."""
        n = 0
        for info in self._tiered.values():
            if info.get("registered"):
                n += 1
            elif info.get("world") and \
                    len(info["ranks_ram"]) >= info["world"]:
                n += 1
        return n

    def _gang_fate_shared(self, group: WorkerGroup) -> bool:
        """True when THIS group's placement gang was failed as a unit by
        the GCS (node death inside the gang -> whole gang FAILED ->
        atomic re-reservation).  Like a drain, that is infrastructure
        preemption, not an application fault: the restart takes the
        existing no-charge path.  Each generation creates a fresh gang,
        so the check never sees a previous generation's marker."""
        pg = getattr(group, "pg", None)
        if pg is None:
            return False
        try:
            from ray_tpu._private.worker import get_global_worker

            w = get_global_worker()
            gangs = w.run_coro(w.gcs.call("list_gangs", timeout=5.0),
                               timeout=10.0)
        except Exception:  # noqa: BLE001 — control plane hiccup
            return False
        for g in gangs or []:
            if g.get("gang_id") == pg.id.binary():
                return bool(g.get("fate_shared"))
        return False

    # -- control loop ------------------------------------------------------
    def run(self) -> Result:
        self._started_at = time.time()
        group = self._start_group()
        self._last_world_size = len(group.workers)
        error: Optional[BaseException] = None
        try:
            while True:
                self._last_world_size = len(group.workers)
                statuses = group.poll()
                self._collect_results(statuses)
                self._publish_status(group, "RUNNING")

                if not all(s.finished for s in statuses) and \
                        self._maybe_handle_drain(group):
                    # planned migration, not a failure: no failure-budget
                    # charge; the restart re-runs the ScalingPolicy so an
                    # elastic run resizes to the surviving capacity
                    group.shutdown()
                    group = self._restart_group()
                    continue

                errs = [s for s in statuses if s.error]
                if errs and any(_drain_caused_collective_abort(s.error)
                                for s in errs):
                    logger.warning(
                        "train %s: collective group aborted by a node "
                        "drain covering a worker; restarting from the "
                        "latest checkpoint (planned migration, no "
                        "failure-budget charge):\n%s",
                        self.name, errs[0].error)
                    group.shutdown()
                    group = self._restart_group()
                    continue
                if errs and any(s.dead for s in errs) and \
                        self._gang_fate_shared(group):
                    logger.warning(
                        "train %s: placement gang fate-shared (node died "
                        "inside the gang); restarting the FULL group from "
                        "the latest checkpoint (infrastructure preemption,"
                        " no failure-budget charge):\n%s",
                        self.name, errs[0].error)
                    group.shutdown()
                    group = self._restart_group()
                    continue
                if errs:
                    self._ctx.errors_seen += 1
                    first = errs[0].error
                    decision = self.failure_policy.make_decision(self._ctx, first)
                    if decision == FailureDecision.RETRY:
                        logger.warning(
                            "train %s: worker failure (%d so far), restarting "
                            "from latest checkpoint:\n%s",
                            self.name, self._ctx.errors_seen, first)
                        group.shutdown()
                        group = self._restart_group()
                        continue
                    error = RuntimeError(
                        f"training failed after {self._ctx.errors_seen} "
                        f"failure(s):\n{first}")
                    break

                if all(s.finished for s in statuses):
                    break
                time.sleep(self.poll_interval_s)
        except BaseException as e:  # noqa: BLE001 — status must not lie
            # an exception propagating out (e.g. restart retries
            # exhausted) is a FAILED run even though no break set `error`
            error = e
            raise
        finally:
            group.shutdown()
            if self._replica_plane is not None:
                # the RAM tier's lifetime is the run's: disk commits
                # survive; the emergency replicas die with their purpose
                self._replica_plane.shutdown()
            self._publish_status(
                group, "FAILED" if error is not None else "FINISHED")

        return Result(
            metrics=self.metrics_history[-1] if self.metrics_history else None,
            checkpoint=self.checkpoint_manager.best,
            path=self.checkpoint_manager.storage_dir,
            error=error,
            metrics_history=list(self.metrics_history),
        )

    def _collect_results(self, statuses: List[WorkerStatus]) -> None:
        """Merge per-rank reports.

        Rows are keyed (generation, per-rank absolute row index): rank r's
        i-th ``report()`` call pairs with every other rank's i-th call no
        matter how the rows split across poll windows.  Rank-0 metrics are
        canonical; the first checkpoint seen for a step is registered
        (rank 0 wins when it arrives in the same poll).
        """
        for s in statuses:
            base = self._rank_row_counts.get(s.rank, 0)
            for off, row in enumerate(s.results):
                key = (self._generation, base + off)
                self._step_buffer.setdefault(key, {})[s.rank] = row
            self._rank_row_counts[s.rank] = base + len(s.results)
            if s.ckpt:
                self._note_tiered_status(s.rank, s.ckpt)

        for key in sorted(self._step_buffer):
            rows = self._step_buffer[key]
            if key not in self._emitted:
                if 0 not in rows:
                    continue  # wait for the canonical rank
                metrics = dict(rows[0]["metrics"])
                metrics.setdefault("training_iteration",
                                   len(self.metrics_history) + 1)
                self.metrics_history.append(metrics)
                self._emitted[key] = metrics
            if key not in self._ckpt_registered:
                for rank in sorted(rows):
                    path = rows[rank].get("checkpoint_path")
                    if path:
                        self.checkpoint_manager.register(
                            Checkpoint(path), self._emitted[key])
                        self._ckpt_registered.add(key)
                        break
            if len(rows) == len(statuses) and key in self._emitted:
                del self._step_buffer[key]

    def _note_tiered_status(self, rank: int, st: Dict[str, Any]) -> None:
        """Fold one rank's poll-time checkpointer status into per-index
        durability tracking (the background persist lands after the
        report row drained, so tier progress arrives here).  A committed
        sharded dir is adopted into the CheckpointManager in place — it
        already lives inside the storage dir — which also gives it
        top-K eviction and ``Result.checkpoint`` visibility."""
        idx = st.get("index")
        if idx is None:
            return
        info = self._tiered.setdefault(
            idx, {"ranks_ram": set(), "world": st.get("world"),
                  "path": None, "registered": False})
        if st.get("world"):
            info["world"] = st["world"]
        if st.get("ram_acked"):
            info["ranks_ram"].add(rank)
        path = st.get("committed_path")
        if path and not info["registered"]:
            import os

            if os.path.isdir(path):
                metrics = self.metrics_history[-1] \
                    if self.metrics_history else {}
                self.checkpoint_manager.register(Checkpoint(path), metrics)
                info["registered"] = True
                info["path"] = path
