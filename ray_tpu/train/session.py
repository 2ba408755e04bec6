"""Worker-side training session: ``report``, ``get_context``.

Parity: ``ray.train.report`` / ``ray.train.get_context``
(``python/ray/train/_internal/session.py``).  The session lives in the
worker actor; ``report()`` enqueues (metrics, checkpoint) rows the
controller polls (Train-v2 poll-based worker group,
``python/ray/train/v2/_internal/execution/worker_group/worker_group.py``).
"""

from __future__ import annotations

import contextlib
import json
import queue
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, Optional

from ray_tpu.train.checkpoint import Checkpoint

_session_lock = threading.Lock()
_session: Optional["_TrainSession"] = None


class StepLedger:
    """Per-training-step wall-time attribution: where did this step go?

    Buckets every second of a step into ``data_wait`` (blocked on the
    ingest pipeline), ``h2d`` (host→device staging), ``compute`` (the
    jitted update), ``collective_wait`` (supervised collective ops —
    auto-attributed via the tracing duration-sink, no loop changes),
    ``channel_wait`` (compiled-graph / pipeline channel reads —
    auto-attributed by ``EdgeTransport.read``, so pipeline steps see
    their inter-stage stalls), ``checkpoint_snapshot`` (the inline D2H
    copy a tiered save charges the step), ``checkpoint_persist``
    (serialize+fsync — on the async path attributed from the background
    thread, so the breakdown shows it OVERLAPPING compute instead of
    stalling the step), ``weight_publish`` (auto-attributed by the RL
    weight-sync publisher), and ``other`` (the unexplained remainder).
    The MFU number finally gets a denominator breakdown::

        ledger = train.get_context().step_ledger()
        for batch in it:
            with ledger.step():
                with ledger.bucket("compute"):
                    state, m = train_step(state, batch)

    Emissions: a ``train_step_bucket_s`` histogram series per bucket, a
    ``step_breakdown/<group>/<rank>`` KV record for the dashboard's
    step-breakdown panel (throttled), a ``train.step`` span in the
    current trace, and — while a profiler session runs
    (``train.profile()``) — ``train.step`` / ``train.<bucket>``
    annotations in the profiler's trace, on the device's clock.
    Standalone-constructible (``StepLedger(group_name=...)``): it
    needs no session.
    """

    BUCKETS = ("data_wait", "h2d", "compute", "collective_wait",
               "channel_wait", "checkpoint_snapshot", "checkpoint_persist",
               "weight_publish")

    _PUBLISH_EVERY_S = 2.0
    _HISTORY = 64

    def __init__(self, group_name: str = "", rank: int = 0,
                 publish: bool = True):
        self.group_name = group_name
        self.rank = rank
        self._publish = publish
        self._lock = threading.Lock()  # sinks fire from prefetch threads
        self._cur: Dict[str, float] = {}
        self._in_step = False
        self._step_idx = 0
        self._history: deque = deque(maxlen=self._HISTORY)
        self._totals: Dict[str, float] = {}
        self._total_wall = 0.0
        self._last_publish = 0.0
        self._metric = None

    # -- accumulation -------------------------------------------------------

    def note(self, bucket: str, seconds: float) -> None:
        """Attribute ``seconds`` to ``bucket`` in the current step (no-op
        between steps, so pipelined background work between boundaries is
        not mischarged)."""
        if not self._in_step or seconds <= 0:
            return
        with self._lock:
            if self._in_step:
                self._cur[bucket] = self._cur.get(bucket, 0.0) + seconds

    @contextlib.contextmanager
    def bucket(self, name: str) -> Iterator[None]:
        from ray_tpu._private import tracing

        t0 = time.perf_counter()
        try:
            # in a profiler trace the bucket is ``train.<name>`` on the
            # device's clock (auto-attributed durations arrive through
            # ``note`` after the fact and cannot be)
            with tracing.annotate("train." + name):
                yield
        finally:
            self.note(name, time.perf_counter() - t0)

    @contextlib.contextmanager
    def step(self) -> Iterator["StepLedger"]:
        """Mark one training-step boundary; nesting is rejected."""
        from ray_tpu._private import tracing

        if self._in_step:
            raise RuntimeError("StepLedger.step() does not nest")
        with self._lock:
            self._cur = {}
            self._in_step = True
        # route auto-attributed durations (collective_wait from the
        # supervision spine, weight_publish from the RL publisher,
        # data_wait/h2d from the ingest plane) into this step
        token = tracing.register_duration_sink(self.note)
        t0 = time.perf_counter()
        start_wall = time.time()
        try:
            with tracing.annotate("train.step", step=self._step_idx + 1):
                yield self
        finally:
            wall = time.perf_counter() - t0
            tracing.unregister_duration_sink(token)
            with self._lock:
                self._in_step = False
                buckets = dict(self._cur)
            self._finish_step(buckets, wall, start_wall)

    # -- per-step bookkeeping ----------------------------------------------

    def _finish_step(self, buckets: Dict[str, float], wall: float,
                     start_wall: float) -> None:
        from ray_tpu._private import tracing

        accounted = sum(buckets.values())
        buckets["other"] = max(0.0, wall - accounted)
        self._step_idx += 1
        entry = {"step": self._step_idx, "wall_s": wall,
                 "buckets": buckets}
        self._history.append(entry)
        for k, v in buckets.items():
            self._totals[k] = self._totals.get(k, 0.0) + v
        self._total_wall += wall
        try:
            self._observe_metrics(buckets, wall)
        except Exception:  # noqa: BLE001 — attribution must never fail a step
            pass
        if tracing.is_enabled():
            ctx = tracing.current_or_root().child()
            tracing.record_span(
                "train.step", start_wall, start_wall + wall, ctx,
                kind="step",
                attrs={"step": self._step_idx, "group": self.group_name,
                       "rank": self.rank,
                       **{f"{k}_ms": round(v * 1e3, 3)
                          for k, v in buckets.items()}})
        if self._publish and \
                time.time() - self._last_publish > self._PUBLISH_EVERY_S:
            self._last_publish = time.time()
            try:
                self._publish_kv()
            except Exception:  # noqa: BLE001 — best-effort surfacing
                pass

    def _observe_metrics(self, buckets: Dict[str, float],
                         wall: float) -> None:
        if self._metric is None:
            from ray_tpu.util.metrics import Histogram

            self._metric = Histogram(
                "train_step_bucket_s",
                "per-step wall time attributed to each step-ledger bucket",
                boundaries=[0.001, 0.005, 0.02, 0.1, 0.5, 2.0, 10.0],
                tag_keys=("bucket", "group"))
        for k, v in buckets.items():
            self._metric.observe(v, tags={"bucket": k,
                                          "group": self.group_name or "-"})

    # -- read-out -----------------------------------------------------------

    def last_breakdown(self) -> Optional[Dict[str, Any]]:
        return dict(self._history[-1]) if self._history else None

    def recent_breakdown(self, n: int = 16) -> Optional[Dict[str, Any]]:
        """Mean wall + per-bucket seconds over the last ``n`` recorded
        steps — the health plane's scoring window (lifetime means would
        dilute a freshly degraded rank under a long healthy history)."""
        with self._lock:
            hist = list(self._history)[-n:]
        if not hist:
            return None
        steps = len(hist)
        wall = sum(h["wall_s"] for h in hist)
        buckets: Dict[str, float] = {}
        for h in hist:
            for k, v in h["buckets"].items():
                buckets[k] = buckets.get(k, 0.0) + v
        return {"steps": steps, "wall_s_per_step": wall / steps,
                "buckets_s": {k: v / steps for k, v in buckets.items()}}

    def breakdown(self) -> Dict[str, Any]:
        """Aggregate view: mean seconds and fraction per bucket across
        recorded steps — the ``step_time_breakdown`` block bench records."""
        n = max(self._step_idx, 1)
        wall = self._total_wall
        out: Dict[str, Any] = {
            "steps": self._step_idx,
            "step_wall_s": wall / n,
            "buckets_s": {k: v / n for k, v in self._totals.items()},
            "fractions": {k: (v / wall if wall > 0 else 0.0)
                          for k, v in self._totals.items()},
        }
        return out

    def _publish_kv(self) -> None:
        import ray_tpu

        if not ray_tpu.is_initialized():
            return
        from ray_tpu._private.worker import get_global_worker

        w = get_global_worker(required=False)
        if w is None:
            return
        rec = {"ts": time.time(), "group": self.group_name,
               "rank": self.rank, **self.breakdown(),
               "last": self.last_breakdown(),
               # health-plane inputs: the recent scoring window, where
               # this rank runs, and the per-edge channel latencies its
               # process observed (straggler attribution evidence)
               "recent": self.recent_breakdown(),
               "node_id": getattr(w, "node_id", "") or ""}
        try:
            from ray_tpu.util.health import edge_latency_snapshot

            edges = edge_latency_snapshot()
            if edges:
                rec["edges"] = edges
        except Exception:  # noqa: BLE001 — evidence stays best-effort
            pass
        key = f"step_breakdown/{self.group_name or 'default'}/{self.rank}"
        # bounded: this runs inline at a step boundary — a wedged GCS
        # must cost the training loop at most the timeout, never a hang
        w.run_coro(
            w.gcs.call("kv_put", ns="train", key=key,
                       value=json.dumps(rec).encode(), overwrite=True,
                       timeout=2),
            timeout=4)


class _TrainSession:
    def __init__(
        self,
        rank: int,
        world_size: int,
        group_name: str,
        config: Dict[str, Any],
        checkpoint: Optional[Checkpoint],
        mesh_config: Any = None,
        axis_rules: Optional[Dict[str, Any]] = None,
        ckpt_plane: Optional[Dict[str, Any]] = None,
    ):
        self.rank = rank
        self.world_size = world_size
        self.group_name = group_name
        self.config = config
        self.latest_checkpoint = checkpoint
        self.results: "queue.Queue" = queue.Queue()
        self.finished = threading.Event()
        self.error: Optional[BaseException] = None
        self.error_tb: Optional[str] = None
        self.dataset_shard: Any = None
        # the REQUESTED mesh (parallel.MeshConfig or None) + rule-table
        # override from ScalingConfig; get_mesh() resolves it against the
        # devices this generation actually sees, so every elastic restart
        # re-forms a mesh that fits the surviving hardware
        self.mesh_config = mesh_config
        self.axis_rules = axis_rules
        self._mesh = None  # resolved jax Mesh, built lazily once
        # set by the controller when the node hosting this worker got a
        # drain (preemption) notice: the loop should checkpoint at its
        # next step boundary; cleared when a checkpoint is reported
        self.checkpoint_requested = threading.Event()
        # the tier the drain checkpoint must reach: "any" (default —
        # whatever tier lands) or "memory" (deadline below disk-write
        # time: peer-RAM ack suffices, skip waiting on the disk tier)
        self.checkpoint_request_tier = "any"
        # node ids covered by the drain notice: the emergency push must
        # not place its replica on a node about to be shut down
        self.checkpoint_request_avoid: set = set()
        # tiered-checkpoint plane wiring from the controller (None in
        # legacy sync mode): storage_dir/run/peer/server names — see
        # ``train.checkpoint_async`` (mode "tiered")
        self.ckpt_plane = ckpt_plane
        self._checkpointer = None  # lazy AsyncCheckpointer
        # lazy per-session step-time attribution ledger (step_ledger())
        self._ledger: Optional[StepLedger] = None


def _start_session(**kw) -> _TrainSession:
    global _session
    with _session_lock:
        _session = _TrainSession(**kw)
        return _session


def _get_session() -> _TrainSession:
    s = _session
    if s is None:
        raise RuntimeError(
            "No training session active — this API must be called inside "
            "a train_loop_per_worker"
        )
    return s


def report(
    metrics: Dict[str, Any], checkpoint: Optional[Any] = None
) -> None:
    """Report metrics (and optionally a checkpoint) to the controller.

    ``checkpoint`` may be a directory :class:`Checkpoint` (legacy
    whole-tree path) or a ``checkpoint_async.TieredCheckpoint`` handle
    from ``get_context().checkpointer().save(...)`` — the tiered row
    carries the generation index; the controller tracks per-tier
    durability from poll-time checkpointer status (the background
    persist finishes after this call returns).
    """
    s = _get_session()
    if checkpoint is not None:
        s.checkpoint_requested.clear()
        s.checkpoint_request_tier = "any"
        s.checkpoint_request_avoid = set()
    s.results.put({"metrics": dict(metrics), "checkpoint": checkpoint})


# -- GSPMD mesh + sharding (worker-side face of ScalingConfig.mesh) ----------


def get_mesh():
    """The resolved ``jax.sharding.Mesh`` for this worker generation.

    Joins the multi-process jax runtime first (no-op single-process),
    then resolves the *requested* ``ScalingConfig.mesh`` against the
    devices actually visible — ``MeshConfig.clamp_to`` degrades fixed
    axes that no longer fit, so a restart after a drain shrank the group
    re-forms a valid smaller mesh instead of dying on a divisibility
    error.  No mesh request means pure data parallelism over every
    device.  Built once per session and cached.
    """
    s = _get_session()
    if s._mesh is not None:
        return s._mesh
    from ray_tpu.train.trainer import initialize_jax_distributed

    initialize_jax_distributed()
    import logging

    import jax

    from ray_tpu._private import accelerators, tracing
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh

    tracing.watch_builds()  # where start_loop came before jax's import
    requested = s.mesh_config or MeshConfig(dp=-1)
    n = len(jax.devices())
    accelerators.record_chip_acquire()  # the worker's first backend
    concrete = requested.clamp_to(n)
    try:
        fits = requested.resolve(n) == concrete.resolve(n)
    except ValueError:
        fits = False
    if not fits:
        logging.getLogger(__name__).warning(
            "train %s: requested mesh (%s) does not fit %d devices; "
            "clamped to (%s)", s.group_name, requested._named(), n,
            concrete._named())
    s._mesh = create_mesh(concrete)
    return s._mesh


def shard_params(params: Any, spec_tree: Any, rules=None):
    """Place a host-materialized param pytree on the session mesh as
    ``NamedSharding`` arrays, per its logical-axis ``spec_tree`` (e.g.
    ``llama_param_specs(cfg)``) and the session's rule table.

    Works single- and multi-process: every process passes the same full
    host tree and contributes the shards its local devices own.  (For
    models too big to materialize on one host, init inside ``jit`` with
    sharded ``out_shardings`` instead — ``ShardedTrainer.init_state``
    does exactly that.)
    """
    import numpy as np

    import jax

    from ray_tpu.parallel.sharding import spec_tree_to_shardings

    s = _get_session()
    mesh = get_mesh()
    shardings = spec_tree_to_shardings(
        spec_tree, mesh, rules or s.axis_rules)

    def _put(x, sh):
        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, sh, lambda idx: arr[idx])

    return jax.tree.map(_put, params, shardings)


def shard_inputs(batch: Any, logical_axes=("batch",), rules=None):
    """Shard per-step input arrays over the session mesh's data axes.

    ``logical_axes`` names each array dimension (default: leading
    "batch" dim sharded over dp×fsdp, rest replicated).  Single-process:
    a plain sharded ``device_put``.  Multi-process: each process passes
    its *local* rows and they concatenate, in rank order, into one
    global array — the multi-host batch contract of
    ``jax.distributed`` — without the loop touching
    ``multihost_utils``.
    """
    import jax

    from ray_tpu.parallel.sharding import logical_to_pspec

    s = _get_session()
    mesh = get_mesh()
    spec = logical_to_pspec(logical_axes, rules or s.axis_rules, mesh=mesh)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return jax.tree.map(
            lambda x: multihost_utils.host_local_array_to_global_array(
                x, mesh, spec), batch)
    from jax.sharding import NamedSharding

    sh = NamedSharding(mesh, spec)
    return jax.tree.map(lambda x: jax.device_put(x, sh), batch)


class TrainContext:
    def get_world_size(self) -> int:
        return _get_session().world_size

    def get_world_rank(self) -> int:
        return _get_session().rank

    def get_local_rank(self) -> int:
        return _get_session().rank  # single-node local == world for now

    def get_trial_name(self) -> str:
        return _get_session().group_name

    def get_checkpoint(self) -> Optional[Checkpoint]:
        return _get_session().latest_checkpoint

    def get_config(self) -> Dict[str, Any]:
        return _get_session().config

    def get_mesh(self):
        """The resolved GSPMD mesh for this generation (see
        :func:`get_mesh`)."""
        return get_mesh()

    def shard_params(self, params: Any, spec_tree: Any, rules=None):
        """Place params on the mesh per a logical-axis spec tree (see
        :func:`shard_params`)."""
        return shard_params(params, spec_tree, rules=rules)

    def shard_inputs(self, batch: Any, logical_axes=("batch",), rules=None):
        """Shard input arrays over the mesh's data axes (see
        :func:`shard_inputs`)."""
        return shard_inputs(batch, logical_axes=logical_axes, rules=rules)

    def step_ledger(self) -> StepLedger:
        """This worker's step-time attribution ledger (one per session;
        see :class:`StepLedger`)."""
        s = _get_session()
        if s._ledger is None:
            s._ledger = StepLedger(group_name=s.group_name, rank=s.rank)
        return s._ledger

    def drain_requested(self) -> bool:
        """True when the node hosting this worker received a drain
        (preemption) notice and the controller asked for an immediate
        checkpoint: report one at the next step boundary — steps since
        the last reported checkpoint will be re-run by the replacement
        group.  Loops that checkpoint every step can ignore this."""
        return _get_session().checkpoint_requested.is_set()

    def drain_checkpoint_tier(self) -> str:
        """The durability tier the pending drain checkpoint must reach:
        ``"any"`` (normal — let the disk tier land) or ``"memory"`` (the
        drain deadline is below disk-write time: the peer-RAM ack is the
        commit; call ``checkpointer().commit_ram()`` and report)."""
        return _get_session().checkpoint_request_tier

    def checkpoint_mode(self) -> str:
        """``"tiered"`` when the controller wired the async sharded
        checkpoint plane into this session (``CheckpointConfig(mode=
        "tiered")``), else ``"sync"`` (legacy whole-tree reports)."""
        return "tiered" if _get_session().ckpt_plane is not None else "sync"

    def checkpointer(self, writers: Optional[int] = None):
        """This rank's tiered :class:`~ray_tpu.train.checkpoint_async.
        AsyncCheckpointer` (one per session, wired to the run's storage
        dir, peer replica server, and this session's step ledger).
        ``writers`` overrides the writer-group size when fewer ranks
        than the world save (e.g. the RLHF loop checkpoints from rank 0
        only: ``writers=1`` makes it a sole-writer generation).  Usable
        even in sync mode (local-RAM + disk tiers only) — e.g. bench
        arms construct sessions without a controller."""
        s = _get_session()
        if s._checkpointer is None:
            from ray_tpu.train.checkpoint_async import AsyncCheckpointer

            plane = s.ckpt_plane or {}
            s._checkpointer = AsyncCheckpointer(
                storage_dir=plane.get("storage_dir"),
                run=plane.get("run", s.group_name),
                rank=s.rank,
                world=writers if writers is not None else s.world_size,
                peer_name=plane.get("peer"),
                server_names=plane.get("servers", ()),
                ledger=self.step_ledger(),
                # memory-tier drain requests preempt save()'s disk
                # backpressure: the emergency checkpoint must commit at
                # the RAM tier inside the reclaim window even when a
                # slow disk persist is still in flight
                preempt_ram=lambda: (
                    s.checkpoint_requested.is_set()
                    and s.checkpoint_request_tier == "memory"),
                drain_avoid=lambda: s.checkpoint_request_avoid,
            )
        return s._checkpointer

    def restore_checkpoint(self):
        """Restore the newest complete checkpoint, mode-appropriately.

        Tiered mode walks the per-shard preference ladder (local RAM ->
        peer RAM -> committed disk) and reassembles the full tree
        whatever mesh wrote it; sync mode loads the controller-provided
        directory checkpoint.  Returns a ``checkpoint_async.
        RestoreResult`` (``.tree``, ``.meta``, ``.index``, ``.tier``) or
        None when no checkpoint exists yet.
        """
        s = _get_session()
        if s.ckpt_plane is not None:
            return self.checkpointer().restore()
        ck = s.latest_checkpoint
        if ck is None:
            return None
        import re

        from ray_tpu.train.checkpoint_async import RestoreResult

        m = re.search(r"checkpoint_(\d+)$", ck.path)
        return RestoreResult(
            tree=ck.to_pytree(), meta={}, index=int(m.group(1)) if m else 0,
            world=s.world_size, tier_by_rank={}, disk_reads=1, path=ck.path)

    def collective_group(self, backend: str = "tcp",
                         timeout_s: Optional[float] = None) -> str:
        """Join (once) the all-workers collective group; returns its name.

        The DP pattern over DCN-separated hosts: compute grads locally,
        ``col.allreduce(grads, ctx.collective_group())``, apply locally.
        The group name is generation-scoped, so a restarted worker group
        re-forms a FRESH group (new epoch) — a watchdog-aborted
        generation's rendezvous state can never leak into its
        replacement.  ``timeout_s`` bounds every op: a peer that dies or
        hangs mid-allreduce surfaces as ``CollectiveAbortError`` (a
        worker failure the controller restarts from the latest
        checkpoint) instead of wedging this loop forever.
        """
        from ray_tpu.util import collective as col

        s = _get_session()
        name = f"train::{s.group_name}"
        if not col.is_group_initialized(name):
            col.init_collective_group(
                s.world_size, s.rank, backend, name, timeout_s=timeout_s
            )
        return name


def get_context() -> TrainContext:
    return TrainContext()


def get_dataset_shard(name: str = "train"):
    """This rank's dataset shard (parity: ``ray.train.get_dataset_shard``).

    Returns the shard the controller assigned via
    ``DataParallelTrainer(datasets={name: ds})`` — a ``DataIterator`` for
    ``ray_tpu.data`` datasets (``streaming_split`` per rank), or the value
    itself for plain iterables (replicated).
    """
    s = _get_session()
    shards = s.dataset_shard
    if shards is None:
        raise KeyError(
            f"no datasets were passed to the trainer (requested {name!r})")
    if isinstance(shards, dict):
        if name not in shards:
            raise KeyError(f"no dataset shard named {name!r}; have {list(shards)}")
        return shards[name]
    return shards


class _ProfileCapture:
    """Context manager for ``ray_tpu.train.profile`` (device-level
    profiler; complements the task-span chrome trace of
    ``raytpu timeline``).  Reference counterpart: torch-profiler hooks in
    ``ray.train`` callbacks; here it is ``jax.profiler.trace`` capturing
    XLA/TPU execution (xplane + trace-viewer files, loadable in
    TensorBoard or Perfetto)."""

    def __init__(self, logdir: Optional[str] = None):
        import os

        if logdir is None:
            base = os.environ.get("RAY_TPU_SESSION_DIR", "/tmp/ray_tpu")
            rank = _session.rank if _session is not None else 0
            logdir = os.path.join(base, "profiles", f"rank{rank}")
        self.logdir = logdir

    def __enter__(self):
        import os

        import jax

        os.makedirs(self.logdir, exist_ok=True)
        jax.profiler.start_trace(self.logdir)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False


def profile(logdir: Optional[str] = None) -> _ProfileCapture:
    """Capture a device-level profiler trace around training steps::

        for step in range(10):
            if step == 3:
                prof = train.profile().__enter__()
            state, m = train_step(state, batch)
            if step == 5:
                prof.__exit__()

    or as a context manager around a block of steps.  Writes per-rank
    trace directories under the session dir by default."""
    return _ProfileCapture(logdir)
