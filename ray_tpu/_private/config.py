"""Central config-flag table, overridable via environment variables.

TPU-native equivalent of the reference's ``RAY_CONFIG`` X-macro table
(``src/ray/common/ray_config_def.h`` — 225 flags, overridable as ``RAY_{name}``
env vars, materialized by the ``RayConfig`` singleton in
``src/ray/common/ray_config.h``).  Here the table is a plain dict of typed
defaults; every flag is overridable as ``RAY_TPU_{NAME}`` and the whole
resolved map can be shipped cross-process (the reference passes
``_system_config`` through ``ray.init``).
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_FLAG_DEFS: Dict[str, Any] = {
    # --- transport / rpc ---
    "rpc_connect_timeout_s": 30.0,
    "rpc_retry_delay_ms": 100,
    "rpc_max_retries": 5,
    # chaos injection, same spirit as RAY_testing_rpc_failure
    # (src/ray/rpc/rpc_chaos.h:23): "method=N:req_prob:resp_prob,..."
    "testing_rpc_failure": "",
    # seed for the transport-chaos decision stream: same spec + same seed
    # => the same drop/delay/dup decisions at the same call indices
    # (chaos.py determinism contract, extended to the RPC layer)
    "testing_rpc_seed": 0,
    # netem rule set keyed on (src node, dst node, verb):
    # "src>dst:verb:action[:p=..][:at=..][:for=..][:n=..][:phase=..];..."
    # ("<>" for symmetric links; actions drop | delay=<s> | dup)
    "netem": "",
    "netem_seed": 0,
    # bounded at-most-once reply cache: deduped GCS mutations keyed by a
    # client-minted request id keep their first reply for replay, so the
    # transport retry layer can never double-apply one
    "gcs_reply_cache_size": 4096,
    # --- object store ---
    # C++ shm arena (ray_tpu/_native/store.cc) — the plasma-equivalent fast
    # path; objects > arena_store_bytes/4 use per-object segments instead
    "use_native_arena_store": True,
    "arena_store_bytes": 256 * 1024 * 1024,
    # results smaller than this return in-band to the owner's memory store
    # (reference: RayConfig::max_direct_call_object_size, 100KB)
    "max_inline_object_size": 100 * 1024,
    "object_spill_dir": "",
    # --- object lifetime (reference_count.h:72, object_recovery_manager.h) ---
    "reference_counting_enabled": True,
    # failsafe expiry for the executor→submitter bridge pin on refs
    # embedded in return values (the submitter's reply-time registration
    # retires it; the TTL only fires for replies that were lost) —
    # correctness does not depend on any receiver deserializing in time
    "transfer_pin_ttl_s": 60.0,
    # how many producing TaskSpecs the owner retains for lineage
    # reconstruction (reference max_lineage_bytes, task_manager.h:182)
    "lineage_max_entries": 100_000,
    "ref_event_drain_interval_s": 0.05,
    "borrower_liveness_interval_s": 30.0,
    # --- scheduling ---
    # hybrid policy threshold (reference scheduler_spread_threshold,
    # src/ray/raylet/scheduling/policy/hybrid_scheduling_policy.cc)
    "scheduler_spread_threshold": 0.5,
    "worker_lease_timeout_s": 30.0,
    # how long a PENDING placement group whose bundles fit no ALIVE node
    # keeps retrying before failing as infeasible — long enough for the
    # autoscaler to provision a larger node type
    "pg_infeasible_timeout_s": 300.0,
    # concurrent leased workers per scheduling key (reference
    # NormalTaskSubmitter requests one worker per queued task)
    "max_leases_per_scheduling_key": 32,
    # seed for the gang-preemption victim tiebreak (chaos.py-style
    # determinism: same cluster spec + same seed => same victims)
    "gang_preempt_seed": 0,
    # drain deadline broadcast when preempting a lower-priority gang:
    # the victim's budget to checkpoint + vacate before its nodes are
    # treated as preempted (never SIGKILL-first)
    "gang_preempt_drain_deadline_s": 30.0,
    # --- worker pool ---
    "num_prestart_workers": 0,
    "worker_startup_timeout_s": 60.0,
    "idle_worker_kill_s": 300.0,
    "maximum_startup_concurrency": 4,
    # fork-server worker spawning: one zygote process pays the
    # interpreter+jax import once, workers fork from it in ~ms
    # (reference WorkerPool prestart, src/ray/raylet/worker_pool.h)
    "use_worker_zygote": 1,
    # generous: the zygote's accept loop is serial (one ~ms fork per
    # request), so a deep spawn backlog is delay, not failure — timing
    # out after the request was sent risks a duplicate worker
    "zygote_spawn_timeout_s": 60.0,
    # --- memory monitor / OOM killing ---
    # (reference src/ray/common/memory_monitor.h:52 +
    # worker_killing_policy*.h; refresh 0 disables)
    "memory_monitor_refresh_ms": 250,
    "memory_usage_threshold": 0.95,
    "worker_killing_policy": "retriable_fifo",  # | "group_by_owner"
    # don't kill when our workers hold less than this share of used bytes
    # (pressure is then external to the raylet — shared-host tenants)
    "memory_kill_min_worker_share": 0.10,
    # --- node drain / preemption ---
    # default drain window when none is given (reference: DrainNode RPC's
    # deadline; spot-TPU reclaim notices give ~30-60s of advance warning)
    "node_drain_deadline_s": 30.0,
    # how long the train controller waits for the post-drain-notice
    # checkpoint before restarting the group anyway (always additionally
    # capped by the drain deadline itself)
    "train_drain_checkpoint_wait_s": 10.0,
    # --- tiered checkpointing (train.checkpoint_async) ---
    # backpressure bound: a save() issued while the previous persist is
    # still in flight waits at most this long (never silently drops)
    "train_checkpoint_persist_wait_s": 120.0,
    # rank 0's bounded wait for every peer's shard before the manifest
    # commit; expiry leaves the generation torn (.tmp, swept later)
    "train_checkpoint_manifest_wait_s": 60.0,
    # bound for one replica-plane RPC (peer push / fetch / manifest)
    "train_checkpoint_replica_rpc_timeout_s": 30.0,
    # drain windows shorter than this can't fit the disk persist: the
    # controller requests a memory-tier (peer-RAM) checkpoint instead
    "train_drain_memory_tier_floor_s": 5.0,
    # --- health / failure detection ---
    # (reference gcs_health_check_manager.h:45 timings)
    "health_check_period_s": 5.0,
    "num_heartbeats_timeout": 6,
    # --- health plane (straggler / silent-degradation detection) ---
    # passive-scoring cadence of the HealthMonitor loop
    "health_monitor_interval_s": 2.0,
    # robust-z threshold: |x - median| / (1.4826 * MAD) above this is an
    # outlier window (3.5 is the classic Iglewicz-Hoaglin cutoff)
    "health_mad_threshold": 3.5,
    # hysteresis: consecutive outlier windows before SUSPECT promotion —
    # one noisy window never trips the ladder
    "health_suspect_windows": 3,
    # active probe must run at least this factor slower on the suspect
    # than on the healthy reference to confirm (2x = well past noise)
    "health_probe_factor": 2.0,
    # bound on one active-probe task round-trip; an unschedulable or
    # wedged probe counts as confirmation-by-silence after this long
    "health_probe_timeout_s": 30.0,
    # drain deadline handed to the GCS when quarantining a node: long
    # enough for a no-charge checkpoint, short enough to evict promptly
    "health_quarantine_drain_deadline_s": 15.0,
    # non-force cancel: grace period for the injected async-exception to
    # take effect before the (disposable, fork-server-replaced) worker is
    # terminated — a thread blocked in a C call never sees the injection
    "cancel_escalation_s": 2.0,
    # --- task/actor fault tolerance ---
    "task_max_retries_default": 3,
    "actor_max_restarts_default": 0,
    # how long a caller waits for an actor to leave PENDING_CREATION —
    # creation bursts spawn worker processes serially, so scale this with
    # expected burst size (reference: actor creation has no client-side
    # deadline at all)
    "actor_resolve_timeout_s": 300.0,
    # --- GCS ---
    # "memory" | "file" (head-disk persistence) | "external" (standalone
    # store process — head-disk loss no longer loses the cluster)
    "gcs_storage": "memory",
    "gcs_storage_path": "",
    # host:port of a `python -m ray_tpu._private.gcs_store` process
    # (required when gcs_storage == "external")
    "gcs_external_store_addr": "",
    # --- logging ---
    # worker output files are truncated in place once they exceed this
    # (drained by the raylet log monitor first); 0 disables rotation
    "log_rotation_bytes": 100 * 1024 * 1024,
    # --- object transfer (pull/push managers, object_manager.h:106) ---
    "transfer_chunk_bytes": 8 * 1024 * 1024,
    "transfer_window_chunks": 4,
    "transfer_max_bytes_in_flight": 256 * 1024 * 1024,
    "transfer_push_concurrency": 8,
    # --- collective ---
    "collective_op_timeout_s": 120.0,
    # --- data ---
    # unfused unordered reads stream blocks via generator tasks
    "data_streaming_reads": True,
}


def _coerce(default: Any, raw: str) -> Any:
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


class _Config:
    """Resolved flag map. Access flags as attributes: ``config.rpc_max_retries``."""

    def __init__(self):
        self._values: Dict[str, Any] = {}
        self.reload()

    def reload(self, overrides: Dict[str, Any] | None = None):
        values = dict(_FLAG_DEFS)
        for name, default in _FLAG_DEFS.items():
            env = os.environ.get(f"RAY_TPU_{name.upper()}")
            if env is None:
                env = os.environ.get(f"RAY_TPU_{name}")
            if env is not None:
                values[name] = _coerce(default, env)
        if overrides:
            for k, v in overrides.items():
                if k not in _FLAG_DEFS:
                    raise ValueError(f"Unknown config flag: {k}")
                values[k] = v
        self._values = values

    def __getattr__(self, name: str) -> Any:
        try:
            return self._values[name]
        except KeyError:
            raise AttributeError(name) from None

    def to_json(self) -> str:
        return json.dumps(self._values)

    def apply_json(self, payload: str):
        self._values.update(json.loads(payload))


config = _Config()
