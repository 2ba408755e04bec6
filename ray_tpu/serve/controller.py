"""ServeController: the reconciliation loop.

Reference: ``python/ray/serve/_private/controller.py:86`` (singleton
controller actor), ``deployment_state.py`` (goal-state reconciliation),
``autoscaling_state.py`` + ``autoscaling_policy.py`` (queue-depth-driven
replica autoscaling).
"""

from __future__ import annotations

import threading
import time
import uuid
from typing import Any, Dict, List, Optional

import ray_tpu

CONTROLLER_NAME = "SERVE_CONTROLLER"


@ray_tpu.remote(name=CONTROLLER_NAME, max_restarts=1)
class ServeController:
    def __init__(self):
        self._deployments: Dict[str, Dict[str, Any]] = {}
        self._routes: Dict[str, str] = {}  # route_prefix -> deployment
        self._apps: Dict[str, str] = {}  # app name -> ingress deployment
        self._health_fails: Dict[str, int] = {}  # replica -> consecutive
        # node ids whose drain has already been migrated-from: a
        # replacement that could only land back on the draining node
        # (nowhere else feasible) must not be kill-looped every tick
        self._drains_migrated: set = set()
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._loop = threading.Thread(target=self._reconcile_loop, daemon=True)
        self._loop.start()

    # -- deploy / delete -----------------------------------------------------

    def deploy(self, name: str, target_payload: bytes, init_args: tuple,
               init_kwargs: dict, config: Dict[str, Any],
               route_prefix: Optional[str],
               app_name: Optional[str] = None) -> bool:
        old_replicas: List[Any] = []
        with self._lock:
            st = self._deployments.get(name)
            if st is None:
                st = {"replicas": [], "version": 0, "last_scale": 0.0,
                      "scale_marks": [], "ready": set(), "starting": {}}
                self._deployments[name] = st
            elif st.get("target") != target_payload or st.get("config") != config:
                # code or config changed: running replicas embed the OLD
                # payload — restart them all (full restart, not rolling)
                old_replicas = list(st["replicas"])
                st["replicas"] = []
                st["ready"] = set()
                st["starting"] = {}
            st.update(
                target=target_payload, init_args=init_args,
                init_kwargs=init_kwargs, config=config,
                goal_replicas=config["num_replicas"])
            if app_name:
                self._apps[app_name] = name
            asc = config.get("autoscaling_config")
            if asc:
                st["goal_replicas"] = max(asc["min_replicas"],
                                          min(st["goal_replicas"],
                                              asc["max_replicas"]))
            st["version"] += 1
            if route_prefix:
                self._routes[route_prefix] = name
        for r in old_replicas:
            try:
                ray_tpu.kill(r)
            except Exception:
                pass
        self._reconcile_once()
        return True

    def delete_deployment(self, name: str) -> bool:
        with self._lock:
            st = self._deployments.pop(name, None)
            self._routes = {r: d for r, d in self._routes.items() if d != name}
            self._apps = {a: d for a, d in self._apps.items() if d != name}
        if st:
            for r in st["replicas"]:
                try:
                    ray_tpu.kill(r)
                except Exception:
                    pass
        return True

    def shutdown(self) -> bool:
        with self._lock:
            names = list(self._deployments)
        for n in names:
            self.delete_deployment(n)
        self._stop.set()
        # the reconcile thread re-checks _stop before any publish, so
        # once it drains this delete is the final word on serve status
        self._loop.join(timeout=5.0)
        try:
            from ray_tpu.experimental import internal_kv

            internal_kv._internal_kv_del(b"status", namespace="serve")
        except Exception:  # noqa: BLE001 — cluster may be tearing down
            pass
        return True

    # -- queries -------------------------------------------------------------

    def get_deployment_info(self, name: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            st = self._deployments.get(name)
            if st is None:
                return None
            # routers get READY replicas only: a still-constructing
            # replacement (cold jit init can take seconds) must not
            # receive dispatches that then queue behind its __init__ —
            # the head-of-line the production-day drain surfaced.  With
            # no confirmed-ready replica yet (initial deploy window) the
            # full set is returned: queueing on a cold replica beats
            # shedding the first seconds of traffic.
            ready = st.get("ready") or set()
            reps = [r for r in st["replicas"]
                    if r._actor_id.hex() in ready] or list(st["replicas"])
            return {"replicas": reps,
                    "max_ongoing_requests":
                        st["config"]["max_ongoing_requests"],
                    "max_queued_requests":
                        st["config"].get("max_queued_requests", -1),
                    "version": st["version"]}

    # a reporter whose last report is older than this no longer
    # contributes its ``queued`` GAUGE to the aggregate (the process may
    # have exited mid-burst and would otherwise pin phantom queued
    # requests in the published status forever); its monotonic counters
    # — events that really happened — are kept
    OVERLOAD_REPORT_TTL_S = 15.0
    # a reporter silent this long has exited (live routers re-push an
    # unchanged snapshot every Router.REPORT_HEARTBEAT_S): its entry is
    # dropped and its monotonic counters fold into the deployment's
    # retired base, so a long-lived deployment hit by many short-lived
    # driver/client processes doesn't grow the report dict without bound
    OVERLOAD_RETIRE_S = 120.0

    def report_overload(self, name: str, reporter_id: str,
                        stats: Dict[str, int]) -> bool:
        """One router process's shed/expired/cancelled/queued counters
        (absolute, not deltas).  Keyed by reporter so every handle-owning
        process (driver, proxies, composing replicas) aggregates without
        double counting; summed into the published status."""
        with self._lock:
            st = self._deployments.get(name)
            if st is None:
                return False
            reports = st.setdefault("overload_reports", {})
            reports[reporter_id] = {"stats": dict(stats), "t": time.time()}
            self._retire_silent_reporters(st)
        return True

    @classmethod
    def _retire_silent_reporters(cls, st: Dict[str, Any]) -> None:
        """Lock held.  Worst case a reporter frozen past OVERLOAD_RETIRE_S
        that then resumes re-counts its pre-freeze events — bounded,
        visibility-only, and preferred over tombstones that would defeat
        the eviction."""
        reports = st.get("overload_reports", {})
        cutoff = time.time() - cls.OVERLOAD_RETIRE_S
        dead = [rid for rid, rep in reports.items() if rep["t"] < cutoff]
        if not dead:
            return
        base = st.setdefault(
            "overload_retired", {"shed": 0, "expired": 0, "cancelled": 0})
        for rid in dead:
            stats = reports.pop(rid)["stats"]
            for k in base:
                base[k] += int(stats.get(k, 0))

    @classmethod
    def _overload_total(cls, st: Dict[str, Any]) -> Dict[str, int]:
        total = {"shed": 0, "expired": 0, "cancelled": 0, "queued": 0}
        for k, v in st.get("overload_retired", {}).items():
            total[k] += v
        now = time.time()
        for rep in st.get("overload_reports", {}).values():
            stats = rep["stats"]
            for k in ("shed", "expired", "cancelled"):
                total[k] += int(stats.get(k, 0))
            if now - rep["t"] < cls.OVERLOAD_REPORT_TTL_S:
                total["queued"] += int(stats.get("queued", 0))
        return total

    def get_version(self, name: str) -> int:
        with self._lock:
            st = self._deployments.get(name)
            return -1 if st is None else st["version"]

    def list_deployments(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {name: {"num_replicas": len(st["replicas"]),
                           "goal": st.get("goal_replicas", 0),
                           "version": st["version"],
                           "overload": self._overload_total(st)}
                    for name, st in self._deployments.items()}

    def get_routes(self) -> Dict[str, str]:
        with self._lock:
            return dict(self._routes)

    def get_app_ingress(self, app_name: str) -> Optional[str]:
        with self._lock:
            return self._apps.get(app_name)

    def reconfigure(self, name: str, user_config: dict) -> bool:
        with self._lock:
            st = self._deployments.get(name)
            if st is None:
                return False
            st["config"]["user_config"] = user_config
            replicas = list(st["replicas"])
        # bounded: a wedged replica must not hang the controller's RPC
        # thread — it will be replaced by the health checker instead
        ray_tpu.get([r.reconfigure.remote(user_config) for r in replicas],
                    timeout=30)
        return True

    # -- reconciliation ------------------------------------------------------

    def _start_replica(self, name: str, st: Dict[str, Any]):
        rid = f"{name}#{uuid.uuid4().hex[:6]}"
        from ray_tpu.serve.replica import ReplicaActor

        opts = dict(st["config"].get("ray_actor_options") or {})
        # a replica must admit max_ongoing_requests concurrent calls (the
        # router's load metric — and @serve.batch needs in-replica concurrency)
        opts.setdefault("max_concurrency",
                        max(2, st["config"]["max_ongoing_requests"]))
        handle = ReplicaActor.options(**opts).remote(
            st["target"], st["init_args"], st["init_kwargs"],
            st["config"].get("user_config"), name, rid)
        st["replicas"].append(handle)
        # readiness probe issued NOW; _confirm_starting_once promotes the
        # replica into the routed set once this resolves
        st.setdefault("starting", {})[handle._actor_id.hex()] = \
            handle.check_health.remote()
        st["version"] += 1

    def _reconcile_once(self):
        with self._lock:
            items = list(self._deployments.items())
            for name, st in items:
                goal = st.get("goal_replicas", 0)
                while len(st["replicas"]) < goal:
                    self._start_replica(name, st)
                while len(st["replicas"]) > goal:
                    victim = st["replicas"].pop()
                    self._forget_replica(st, victim)
                    st["version"] += 1
                    try:
                        ray_tpu.kill(victim)
                    except Exception:
                        pass

    @staticmethod
    def _forget_replica(st: Dict[str, Any], replica) -> None:
        """Lock held: drop a replica from the readiness bookkeeping."""
        key = replica._actor_id.hex()
        st.setdefault("ready", set()).discard(key)
        st.setdefault("starting", {}).pop(key, None)

    def _confirm_starting_once(self):
        """Promote replicas whose readiness probe resolved into the
        routed set (``ready``).  Runs every tick, so a replacement
        becomes routable ~1 reconcile interval after its __init__
        finishes — and not one request earlier."""
        with self._lock:
            items = list(self._deployments.items())
        for name, st in items:
            with self._lock:
                starting = list(st.get("starting", {}).items())
            for key, ref in starting:
                try:
                    done, _ = ray_tpu.wait([ref], timeout=0)
                except Exception:  # noqa: BLE001 — transient: next tick
                    continue
                if not done:
                    continue
                ok = False
                try:
                    ray_tpu.get(ref, timeout=1)
                    ok = True
                except Exception:  # noqa: BLE001 — failed init: health
                    pass           # checker / prune will replace it
                with self._lock:
                    st.get("starting", {}).pop(key, None)
                    if ok and any(r._actor_id.hex() == key
                                  for r in st["replicas"]):
                        st.setdefault("ready", set()).add(key)
                        st["version"] += 1

    def _prune_dead_replicas(self):
        """Drop replicas whose actor the GCS reports DEAD (chaos kill,
        node loss) the tick it happens, instead of waiting up to three
        10s health-check rounds — the window in which every router kept
        dispatching to a corpse and burning its retry budget."""
        with self._lock:
            if not any(st["replicas"] for st in self._deployments.values()):
                return  # idle controller: no actor-table scan per tick
        try:
            from ray_tpu._private.worker import get_global_worker

            w = get_global_worker()
            actors = w.run_coro(w.gcs.call("list_actors"))
            dead = {a["actor_id"].hex() for a in actors
                    if a.get("state") == "DEAD"}
        except Exception:  # noqa: BLE001 — control-plane hiccup
            return
        if not dead:
            return
        with self._lock:
            for st in self._deployments.values():
                gone = [r for r in st["replicas"]
                        if r._actor_id.hex() in dead]
                for r in gone:
                    st["replicas"].remove(r)
                    self._forget_replica(st, r)
                    self._health_fails.pop(r._actor_id.hex(), None)
                if gone:
                    st["version"] += 1

    # engine-stats KV records older than this don't vote in autoscaling
    # (a dead replica's last published pressure must not pin a pool up)
    ENGINE_STATS_FRESH_S = 30.0

    def _engine_records(self, name: str) -> list:
        """Fresh engine-stats records LLM replicas of deployment ``name``
        published to the GCS KV (namespace "llm") — the autoscaler's
        engine-signal feed.  Empty for non-engine deployments."""
        import json

        try:
            from ray_tpu.experimental.internal_kv import \
                _internal_kv_get_prefix

            table = _internal_kv_get_prefix(f"engine/{name}/",
                                            namespace="llm")
        except Exception:  # noqa: BLE001 — control-plane hiccup
            return []
        out = []
        now = time.time()
        for raw in (table or {}).values():
            try:
                rec = json.loads(raw)
            except Exception:  # noqa: BLE001 — record mid-write
                continue
            if now - rec.get("ts", 0) <= self.ENGINE_STATS_FRESH_S:
                out.append(rec)
        return out

    def _autoscale_once(self):
        """Per-pool signal-driven scaling (``serve/autoscaling.py``):
        overload counters (queue gauge, shed/expired deltas) + engine
        signals (slot occupancy, block pressure) + the legacy in-flight
        average, so e.g. a prefill pool scales up on queue depth while
        the decode pool scales up on slot occupancy — independently."""
        from ray_tpu.serve.autoscaling import (
            autoscaling_config_from_dict,
            desired_delta,
            pool_signals_from_engine_records,
        )

        with self._lock:
            items = list(self._deployments.items())
        for name, st in items:
            asc = st["config"].get("autoscaling_config")
            if not asc:
                continue
            replicas = list(st["replicas"])
            if not replicas:
                continue
            total = 0
            for r in replicas:
                try:
                    # peak-since-last-tick, not the instantaneous gauge:
                    # a burst shorter than the tick period must still be
                    # visible to the next autoscale decision
                    total += ray_tpu.get(r.take_load_peak.remote(),
                                         timeout=5)
                except Exception:
                    pass
            cfg = autoscaling_config_from_dict(asc)
            # the KV prefix read costs one GCS RPC per tick: only pay it
            # for pools that actually scale on engine signals — a plain
            # serve deployment never publishes engine stats
            engine_recs = [] if (cfg.target_slot_occupancy is None
                                 and cfg.target_block_pressure is None
                                 and cfg.target_queue_depth is None) \
                else self._engine_records(name)
            now = time.monotonic()
            with self._lock:
                overload = self._overload_total(st)
                # first tick: seed the baseline without acting — the
                # deployment's whole overload HISTORY is not one tick's
                # worth of events
                first = "autoscale_last_overload" not in st
                last = st.get("autoscale_last_overload") or {}
                st["autoscale_last_overload"] = dict(overload)
                sig = pool_signals_from_engine_records(
                    engine_recs, len(replicas),
                    ongoing_avg=total / len(replicas),
                    router_queued=int(overload.get("queued", 0)),
                    shed_delta=0 if first else
                    max(0, overload.get("shed", 0) - last.get("shed", 0)),
                    expired_delta=0 if first else
                    max(0, overload.get("expired", 0)
                        - last.get("expired", 0)))
                delta = desired_delta(cfg, sig)
                goal = st.get("goal_replicas", 1)
                if delta > 0 and goal < cfg.max_replicas:
                    if now - st["last_scale"] >= cfg.upscale_delay_s:
                        st["goal_replicas"] = min(goal + 1,
                                                  cfg.max_replicas)
                        st["last_scale"] = now
                elif delta < 0 and goal > cfg.min_replicas:
                    if now - st["last_scale"] >= cfg.downscale_delay_s:
                        st["goal_replicas"] = max(goal - 1,
                                                  cfg.min_replicas)
                        st["last_scale"] = now

    def _drain_migrate_once(self):
        """Migrate replicas off DRAINING nodes before the deadline kills
        them (reference: deployment_state reacting to the autoscaler's
        drain-before-terminate).  Start-then-kill per replica — the old
        replica is killed only after its replacement answers a health
        check (bounded by the drain deadline), so serving capacity never
        dips below goal.  One migration pass per node-drain event: a
        replacement that could only land back on the draining node
        (nowhere else feasible) is left alone instead of kill-looped."""
        try:
            node_info = {n["node_id"]: n for n in ray_tpu.nodes()}
        except Exception:  # noqa: BLE001 — control-plane hiccup
            return
        draining = {nid for nid, n in node_info.items()
                    if n.get("state") == "DRAINING"}
        # forget resolved drains (node back ALIVE, or DEAD and gone)
        self._drains_migrated &= draining
        fresh = draining - self._drains_migrated
        if not fresh:
            return
        try:
            from ray_tpu.util.state import list_actors

            actor_nodes = {a["actor_id"]: a.get("node_id")
                           for a in list_actors()}
        except Exception:  # noqa: BLE001 — transient: retry next tick
            return
        # mark handled only once the actor map is in hand (a zero-work
        # pass must retry); from here even a partial pass never repeats
        self._drains_migrated |= fresh
        with self._lock:
            items = [(n, list(st["replicas"])) for n, st in
                     self._deployments.items()]
        # phase 1: start EVERY replacement first — the waits below then
        # overlap all cold starts instead of serializing them against a
        # ticking drain deadline
        migrations = []  # (old replica, replacement, drain deadline)
        for name, replicas in items:
            for r in replicas:
                node = actor_nodes.get(r._actor_id.hex())
                if node not in fresh:
                    continue
                with self._lock:
                    st = self._deployments.get(name)
                    if st is None or r not in st["replicas"]:
                        continue
                    st["replicas"].remove(r)
                    self._forget_replica(st, r)
                    st["version"] += 1
                    self._start_replica(name, st)
                    replacement = st["replicas"][-1]
                migrations.append(
                    (r, replacement,
                     node_info.get(node, {}).get("drain_deadline")
                     or (time.time() + 10.0)))
        if not migrations:
            return
        # phase 2: one bounded wait for all replacements to come up
        # (health refs issued up front, so the gets overlap), then kill
        # the old replicas — capacity never dips below goal, and the
        # whole pass costs at most one deadline margin, not one per
        # replica
        wait_until = min(dl for _r, _repl, dl in migrations) - 2.0
        refs = [repl.check_health.remote() for _r, repl, _dl in migrations]
        for ref in refs:
            wait_s = min(15.0, wait_until - time.time())
            if wait_s <= 0:
                break  # deadline looming: kill-and-hope beats losing both
            try:
                ray_tpu.get(ref, timeout=wait_s)
            except Exception:  # noqa: BLE001 — kill anyway: the
                pass  # deadline takes the old replica regardless
        for r, _repl, _dl in migrations:
            self._health_fails.pop(r._actor_id.hex(), None)
            try:
                ray_tpu.kill(r)
            except Exception:  # noqa: BLE001
                pass

    def _health_check_once(self):
        with self._lock:
            items = [(n, list(st["replicas"])) for n, st in
                     self._deployments.items()]
        for name, replicas in items:
            for r in replicas:
                key = r._actor_id.hex()
                try:
                    ray_tpu.get(r.check_health.remote(), timeout=10)
                    self._health_fails.pop(key, None)
                    with self._lock:
                        st = self._deployments.get(name)
                        if st and r in st["replicas"] and \
                                key not in st.setdefault("ready", set()):
                            st["ready"].add(key)
                            st.get("starting", {}).pop(key, None)
                            st["version"] += 1
                    continue
                except Exception:
                    with self._lock:
                        st = self._deployments.get(name) or {}
                        if key in st.get("starting", {}):
                            # still inside __init__ (weights, backend
                            # bring-up): its calls queue behind it.  Not
                            # a failure — a replica whose process died
                            # is _prune_dead_replicas's to replace
                            continue
                    # a slow check (e.g. the replica is jit-compiling and
                    # holding the GIL) is not death: replace only after
                    # consecutive failures
                    fails = self._health_fails.get(key, 0) + 1
                    self._health_fails[key] = fails
                    if fails < 3:
                        continue
                self._health_fails.pop(key, None)
                with self._lock:
                    st = self._deployments.get(name)
                    if st and r in st["replicas"]:
                        st["replicas"].remove(r)
                        self._forget_replica(st, r)
                        st["version"] += 1
                try:
                    ray_tpu.kill(r)
                except Exception:
                    pass

    def _publish_status(self):
        """Snapshot deployments/routes/apps into the GCS KV (namespace
        "serve") so the dashboard head renders serve state with a plain
        table read — no actor RPC on a dashboard refresh (reference:
        dashboard/modules/serve reading controller state)."""
        import json

        from ray_tpu.experimental import internal_kv

        with self._lock:
            status = {
                "running": True,
                "deployments": {
                    name: {"num_replicas": len(st["replicas"]),
                           "goal": st.get("goal_replicas", 0),
                           "version": st["version"],
                           "max_ongoing_requests":
                               st["config"]["max_ongoing_requests"],
                           "max_queued_requests":
                               st["config"].get("max_queued_requests", -1),
                           "overload": self._overload_total(st)}
                    for name, st in self._deployments.items()},
                "routes": dict(self._routes),
                "apps": dict(self._apps),
            }
        # dedup BEFORE stamping the time: an idle serve cluster must not
        # re-write the KV (and re-dirty GCS persistence) every second
        blob = json.dumps(status).encode()
        if blob != getattr(self, "_last_status_blob", None):
            if self._stop.is_set():
                # racing shutdown(): its KV delete must be the LAST write,
                # or a stale running=true entry survives the controller
                return
            self._last_status_blob = blob
            status["ts"] = time.time()
            internal_kv._internal_kv_put(
                b"status", json.dumps(status).encode(), namespace="serve")

    def _reconcile_loop(self):
        n = 0
        while not self._stop.is_set():
            try:
                self._autoscale_once()
                self._reconcile_once()
                self._confirm_starting_once()
                self._prune_dead_replicas()
                self._drain_migrate_once()
                if n % 10 == 9:
                    self._health_check_once()
                self._publish_status()
            except Exception:
                pass
            n += 1
            self._stop.wait(1.0)

    def ping(self) -> bool:
        return True


def get_controller():
    from ray_tpu.actor import get_actor_or_none

    handle = get_actor_or_none(CONTROLLER_NAME)
    if handle is None:
        handle = ServeController.options(get_if_exists=True).remote()
        ray_tpu.get(handle.ping.remote(), timeout=60)
    return handle
