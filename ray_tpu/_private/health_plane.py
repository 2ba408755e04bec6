"""HealthMonitor: the driving loop of the hardware health plane.

``ray_tpu.util.health`` owns the pure math (median/MAD outlier test,
hysteresis, signal extractors, verdict records); this module owns the
*loop* that turns passively-published ledgers into node verdicts and
actuates them:

1. **Passive scoring** (every ``health_monitor_interval_s``): read the
   per-rank StepLedger records (KV namespace ``"train"``, key
   ``step_breakdown/<group>/<rank>``) and score each group with
   :func:`~ray_tpu.util.health.score_step_records` — the straggler is
   the rank with outlier *own time* whose ``collective_wait`` is below
   the group median (everyone waits for it; it waits for nobody).
   Collective supervision records corroborate (per-rank completed-seq
   lag, in-flight op age) and map ranks to nodes; per-edge channel
   latencies ride the step records as context evidence.
2. **Active confirmation** (on SUSPECT, after
   ``health_suspect_windows`` consecutive outlier windows): run a small
   timed probe — matmul loop threaded through the ``health.probe``
   fault site, an ICI ``ppermute`` ping where this worker already runs
   a multi-device jax backend, and the deterministic SDC canary — on
   the suspect node AND a healthy reference node.  Suspect/reference
   elapsed ratio >= ``health_probe_factor`` confirms *slow*; a canary
   digest mismatch confirms *corrupting* (hardware, final).  A probe
   that times out on the suspect while the reference answered is
   confirmation by silence.
3. **Quarantine** (on CONFIRMED): the GCS ``set_node_health`` verb
   moves the node to QUARANTINED — excluded from new placement and
   ``available_resources``, and immediately drained
   (``health_quarantine_drain_deadline_s``) so the train controller
   takes its **no-charge** checkpoint-restart and re-meshes off the
   sick node while the autoscaler provisions a replacement.
   Hardware-confirmed cases ride ``hw_confirmed`` so the eventual death
   is FINAL (``report_node_failure`` semantics).

An optional **probe sweep** leg (``probe_sweep=True``) periodically
probes *every* alive node and MAD-tests the elapsed times across nodes
— detection that needs no train group at all (the production-day
crucible runs it): a degraded node is an outlier against its peers, and
any canary mismatch quarantines immediately (SDC is binary, no
hysteresis).

Everything the monitor decides is published as
:class:`~ray_tpu.util.health.HealthVerdict` records (KV namespace
``"health"``) for ``util.state.list_node_health`` / ``raytpu health`` /
the dashboard ``/api/health``, and counted on ``health_*`` metrics.
Detection timestamps ride the verdicts (``suspect_ts`` /
``quarantine_ts``) so benches can report detection-to-recovery time.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu._private.config import config
from ray_tpu.util import health as H
from ray_tpu.util.fault_injection import fault_point

logger = logging.getLogger(__name__)

_STEP_PREFIX = "step_breakdown/"
_COLLECTIVE_PREFIX = "collective/"


def _probe_payload(n: int = 96, iters: int = 30, seed: int = 7) -> Dict:
    """The active probe body, run as a task pinned to the probed node.

    Three measurements in one round-trip: a timed small matmul loop
    threaded through the ``health.probe`` fault site (so rehearsed
    degradation — the ``slow`` kind armed on the node — shows up
    exactly like a slow chip), an ICI ``ppermute`` ring ping when this
    process already runs a multi-device jax backend (never triggers
    backend init), and the SDC canary digest (int64 modular matmul
    chain — bit-exact on every honest backend)."""
    import time as _t

    import numpy as np

    import ray_tpu
    from ray_tpu._private.accelerators import jax_backend_initialized
    from ray_tpu.util import health as _health
    from ray_tpu.util.fault_injection import fault_point as _fp

    out: Dict[str, Any] = {
        "node_id": ray_tpu.get_runtime_context().get_node_id()}
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n))
    b = rng.standard_normal((n, n))
    t0 = _t.monotonic()
    for _ in range(iters):
        a = (a @ b) / float(n)
        _fp("health.probe")
    out["elapsed_s"] = _t.monotonic() - t0
    if jax_backend_initialized():
        try:
            import jax

            devs = jax.local_devices()
            if len(devs) > 1:
                import jax.numpy as jnp

                ndev = len(devs)
                perm = [(i, (i + 1) % ndev) for i in range(ndev)]
                ping = jax.pmap(
                    lambda v: jax.lax.ppermute(v, "ring", perm),
                    axis_name="ring")
                x = jnp.ones((ndev, 128))
                ping(x).block_until_ready()  # compile outside the clock
                t1 = _t.monotonic()
                ping(x).block_until_ready()
                out["ppermute_s"] = _t.monotonic() - t1
        except Exception:  # noqa: BLE001 — ping is auxiliary evidence
            pass
    out["digest"] = _health.sdc_digest(seed=seed)
    return out


class HealthMonitor(threading.Thread):
    """Background straggler/degradation detector (driver-side).

    Start one per driver that wants automatic quarantine::

        mon = HealthMonitor()          # knobs default from config
        mon.start()
        ...
        mon.stop()

    Every threshold is constructor-overridable for tests; the
    ``probe_fn`` hook lets tests substitute the remote probe (e.g. a
    canary that lies) without a cluster."""

    def __init__(self, *,
                 interval_s: Optional[float] = None,
                 mad_threshold: Optional[float] = None,
                 suspect_windows: Optional[int] = None,
                 probe_factor: Optional[float] = None,
                 probe_timeout_s: Optional[float] = None,
                 probe_sweep: bool = False,
                 probe_sweep_every: int = 3,
                 probe_fn=None):
        super().__init__(name="health-monitor", daemon=True)
        self.interval_s = float(interval_s if interval_s is not None
                                else config.health_monitor_interval_s)
        self.mad_threshold = float(
            mad_threshold if mad_threshold is not None
            else config.health_mad_threshold)
        self.suspect_windows = int(
            suspect_windows if suspect_windows is not None
            else config.health_suspect_windows)
        self.probe_factor = float(
            probe_factor if probe_factor is not None
            else config.health_probe_factor)
        self.probe_timeout_s = float(
            probe_timeout_s if probe_timeout_s is not None
            else config.health_probe_timeout_s)
        self.probe_sweep = bool(probe_sweep)
        self.probe_sweep_every = max(1, int(probe_sweep_every))
        self._probe_fn = probe_fn
        self._stop_event = threading.Event()
        self._lock = threading.Lock()  # guards _ticks across thread+tests
        self._rank_hyst = H.HysteresisTracker(self.suspect_windows)
        self._node_hyst = H.HysteresisTracker(self.suspect_windows)
        self._quarantined: set = set()       # node_ids we actuated
        self._suspect_since: Dict[str, float] = {}   # node_id -> wall ts
        self._ticks = 0
        self.events: List[Dict[str, Any]] = []  # detection timeline
        from ray_tpu.util.metrics import Counter, Gauge

        self._m_ticks = Counter(
            "health_monitor_ticks_total",
            "passive-scoring iterations of the health monitor")
        self._m_suspects = Counter(
            "health_suspects_total",
            "subjects promoted to SUSPECT by the hysteresis gate")
        self._m_quarantines = Counter(
            "health_quarantines_total",
            "nodes moved to QUARANTINED by confirmed verdicts")
        self._m_probe_s = Gauge(
            "health_probe_seconds",
            "latest active-probe elapsed time", tag_keys=("node",))

    # ------------------------------------------------------------- control

    def stop(self, timeout: Optional[float] = 5.0) -> None:
        self._stop_event.set()
        if self.is_alive():
            self.join(timeout=timeout)

    def run(self) -> None:  # pragma: no cover - exercised via e2e tests
        while not self._stop_event.is_set():
            try:
                self.tick()
            except Exception:  # noqa: BLE001 — the monitor must survive
                logger.debug("health tick failed", exc_info=True)
            self._stop_event.wait(self.interval_s)

    def summary(self) -> Dict[str, Any]:
        """Detection timeline + outcome, for bench/chaos records.  When
        a quarantine happened, ``detection_to_quarantine_s`` is the
        SUSPECT->QUARANTINED latency the acceptance record wants."""
        with self._lock:
            ticks = self._ticks
        out: Dict[str, Any] = {
            "ticks": ticks,
            "quarantined": sorted(self._quarantined),
            "events": list(self.events),
        }
        sus = {e["node_id"]: e["t"] for e in self.events
               if e["event"] == "suspect" and e.get("node_id")}
        for e in self.events:
            if e["event"] == "quarantine":
                t0 = sus.get(e["node_id"])
                if t0 is not None:
                    out["detection_to_quarantine_s"] = round(
                        e["t"] - t0, 3)
        return out

    # ----------------------------------------------------------- main loop

    def tick(self) -> None:
        """One passive-scoring pass (public so tests can drive the
        monitor synchronously, without the thread)."""
        with self._lock:
            self._ticks += 1
            ticks = self._ticks
        self._m_ticks.inc()
        statuses = self._read_collective_statuses()
        rank_nodes = self._rank_node_map(statuses)
        step_groups = self._read_step_groups()
        for group, records in step_groups.items():
            self._score_group(group, records, statuses.get(group, []),
                              rank_nodes.get(group, {}))
        if self.probe_sweep and \
                ticks % self.probe_sweep_every == 1 % self.probe_sweep_every:
            self._sweep_nodes()

    # ------------------------------------------------------- passive reads

    def _kv_prefix(self, prefix: str, ns: str) -> Dict[str, bytes]:
        try:
            from ray_tpu.experimental.internal_kv import \
                _internal_kv_get_prefix

            return _internal_kv_get_prefix(prefix, namespace=ns) or {}
        except Exception:  # noqa: BLE001 — no cluster / mid-shutdown
            return {}

    def _read_step_groups(self) -> Dict[str, List[Dict[str, Any]]]:
        import json

        groups: Dict[str, List[Dict[str, Any]]] = {}
        for raw in self._kv_prefix(_STEP_PREFIX, "train").values():
            try:
                rec = json.loads(raw)
                groups.setdefault(str(rec["group"]), []).append(rec)
            except Exception:  # noqa: BLE001 — record mid-write
                continue
        return groups

    def _read_collective_statuses(self) -> Dict[str, List[Dict[str, Any]]]:
        import json

        from ray_tpu.util.collective.supervision import \
            aggregate_status_records

        records = []
        for raw in self._kv_prefix(_COLLECTIVE_PREFIX, "collective").values():
            try:
                records.append(json.loads(raw))
            except Exception:  # noqa: BLE001 — record mid-write
                continue
        out: Dict[str, List[Dict[str, Any]]] = {}
        for grp in aggregate_status_records(records):
            out[str(grp.get("group_name", ""))] = grp.get("members", [])
        return out

    @staticmethod
    def _rank_node_map(statuses: Dict[str, List[Dict[str, Any]]]
                       ) -> Dict[str, Dict[int, str]]:
        out: Dict[str, Dict[int, str]] = {}
        for group, members in statuses.items():
            for m in members:
                node = m.get("node_id")
                if node and m.get("rank") is not None:
                    out.setdefault(group, {})[int(m["rank"])] = node
        return out

    # ---------------------------------------------------------- rank leg

    def _score_group(self, group: str, records: List[Dict[str, Any]],
                     members: List[Dict[str, Any]],
                     rank_nodes: Dict[int, str]) -> None:
        # step records carry their publisher's node_id; collective
        # statuses refine/override (a group need not run a supervised
        # collective to get straggler coverage)
        rank_nodes = dict(rank_nodes)
        for rec in records:
            if rec.get("node_id") and rec.get("rank") is not None:
                rank_nodes.setdefault(int(rec["rank"]), rec["node_id"])
        score = H.score_step_records(records,
                                     mad_threshold=self.mad_threshold)
        population = [(group, r) for r in score["ranks"]]
        outliers = [(group, r) for r in score["suspects"]]
        promoted = self._rank_hyst.observe(outliers, population)
        if not promoted:
            return
        # corroborating signals: completed-seq lag + in-flight op ages
        seqs = {int(m["rank"]): int(m.get("last_done_seq", 0))
                for m in members if m.get("rank") is not None}
        max_seq = max(seqs.values(), default=0)
        ages = H.pending_age_lags(members)
        for _g, rank in promoted:
            node_id = rank_nodes.get(rank, "")
            if node_id in self._quarantined:
                continue
            detail = dict(score["ranks"].get(rank, {}))
            signals = {
                "own_time_z": detail.get("z"),
                "own_s": detail.get("own_s"),
                "collective_wait_s": detail.get("collective_wait_s"),
                "seq_lag": (max_seq - seqs[rank]) if rank in seqs else None,
                "pending_age_s": round(ages[rank], 3)
                if rank in ages else None,
                "windows": self.suspect_windows,
            }
            self._mark_suspect(kind="rank", subject=f"{group}/{rank}",
                               group=group, rank=rank, node_id=node_id,
                               reason="own-time outlier with low "
                                      "collective wait",
                               signals=signals)
            if node_id:
                reference = self._pick_reference(group, rank_nodes,
                                                 exclude=node_id)
                self._confirm_and_quarantine(node_id, reference,
                                             group=group, rank=rank,
                                             signals=signals)

    def _pick_reference(self, group: str, rank_nodes: Dict[int, str],
                        exclude: str) -> Optional[str]:
        """A healthy node to race the probe against: prefer one hosting
        another rank of the same group (same hardware class), else any
        other alive, non-quarantined node."""
        for _rank, node in sorted(rank_nodes.items()):
            if node and node != exclude and node not in self._quarantined:
                return node
        for n in self._alive_nodes():
            nid = n.get("node_id", "")
            if nid and nid != exclude and nid not in self._quarantined \
                    and n.get("health") != "QUARANTINED":
                return nid
        return None

    # ---------------------------------------------------------- node sweep

    def _sweep_nodes(self) -> None:
        """Probe every alive node and MAD-test the elapsed times: the
        train-free detection leg (needs >= 3 nodes for a verdict; any
        canary mismatch quarantines immediately)."""
        nodes = [n.get("node_id", "") for n in self._alive_nodes()
                 if n.get("health") != "QUARANTINED"]
        nodes = [n for n in nodes if n and n not in self._quarantined]
        if len(nodes) < 3:
            return
        results: Dict[str, Dict[str, Any]] = {}
        expected = H.sdc_digest(seed=7)
        for nid in nodes:
            res = self._run_probe(nid)
            if res is None:
                continue
            results[nid] = res
            self._m_probe_s.set(res.get("elapsed_s", 0.0),
                                tags={"node": nid[:8]})
            if res.get("digest") and res["digest"] != expected:
                # a corrupting chip: binary evidence, no hysteresis
                self._mark_suspect(
                    kind="node", subject=nid, node_id=nid,
                    reason="SDC canary digest mismatch",
                    signals={"digest": res["digest"],
                             "expected": expected})
                self._quarantine(nid, reason="SDC canary digest mismatch",
                                 hw_confirmed=True,
                                 signals={"digest": res["digest"],
                                          "expected": expected})
        if len(results) < 3:
            return
        ordered = sorted(results)
        elapsed = [results[n]["elapsed_s"] for n in ordered]
        zs = H.robust_z(elapsed)
        outliers = [n for n, z in zip(ordered, zs)
                    if z > self.mad_threshold]
        promoted = self._node_hyst.observe(outliers, ordered)
        for nid in promoted:
            if nid in self._quarantined:
                continue
            signals = {"probe_elapsed_s":
                       round(results[nid]["elapsed_s"], 4),
                       "probe_z": round(zs[ordered.index(nid)], 3),
                       "windows": self.suspect_windows}
            self._mark_suspect(kind="node", subject=nid, node_id=nid,
                               reason="probe-sweep elapsed outlier",
                               signals=signals)
            reference = min(
                (n for n in ordered if n != nid),
                key=lambda n: results[n]["elapsed_s"], default=None)
            self._confirm_and_quarantine(nid, reference, signals=signals)

    # ------------------------------------------------------- active probe

    def _run_probe(self, node_id: str) -> Optional[Dict[str, Any]]:
        """One probe round-trip against ``node_id`` (None on timeout or
        dispatch failure).  ``probe_fn`` substitutes the whole leg in
        tests."""
        if self._probe_fn is not None:
            return self._probe_fn(node_id)
        try:
            fault_point("health.probe")
            import ray_tpu
            from ray_tpu.util.scheduling_strategies import \
                NodeAffinitySchedulingStrategy

            ref = ray_tpu.remote(_probe_payload).options(
                num_cpus=0,
                scheduling_strategy=NodeAffinitySchedulingStrategy(
                    node_id, soft=False)).remote()
            return ray_tpu.get(ref, timeout=self.probe_timeout_s)
        except Exception:  # noqa: BLE001 — timeout / unschedulable
            return None

    def _confirm_and_quarantine(self, node_id: str,
                                reference: Optional[str],
                                group: str = "", rank: Optional[int] = None,
                                signals: Optional[Dict[str, Any]] = None
                                ) -> bool:
        """The SUSPECT -> CONFIRMED leg: probe suspect vs reference.
        Quarantines (and returns True) when the suspect is
        ``probe_factor`` x slower than the reference, silent while the
        reference answers, or failing the SDC canary."""
        signals = dict(signals or {})
        ref_res = self._run_probe(reference) if reference else None
        sus_res = self._run_probe(node_id)
        if ref_res is None:
            # no healthy yardstick: cannot confirm — leave SUSPECT, the
            # hysteresis streak resets and scoring continues
            self._rank_hyst.reset()
            return False
        expected = H.sdc_digest(seed=7)
        if sus_res is None:
            signals["probe"] = "timeout"
            self._quarantine(node_id, reason="probe timed out while "
                             "reference answered", group=group, rank=rank,
                             signals=signals)
            return True
        self._m_probe_s.set(sus_res.get("elapsed_s", 0.0),
                            tags={"node": node_id[:8]})
        if sus_res.get("digest") and sus_res["digest"] != expected:
            signals["digest"] = sus_res["digest"]
            signals["expected"] = expected
            self._quarantine(node_id, reason="SDC canary digest mismatch",
                             hw_confirmed=True, group=group, rank=rank,
                             signals=signals)
            return True
        ratio = sus_res.get("elapsed_s", 0.0) / max(
            ref_res.get("elapsed_s", 0.0), 1e-9)
        signals["probe_ratio"] = round(ratio, 2)
        signals["probe_suspect_s"] = round(sus_res.get("elapsed_s", 0.0), 4)
        signals["probe_reference_s"] = round(
            ref_res.get("elapsed_s", 0.0), 4)
        if "ppermute_s" in sus_res and "ppermute_s" in ref_res:
            signals["ppermute_ratio"] = round(
                sus_res["ppermute_s"] / max(ref_res["ppermute_s"], 1e-9), 2)
        if ratio >= self.probe_factor:
            self._quarantine(node_id, reason=f"probe {ratio:.1f}x slower "
                             "than reference", group=group, rank=rank,
                             signals=signals)
            return True
        # probe cleared it: false alarm — reset the streaks so a fresh
        # run of outlier windows is required before the next probe
        if rank is not None:
            self._rank_hyst.reset((group, rank))
        self._node_hyst.reset(node_id)
        return False

    # ----------------------------------------------------------- verdicts

    def _mark_suspect(self, *, kind: str, subject: str, node_id: str,
                      reason: str, signals: Dict[str, Any],
                      group: str = "", rank: Optional[int] = None) -> None:
        now = time.time()
        if node_id and node_id not in self._suspect_since:
            self._suspect_since[node_id] = now
        self._m_suspects.inc()
        self.events.append({"t": now, "event": "suspect", "kind": kind,
                            "subject": subject, "node_id": node_id,
                            "reason": reason})
        logger.warning("health: %s %s SUSPECT (%s)", kind, subject, reason)
        H.publish_health_verdict(H.HealthVerdict(
            kind=kind, subject=subject, health=H.SUSPECT, reason=reason,
            node_id=node_id, group=group, rank=rank, signals=signals,
            suspect_ts=self._suspect_since.get(node_id, now)))
        if node_id:
            self._set_node_health(node_id, "SUSPECT", reason)

    def _quarantine(self, node_id: str, *, reason: str,
                    hw_confirmed: bool = False, group: str = "",
                    rank: Optional[int] = None,
                    signals: Optional[Dict[str, Any]] = None) -> None:
        if node_id in self._quarantined:
            return
        self._quarantined.add(node_id)
        now = time.time()
        self._m_quarantines.inc()
        self.events.append({"t": now, "event": "quarantine",
                            "node_id": node_id, "reason": reason,
                            "hw_confirmed": hw_confirmed})
        logger.warning("health: node %s QUARANTINED (%s)%s", node_id[:8],
                       reason, " [hw-confirmed]" if hw_confirmed else "")
        H.publish_health_verdict(H.HealthVerdict(
            kind="node", subject=node_id, health=H.QUARANTINED,
            reason=reason, node_id=node_id, group=group, rank=rank,
            signals=dict(signals or {}), hw_confirmed=hw_confirmed,
            suspect_ts=self._suspect_since.get(node_id), quarantine_ts=now))
        self._set_node_health(node_id, "QUARANTINED", reason,
                              hw_confirmed=hw_confirmed)

    # ------------------------------------------------------------ gcs legs

    def _alive_nodes(self) -> List[Dict[str, Any]]:
        try:
            from ray_tpu._private.worker import get_global_worker

            w = get_global_worker()
            nodes = w.run_coro(w.gcs.call("get_all_nodes"))
            return [n for n in nodes if n.get("alive")]
        except Exception:  # noqa: BLE001 — no cluster
            return []

    def _set_node_health(self, node_id: str, health: str, reason: str,
                         hw_confirmed: bool = False) -> None:
        try:
            from ray_tpu._private.worker import get_global_worker

            w = get_global_worker()
            w.run_coro(w.gcs.call(
                "set_node_health", node_id=node_id, health=health,
                reason=reason, hw_confirmed=hw_confirmed))
        except Exception:  # noqa: BLE001 — verdict record still stands
            logger.debug("set_node_health(%s, %s) failed", node_id[:8],
                         health, exc_info=True)
