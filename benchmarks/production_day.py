"""Production-day macro-crucible: all planes, one cluster, scheduled chaos.

The millions-of-users rehearsal (ROADMAP "production day"): run the
three planes a production cluster carries SIMULTANEOUSLY —

- **serve**: open-loop LLM traffic against a 2-replica deployment.
  Arrivals are a seeded Poisson process; each request's latency is
  measured from its *intended* arrival time, so a stalled client thread
  cannot pause the arrival clock and launder server slowness out of the
  percentiles (coordinated omission);
- **RLHF**: the PR 8 rollout → reward → update loop, publishing weights
  live through the versioned weight-sync plane;
- **ingest**: a Ray Data job streaming blocks through the object store
  into a consumer (the training-ingest pattern, and — by design — the
  object-store contention partner for the other planes' KV commits);

then run them AGAIN under a **scheduled chaos timeline**
(``ray_tpu.util.chaos.ChaosTimeline``): drain a node, kill a serve
replica, kill a rollout actor, and flake the GCS for a window — four
distinct fault events at scripted offsets, deterministic given
``(scenario, seed)``.

Per-plane SLOs (``ray_tpu.util.slo``) are evaluated for both phases and
published as verdict records (``raytpu status`` / dashboard SLO panel);
the final bare-JSON record carries baseline-vs-chaos SLO deltas, the
executed timeline, and a span-based cross-plane interference table (PR 9
tracing: how much each plane's spans slowed inside each fault window).

Hard invariants the record gates on (``ok``):

- zero RLHF trajectory double-counts and zero unaccounted losses in
  BOTH phases (drops with accounting are expected under chaos);
- serve sheds fail FAST (p99 shed latency far under the request
  timeout) rather than riding out the deadline;
- ingest throughput recovers after every chaos event;
- every scheduled chaos event actually fired.

Usage::

    python benchmarks/production_day.py                 # tier-1 profile
    python benchmarks/production_day.py --profile full  # the slow one
    python benchmarks/production_day.py --scenario my_timeline.json
    python benchmarks/production_day.py --degrade       # health plane
    python benchmarks/production_day.py --partition     # netem layer

``--degrade`` swaps the timeline for the silent-degradation variant:
one worker node is slowed 3x (no crash, no drain notice) and the
record gates on the health plane noticing — probe-sweep detection,
quarantine through the GCS ladder, a recorded detection latency, and
ZERO quarantines in the clean baseline phase (false-positive gate).

``--partition`` swaps the timeline for the network-partition variant:
one worker node is cut off the control plane for a transient netem
window (``partition_nodes`` builtin — deterministic drop rules at the
RPC transport).  Nothing is declared dead; the gate is that all three
planes ride the partition out on the retry layer with exactly-once
accounting intact and ingest recovering.

The tier-1 miniature lives in ``tests/test_production_day.py`` and calls
:func:`run_production_day` directly.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import random
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu._private.bench_emit import emit_final_record
from ray_tpu.util import slo as slo_mod
from ray_tpu.util.chaos import ChaosTimeline

# ---------------------------------------------------------------------------
# profiles
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Profile:
    name: str = "tier1"
    seed: int = 0
    # cluster shape: head + one drainable worker node
    head_cpus: int = 8
    worker_cpus: int = 4
    # serve plane
    serve_rate_hz: float = 8.0
    serve_timeout_s: float = 5.0
    serve_replicas: int = 2
    serve_work_ms: float = 8.0
    serve_mode: str = "proxy"        # "proxy" (numpy decode) | "engine"
    # disaggregated serve plane: real LLM prefill/decode pools behind the
    # two-stage ingress instead of the monolithic PdLLM deployment — the
    # existing chaos timeline (drain / kill_replica / GCS flake) then
    # exercises KV handoffs + re-prefill fallback with no new scenario
    # code (`--disaggregated`)
    serve_disaggregated: bool = False
    max_ongoing: int = 4
    max_queued: int = 16
    # RLHF plane
    rlhf_iterations: int = 8
    rlhf_interval_s: float = 1.0     # continual-learning cadence: keeps
    #                                  the loop live across the timeline
    rollout_actors: int = 2
    rollout_batch: int = 16
    # ingest plane
    ingest_block_rows: int = 64
    ingest_blocks: int = 8
    ingest_batch_rows: int = 64
    ingest_payload_floats: int = 256
    # phase shape
    baseline_s: float = 8.0
    chaos_tail_s: float = 6.0        # keep running this long past the
    #                                  last event so recovery is visible
    drain_deadline_s: float = 10.0
    # degrade variant: silent slowdown instead of a clean kill — the
    # health plane's probe sweep must notice and quarantine
    degrade_factor: float = 3.0
    degrade_duration_s: float = 60.0
    # partition variant: cut one worker off the control plane for a
    # TRANSIENT window (well under the ~30s default death timeout) —
    # the planes must ride it out on the retry layer, exactly-once
    partition_duration_s: float = 3.0
    partition_mode: str = "symmetric"
    # SLO thresholds (None = report only); chaos phase gets looser ones
    serve_p99_s: Optional[float] = None
    serve_max_shed_rate: Optional[float] = None
    shed_fail_fast_s: float = 2.0
    rlhf_p99_step_s: Optional[float] = None
    ingest_floor_frac: float = 0.25   # chaos floor = frac x baseline rate
    ingest_recovery_s: float = 6.0

    def scenario(self) -> Dict[str, Any]:
        """The default chaos timeline: four distinct fault events."""
        return {"seed": self.seed, "events": [
            {"at": 1.5, "kind": "drain_node",
             "deadline_s": self.drain_deadline_s},
            {"at": 3.0, "kind": "kill_replica", "deployment": "pd-llm"},
            {"at": 4.5, "kind": "kill_rollout"},
            {"at": 6.0, "kind": "fault", "site": "gcs_store.call",
             "duration": 2.0, "fault": "connection"},
        ]}

    def scenario_degrade(self) -> Dict[str, Any]:
        """The degrade variant (``--degrade``): instead of clean kills,
        silently slow one worker node ``degrade_factor``x (the ``slow``
        fault on its compute + probe sites).  Nothing crashes and no
        drain notice arrives — the health plane's probe sweep has to
        NOTICE the sick node, quarantine it through the GCS ladder, and
        the SLOs must pass once the planes re-land on healthy hardware.

        One event on purpose: the quarantine itself cascades (drain,
        replica migration, rollout respawn), so a second scripted kill
        would race the health plane's own actuation for victims."""
        return {"seed": self.seed, "events": [
            {"at": 1.5, "kind": "degrade_node",
             "factor": self.degrade_factor,
             "duration": self.degrade_duration_s},
        ]}

    def scenario_partition(self) -> Dict[str, Any]:
        """The partition variant (``--partition``): drop every frame
        between one worker node and the GCS for a transient window via
        the netem layer (``partition_nodes`` builtin).  Nothing dies —
        the window is far shorter than the death timeout — so the gate
        is that all three planes ride it out on the RPC retry layer
        with exactly-once accounting intact and ingest recovering."""
        return {"seed": self.seed, "events": [
            {"at": 1.5, "kind": "partition_nodes",
             "mode": self.partition_mode,
             "duration": self.partition_duration_s},
        ]}


PROFILES = {
    "tier1": Profile(),
    # full: real tiny-LLM engine replicas, bigger everything.  Rates and
    # margins are calibrated for the shared 1-vCPU CI box all three
    # planes contend on — the hard invariants (exactly-once accounting,
    # fail-fast sheds, recovery) must hold there too, with GIL-starved
    # dispatch threads and compile bursts in the noise floor.
    "full": Profile(
        name="full", serve_rate_hz=8.0, serve_mode="engine",
        serve_work_ms=0.0, rlhf_iterations=12, rlhf_interval_s=2.0,
        rollout_batch=32,
        ingest_blocks=12, ingest_block_rows=256, ingest_batch_rows=128,
        ingest_payload_floats=512, baseline_s=20.0, chaos_tail_s=14.0,
        serve_p99_s=3.0, serve_max_shed_rate=0.5, rlhf_p99_step_s=30.0,
        shed_fail_fast_s=4.0, ingest_recovery_s=12.0,
    ),
}


# ---------------------------------------------------------------------------
# serve plane
# ---------------------------------------------------------------------------


def _build_app(profile: Profile):
    """The serve deployment, defined in a closure so cloudpickle ships
    it by value to replica workers."""
    from ray_tpu import serve

    @serve.deployment(name="pd-llm", num_replicas=profile.serve_replicas,
                      max_ongoing_requests=profile.max_ongoing,
                      max_queued_requests=profile.max_queued,
                      ray_actor_options={"resources": {"pd_replica": 1}})
    class PdLLM:
        """LLM decode proxy (or the real tiny engine): each request
        "generates" a handful of tokens' worth of compute."""

        def __init__(self, mode: str, work_ms: float, seed: int):
            import numpy as np

            self._mode = mode
            self._work_ms = work_ms
            if mode == "engine":
                from ray_tpu.llm.engine import LLMEngine
                from ray_tpu.llm import SamplingParams
                from ray_tpu.models.llama import LlamaConfig

                cfg = LlamaConfig.tiny(num_heads=4, num_kv_heads=4,
                                       num_layers=2)
                self._engine = LLMEngine(cfg, batch_slots=4, max_len=96,
                                         seed=seed)
                self._sp = SamplingParams(temperature=0.0, max_tokens=8)
                self._vocab = cfg.vocab_size
            else:
                rng = np.random.default_rng(seed)
                self._w = rng.standard_normal((256, 256)).astype(
                    np.float32)
            self._np = np

        def __call__(self, tokens: List[int]) -> Dict[str, Any]:
            np = self._np
            if self._mode == "engine":
                out = self._engine.generate(
                    [[max(3, t % self._vocab) for t in tokens]],
                    self._sp)
                return {"tokens": out[0].token_ids}
            # decode-step proxy: a few small matmuls per "token"
            x = np.asarray(tokens[:16], np.float32)
            h = np.resize(x, (256,))
            deadline = time.perf_counter() + self._work_ms / 1e3
            steps = 0
            while time.perf_counter() < deadline:
                h = np.tanh(self._w @ h)
                steps += 1
            return {"tokens": [int(abs(v) * 100) % 97
                               for v in h[:8]], "steps": steps}

    return PdLLM.bind(profile.serve_mode, profile.serve_work_ms,
                      profile.seed)


def _build_disagg_app(profile: Profile):
    """Disaggregated serve plane: tiny-engine prefill + decode pools
    behind the two-stage ingress.  Replica placement mirrors the PdLLM
    deployment (``pd_replica`` steers one decode replica onto the
    drainable worker node so the drain event migrates real serving
    capacity)."""
    from ray_tpu.llm.serving import (LLMDecodeServer, LLMDisaggIngress,
                                     LLMPrefillServer)

    ek = {"model": "tiny", "batch_slots": 4, "max_len": 96}
    prefill = LLMPrefillServer.options(
        num_replicas=1, max_ongoing_requests=profile.max_ongoing,
        max_queued_requests=profile.max_queued,
        ray_actor_options={"resources": {"pd_replica": 1}}).bind(ek)
    decode = LLMDecodeServer.options(
        num_replicas=profile.serve_replicas,
        max_ongoing_requests=profile.max_ongoing,
        max_queued_requests=profile.max_queued,
        ray_actor_options={"resources": {"pd_replica": 1}}).bind(ek)
    return LLMDisaggIngress.options(
        max_ongoing_requests=profile.max_ongoing * 2,
        max_queued_requests=profile.max_queued).bind(prefill, decode)


def _serve_body(profile: Profile, prompt: List[int]):
    """The per-request payload: raw token list for PdLLM, an LLM body
    for the disaggregated ingress."""
    if profile.serve_disaggregated:
        return {"prompt": [max(3, t % 256) for t in prompt],
                "max_tokens": 8, "temperature": 0.0}
    return prompt


def _open_loop_client(handle, profile: Profile, duration_s: float,
                      samples: List[Dict[str, Any]],
                      stop: threading.Event) -> None:
    """Seeded-Poisson open-loop client.  The arrival schedule is fixed
    up front; a slow or failed response never delays later arrivals
    (each request runs on a pool thread), and latency counts from the
    INTENDED arrival instant."""
    from ray_tpu import serve
    from ray_tpu.exceptions import BackPressureError, DeadlineExceededError

    rng = random.Random(profile.seed + 17)
    arrivals: List[float] = []
    t = 0.0
    while t < duration_s:
        t += rng.expovariate(profile.serve_rate_hz)
        if t < duration_s:
            arrivals.append(t)
    prompts = [[rng.randrange(3, 2000) for _ in range(16)]
               for _ in range(8)]
    lock = threading.Lock()

    def one(intended_wall: float, prompt: List[int]) -> None:
        outcome = "ok"
        t_dispatch = time.time()
        try:
            with serve.request_scope(timeout_s=profile.serve_timeout_s):
                handle.remote(_serve_body(profile, prompt)).result(
                    timeout=profile.serve_timeout_s)
        except BackPressureError:
            outcome = "shed"
        except DeadlineExceededError:
            outcome = "expired"
        except Exception as e:  # noqa: BLE001 — outcome IS the datum
            outcome = "expired" if "DeadlineExceeded" in repr(e) else \
                "shed" if "BackPressure" in repr(e) else "error"
        now = time.time()
        with lock:
            # latency_s from the INTENDED arrival (coordinated-omission-
            # aware: client backlog counts against the p99);
            # dispatch_latency_s from actual submission — the fail-fast
            # gate's clock, so a shed behind a saturated client pool
            # still proves the REJECTION itself was immediate
            samples.append({"t": intended_wall,
                            "latency_s": now - intended_wall,
                            "dispatch_latency_s": now - t_dispatch,
                            "outcome": outcome})

    # enough pool width that a full replica pipeline + queue can be in
    # flight concurrently without the POOL becoming the admission valve
    width = max(8, int(profile.serve_rate_hz * profile.serve_timeout_s))
    t0 = time.time()
    with ThreadPoolExecutor(max_workers=width) as pool:
        for i, at in enumerate(arrivals):
            delay = at - (time.time() - t0)
            if delay > 0 and stop.wait(delay):
                break
            if stop.is_set():
                break
            pool.submit(one, t0 + at, prompts[i % len(prompts)])


# ---------------------------------------------------------------------------
# ingest plane
# ---------------------------------------------------------------------------


def _ingest_runner(profile: Profile, batches: List[Tuple[float, int]],
                   stop: threading.Event, duration_s: float) -> None:
    """Stream synthetic blocks through Ray Data (remote map tasks →
    object store → iterator) until the phase ends, recording one
    ``(wall_ts, rows)`` point per consumed batch."""
    import numpy as np

    import ray_tpu.data as rdata

    floats = profile.ingest_payload_floats
    deadline = time.time() + duration_s
    epoch = 0
    while not stop.is_set() and time.time() < deadline:
        epoch += 1
        ds = rdata.range(profile.ingest_blocks * profile.ingest_block_rows,
                         parallelism=profile.ingest_blocks)

        def attach_payload(batch, _f=floats):
            n = len(batch["id"])
            batch["payload"] = np.ones((n, _f), np.float32)
            return batch

        ds = ds.map_batches(attach_payload,
                            batch_size=profile.ingest_block_rows)
        try:
            it = ds.iterator()
            for b in it.iter_batches(batch_size=profile.ingest_batch_rows,
                                     prefetch_batches=1):
                rows = len(b["id"])
                batches.append((time.time(), rows))
                if stop.is_set() or time.time() > deadline:
                    break
        except Exception:  # noqa: BLE001 — chaos mid-epoch: next epoch
            # a drained node can take this epoch's in-flight blocks with
            # it; recovery is starting the next epoch, which is exactly
            # what the recovery SLO measures
            time.sleep(0.2)


# ---------------------------------------------------------------------------
# chaos actions (timeline handlers)
# ---------------------------------------------------------------------------


def _make_actions(head_node_id: str, fired_log: Dict[str, Any]):
    """Timeline action handlers.  Victim choice is deterministic:
    candidates sort by id, the timeline's seeded rng picks."""
    import ray_tpu
    from ray_tpu.util.state import drain_node, list_actors

    def _kill_actor_id(actor_hex: str) -> None:
        from ray_tpu._private.worker import get_global_worker

        w = get_global_worker()
        w.run_coro(w.gcs.call("kill_actor",
                              actor_id=bytes.fromhex(actor_hex)))

    def act_drain(ev, rng):
        victims = sorted(n["node_id"] for n in ray_tpu.nodes()
                         if n.get("alive") and n["node_id"] != head_node_id)
        if not victims:
            raise RuntimeError("no drainable worker node")
        node_id = victims[ev.get("node_index", 0) % len(victims)]
        ack = drain_node(node_id, reason="production-day chaos",
                         deadline_s=ev.get("deadline_s", 10.0))
        fired_log["drained_node"] = node_id
        return {"node": node_id, "accepted": bool(ack.get("accepted"))}

    def _kill_by_class(class_name: str, rng,
                       wait_s: float = 12.0) -> Dict[str, Any]:
        # bounded wait for a live candidate: the victim plane may still
        # be spawning its actors when the scheduled offset arrives (the
        # RLHF learner pays worker spawn + jit compile first) — the kill
        # fires as soon as a victim exists, and the log records when
        deadline = time.time() + wait_s
        victims: List[str] = []
        while time.time() < deadline:
            victims = sorted(
                a["actor_id"] for a in list_actors()
                if a.get("class_name") == class_name
                and a.get("state") == "ALIVE")
            if victims:
                break
            time.sleep(0.25)
        if not victims:
            raise RuntimeError(f"no ALIVE {class_name} to kill "
                               f"(waited {wait_s}s)")
        victim = victims[rng.randrange(len(victims))]
        _kill_actor_id(victim)
        return {"killed": victim, "class": class_name,
                "candidates": len(victims)}

    def act_kill_replica(ev, rng):
        out = _kill_by_class("ReplicaActor", rng)
        fired_log["killed_replica"] = out["killed"]
        return out

    def act_kill_rollout(ev, rng):
        out = _kill_by_class("RolloutActor", rng)
        fired_log["killed_rollout"] = out["killed"]
        return out

    return {"drain_node": act_drain, "kill_replica": act_kill_replica,
            "kill_rollout": act_kill_rollout}


# ---------------------------------------------------------------------------
# span-based interference attribution
# ---------------------------------------------------------------------------

_PLANE_SPANS = (
    ("rlhf", ("rlhf.", "train.step")),
    ("control", ("lease", "task")),
)


def _classify_span(name: str) -> Optional[str]:
    for plane, prefixes in _PLANE_SPANS:
        if any(name.startswith(p) or name == p for p in prefixes):
            return plane
    return None


def _interference(spans: List[Dict[str, Any]],
                  samples: List[Dict[str, Any]],
                  executed: List[Dict[str, Any]],
                  timeline_t0: float, window_s: float = 3.0
                  ) -> List[Dict[str, Any]]:
    """For each fired chaos event, compare each plane's work inside
    ``[t_event, t_event + window_s]`` against its phase-wide norm — the
    tracing layer's answer to "which plane did this fault actually
    hurt?".  RLHF/train/control planes attribute from span durations;
    the serve plane attributes from its client samples (its request
    spans are mint-time instants, but the open-loop client measured
    every latency)."""
    by_plane: Dict[str, List[Tuple[float, float]]] = {}
    for s in spans:
        if s.get("end") is None or s.get("start") is None:
            continue
        plane = _classify_span(s.get("name", ""))
        if plane is None:
            continue
        by_plane.setdefault(plane, []).append(
            (s["start"], s["end"] - s["start"]))
    serve_pts = [(s["t"], s["latency_s"]) for s in samples
                 if s["outcome"] == "ok"]
    out = []
    for ev in executed:
        if not ev.get("ok"):
            continue
        w0 = timeline_t0 + ev["fired_at"]
        w1 = w0 + window_s
        row: Dict[str, Any] = {"event": ev["kind"], "at": ev["at"]}
        for plane, items in sorted(by_plane.items()):
            inside = [d for (t, d) in items if w0 <= t < w1]
            all_d = [d for (_t, d) in items]
            if not inside or not all_d:
                continue
            mean_in = sum(inside) / len(inside)
            mean_all = sum(all_d) / len(all_d)
            row[plane] = {
                "spans_in_window": len(inside),
                "mean_s_in_window": round(mean_in, 4),
                "mean_s_phase": round(mean_all, 4),
                "slowdown_x": round(mean_in / mean_all, 2)
                if mean_all > 0 else None,
            }
        inside = [lat for (t, lat) in serve_pts if w0 <= t < w1]
        if inside and serve_pts:
            mean_in = sum(inside) / len(inside)
            mean_all = sum(lat for _t, lat in serve_pts) / len(serve_pts)
            row["serve"] = {
                "requests_in_window": len(inside),
                "mean_latency_s_in_window": round(mean_in, 4),
                "mean_latency_s_phase": round(mean_all, 4),
                "slowdown_x": round(mean_in / mean_all, 2)
                if mean_all > 0 else None,
            }
        out.append(row)
    return out


# ---------------------------------------------------------------------------
# one phase: all three planes (optionally under a timeline)
# ---------------------------------------------------------------------------


def _run_phase(profile: Profile, phase: str,
               scenario: Optional[Dict[str, Any]],
               monitor: bool = False) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import tracing
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.rl.rlhf import RLHFConfig, RLHFLoop

    # pd_replica steers one serve replica onto the drainable worker node
    # (so the drain event actually migrates serving capacity) while the
    # head keeps headroom for the migrated replacement; pd_learner pins
    # the RLHF learner to the head so the drain exercises replica
    # migration + rollout respawn, not a full elastic train restart
    # (that composition is the rlhf_chaos drain scenario's job)
    cluster = Cluster(initialize_head=True, head_node_args={
        "num_cpus": profile.head_cpus,
        "resources": {"pd_replica": 3, "pd_learner": 1}})
    worker = cluster.add_node(num_cpus=profile.worker_cpus,
                              resources={"pd_replica": 1})
    if monitor:
        # the probe sweep needs >=3 alive nodes for a meaningful MAD
        # population (and a healthy node to re-land work on)
        cluster.add_node(num_cpus=profile.worker_cpus)
    cluster.connect()
    phase_t0 = time.time()
    samples: List[Dict[str, Any]] = []
    batches: List[Tuple[float, int]] = []
    rlhf_out: Dict[str, Any] = {}
    stop = threading.Event()
    timeline = None
    mon = None
    fired_log: Dict[str, Any] = {}
    try:
        cluster.wait_for_nodes()
        head_id = next(n["node_id"] for n in ray_tpu.nodes()
                       if "pd_learner" in (n.get("total") or {}))
        if monitor:
            from ray_tpu._private.health_plane import HealthMonitor

            # sweep-heavy posture: production_day's single-rank learner
            # publishes no >=3-rank group, so detection rides the node
            # probe sweep.  Thresholds stay at the defaults that must
            # hold on a clean cluster — the baseline phase runs the SAME
            # monitor and must produce zero quarantines.
            mon = HealthMonitor(interval_s=0.5, suspect_windows=3,
                                probe_factor=2.0, probe_timeout_s=20.0,
                                probe_sweep=True, probe_sweep_every=2)
            mon.start()
        handle = serve.run(_build_disagg_app(profile)
                           if profile.serve_disaggregated
                           else _build_app(profile))
        # warm requests: jit/actor cold start must not masquerade as
        # baseline latency.  The disaggregated topology needs several
        # per decode replica — the two-stage reservation picks the
        # least-loaded decode replica per request, so serial warm
        # requests reach every engine's compile with high probability
        warms = profile.serve_replicas * (
            3 if profile.serve_disaggregated else 1)
        for _ in range(warms):
            try:
                handle.remote(_serve_body(profile, list(range(16)))
                              ).result(timeout=120)
            except Exception:  # noqa: BLE001 — measured run will tell
                break

        duration = profile.baseline_s
        if scenario is not None:
            events = []
            for ev in scenario["events"]:
                ev = dict(ev)
                if ev.get("kind") in ("degrade_node", "partition_nodes"):
                    # never degrade/partition the head: it carries the
                    # learner, the serve clients and the monitor itself
                    ev["exclude"] = list(ev.get("exclude", [])) + [head_id]
                events.append(ev)
            timeline = ChaosTimeline(
                events, seed=scenario.get("seed", 0),
                actions=_make_actions(head_id, fired_log))
            duration = timeline.duration_s + profile.chaos_tail_s

        def rlhf_plane():
            cfg = RLHFConfig(
                iterations=profile.rlhf_iterations,
                num_rollout_actors=profile.rollout_actors,
                rollout_batch=profile.rollout_batch,
                learner_batch_size=profile.rollout_batch,
                name=f"pd-{phase}", mesh="dp",
                iteration_interval_s=profile.rlhf_interval_s,
                sample_timeout_s=60.0, respawn_budget=4,
                # the drain event targets the WORKER node; the learner
                # rides the head so the loop keeps stepping while serve
                # replicas migrate (rollout actors go wherever)
                resources_per_worker={"pd_learner": 0.25},
            )
            result = RLHFLoop(cfg).run()
            rlhf_out["error"] = None if result.error is None \
                else str(result.error)
            rlhf_out["metrics"] = dict(result.metrics or {})

        settle_budget = 25.0
        ingest_thread = threading.Thread(
            target=_ingest_runner,
            args=(profile, batches, stop, duration + settle_budget),
            name="pd-ingest", daemon=True)
        rlhf_thread = threading.Thread(target=rlhf_plane, name="pd-rlhf",
                                       daemon=True)
        ingest_thread.start()
        rlhf_thread.start()
        # chaos hits a RUNNING production day, not a booting one: wait
        # (bounded) for the data plane's first batch so the ingest
        # recovery clock measures fault recovery, not pipeline ramp-up
        # (a drain that fires before the first batch produced negative
        # event offsets and charged epoch warm-up as "recovery time")
        settle_deadline = time.time() + settle_budget
        while not batches and time.time() < settle_deadline:
            time.sleep(0.1)
        client_thread = threading.Thread(
            target=_open_loop_client,
            args=(handle, profile, duration, samples, stop),
            name="pd-serve-client", daemon=True)
        client_thread.start()
        threads = [client_thread, rlhf_thread, ingest_thread]
        timeline_t0 = time.time()
        if timeline is not None:
            timeline.start()
        # the serve client paces the phase; the RLHF loop is bounded by
        # its iteration count (join generously — chaos restarts cost)
        threads[0].join(timeout=duration + 60.0)
        if timeline is not None:
            timeline.join()
        threads[1].join(timeout=max(120.0, duration * 4))
        stop.set()
        threads[2].join(timeout=30.0)
        alive = [t.name for t in threads if t.is_alive()]
        tracing.flush()
        spans = tracing.collect_cluster_spans()
        overload = {}
        try:
            from ray_tpu.util.state import list_serve_deployments

            ingress = "LLMIngress" if profile.serve_disaggregated \
                else "pd-llm"
            for d in list_serve_deployments():
                if d.get("name") == ingress:
                    overload = d.get("overload") or {}
        except Exception:  # noqa: BLE001 — status is best-effort
            pass
        return {
            "phase": phase,
            "t0": phase_t0,
            "timeline_t0": timeline_t0,
            "planned": timeline.plan() if timeline else [],
            "duration_s": round(time.time() - phase_t0, 2),
            "samples": samples,
            "batches": batches,
            "rlhf": rlhf_out,
            "overload": overload,
            "spans": spans,
            "executed": timeline.executed() if timeline else [],
            "fired_log": fired_log,
            "health": mon.summary() if mon is not None else None,
            "stuck_threads": alive,
        }
    finally:
        stop.set()
        if mon is not None:
            try:
                mon.stop()
            except Exception:  # noqa: BLE001 — teardown must proceed
                pass
        if timeline is not None:
            try:
                timeline.stop()
            except Exception:  # noqa: BLE001 — teardown must proceed
                pass
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001
            pass
        cluster.shutdown()


# ---------------------------------------------------------------------------
# evaluation + record
# ---------------------------------------------------------------------------


def _evaluate_phase(profile: Profile, ph: Dict[str, Any],
                    baseline_rate: Optional[float]) -> Dict[str, Any]:
    phase = ph["phase"]
    chaos_ts = [ph["timeline_t0"] + e["fired_at"]
                for e in ph["executed"] if e.get("ok")]
    serve_slo = slo_mod.ServeSLO(
        name="pd-llm", p99_latency_s=profile.serve_p99_s,
        max_shed_rate=profile.serve_max_shed_rate,
        shed_fail_fast_s=profile.shed_fail_fast_s)
    rlhf_slo = slo_mod.RLHFSLO(name=f"pd-{phase}",
                               p99_step_time_s=profile.rlhf_p99_step_s)
    floor = None
    if baseline_rate:
        floor = round(baseline_rate * profile.ingest_floor_frac, 2)
    ingest_slo = slo_mod.IngestSLO(
        name=f"pd-{phase}", min_rows_per_s=floor,
        recovery_s=profile.ingest_recovery_s if chaos_ts else None)

    m = ph["rlhf"].get("metrics") or {}
    ledger_counts = None
    if "trajectories_produced" in m:
        ledger_counts = {
            "produced": m.get("trajectories_produced", 0),
            "consumed": m.get("trajectories_consumed", 0),
            "dropped": m.get("trajectories_dropped", 0),
            "duplicates_rejected": m.get("duplicates_rejected", 0),
        }
    verdicts = [
        slo_mod.evaluate_serve(serve_slo, ph["samples"],
                               overload=ph["overload"], phase=phase),
        slo_mod.evaluate_rlhf(rlhf_slo, m.get("iteration_walls_s"),
                              ledger_counts, phase=phase),
        slo_mod.evaluate_ingest(ingest_slo, ph["batches"],
                                chaos_events_at=chaos_ts, phase=phase),
    ]
    for v in verdicts:
        slo_mod.publish_verdict(v)
    return {"verdicts": [v.to_dict() for v in verdicts],
            "summary": slo_mod.summarize(verdicts)}


def _plane_deltas(base_ev: Dict[str, Any],
                  chaos_ev: Dict[str, Any]) -> Dict[str, Any]:
    """baseline-vs-chaos per-plane metric deltas (the record headline)."""
    base = {v["plane"]: v for v in base_ev["verdicts"]}
    chaos = {v["plane"]: v for v in chaos_ev["verdicts"]}
    out: Dict[str, Any] = {}
    for plane in sorted(set(base) | set(chaos)):
        b = (base.get(plane) or {}).get("metrics", {})
        c = (chaos.get(plane) or {}).get("metrics", {})
        row: Dict[str, Any] = {}
        for key in ("p99_latency_s", "shed_rate", "p99_step_s",
                    "rows_per_s"):
            if key in b or key in c:
                row[key] = {"baseline": b.get(key), "chaos": c.get(key)}
        row["status"] = {
            "baseline": (base.get(plane) or {}).get("status"),
            "chaos": (chaos.get(plane) or {}).get("status"),
        }
        out[plane] = row
    return out


def _invariants(profile: Profile, chaos_ph: Dict[str, Any],
                chaos_ev: Dict[str, Any],
                base_ph: Optional[Dict[str, Any]] = None) -> List[str]:
    """The acceptance gates; returns human-readable failures."""
    problems: List[str] = []
    # degrade variant: the silently-slowed node must have been NOTICED —
    # quarantined through the health ladder, with the detection latency
    # recorded — and a clean baseline must never have quarantined anyone
    degraded = [e for e in chaos_ph["executed"]
                if e.get("ok") and e.get("kind") == "degrade_node"]
    if degraded:
        h = chaos_ph.get("health") or {}
        victims = {(e.get("result") or {}).get("node") for e in degraded}
        victims.discard(None)
        quarantined = set(h.get("quarantined") or [])
        if not victims & quarantined:
            problems.append(
                f"degraded node never quarantined: degraded={victims}, "
                f"quarantined={quarantined}, events={h.get('events')}")
        elif "detection_to_quarantine_s" not in h:
            problems.append(
                "quarantine happened but no detection_to_quarantine_s "
                f"in the health summary: {h}")
    if base_ph is not None:
        base_h = base_ph.get("health") or {}
        base_bad = sorted(
            {e.get("node_id") or e.get("subject") or "?"
             for e in base_h.get("events") or []
             if e.get("event") in ("suspect", "quarantine")})
        if base_bad:
            problems.append(
                f"health plane raised verdicts on the CLEAN baseline "
                f"phase (false positive): {base_bad}, "
                f"events={base_h.get('events')}")
    # every SCHEDULED event fired (the scenario's own count, not a
    # hardcoded 4 — custom --scenario files have their own timelines)
    expected = len(chaos_ph.get("planned") or [])
    fired_ok = [e for e in chaos_ph["executed"] if e.get("ok")]
    if len(fired_ok) < expected:
        problems.append(
            f"only {len(fired_ok)}/{expected} chaos events fired "
            f"cleanly: {chaos_ph['executed']}")
    # a plane that produced NO evaluable evidence in the chaos phase is
    # a failure of the crucible, not a pass — silence is not compliance
    for v in chaos_ev["verdicts"]:
        if v["status"] == slo_mod.DEGRADED:
            problems.append(
                f"{v['plane']} plane unevaluable under chaos: "
                f"{v['degraded_reason']}")
    # partition variant: the event must actually have cut a link — a
    # victim chosen and drop rules armed on at least one endpoint (the
    # transient window then stresses the retry layer; the exactly-once
    # and recovery gates below do the rest)
    for e in chaos_ph["executed"]:
        if not (e.get("ok") and e.get("kind") == "partition_nodes"):
            continue
        res = e.get("result") or {}
        if not res.get("node"):
            problems.append(f"partition event picked no victim: {res}")
        elif not any((res.get("armed") or {}).values()):
            problems.append(
                f"partition rules armed on no endpoint: {res}")
    # RLHF: exactly-once trajectory accounting through the chaos
    if chaos_ph["rlhf"].get("error"):
        problems.append(f"rlhf loop failed: {chaos_ph['rlhf']['error']}")
    m = chaos_ph["rlhf"].get("metrics") or {}
    if m.get("duplicates_rejected", 0) != 0:
        problems.append(
            f"trajectory double-counts: {m['duplicates_rejected']}")
    # ledger semantics: produced batches must ALL be consumed (drops are
    # failed sample attempts, counted separately with a reason)
    lost = (m.get("trajectories_produced", 0)
            - m.get("trajectories_consumed", 0))
    if lost != 0:
        problems.append(f"unaccounted trajectories: {lost}")
    # serve: sheds fail fast, never ride out the client timeout
    # (dispatch-relative: a shed queued behind a saturated client pool
    # is the pool's latency, not the overload layer's)
    shed_lat = [s.get("dispatch_latency_s", s["latency_s"])
                for s in chaos_ph["samples"]
                if s["outcome"] in ("shed",)]
    if shed_lat:
        p99_shed = slo_mod.quantile(shed_lat, 0.99)
        if p99_shed > profile.shed_fail_fast_s:
            problems.append(
                f"sheds not fail-fast: p99 shed latency {p99_shed:.2f}s "
                f"> {profile.shed_fail_fast_s}s")
    # ingest: recovered after each event (the ingest verdict's recovery
    # violations are exactly this check)
    for v in chaos_ev["verdicts"]:
        if v["plane"] == "ingest":
            for viol in v["violations"]:
                if viol["metric"].startswith("recovery_after"):
                    problems.append(
                        f"ingest did not recover: {viol}")
    if chaos_ph.get("stuck_threads"):
        problems.append(f"plane threads stuck: {chaos_ph['stuck_threads']}")
    return problems


def run_production_day(profile: Profile = None,
                       scenario: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, Any]:
    """Run baseline + chaos phases; returns the final record (also the
    entry point for the tier-1 miniature and the slow full-size test)."""
    profile = profile or PROFILES["tier1"]
    scenario = scenario or profile.scenario()
    # a degrade event puts the health plane in the loop: run the monitor
    # in BOTH phases (the clean baseline doubles as the false-positive
    # gate) on a 3-node cluster so the probe sweep has a MAD population
    monitor = any(e.get("kind") == "degrade_node"
                  for e in scenario.get("events") or [])
    base_ph = _run_phase(profile, "baseline", None, monitor=monitor)
    base_ev = _evaluate_phase(profile, base_ph, None)
    base_rate = None
    for v in base_ev["verdicts"]:
        if v["plane"] == "ingest":
            base_rate = v["metrics"].get("rows_per_s")
    chaos_ph = _run_phase(profile, "chaos", scenario, monitor=monitor)
    chaos_ev = _evaluate_phase(profile, chaos_ph, base_rate)
    problems = _invariants(profile, chaos_ph, chaos_ev, base_ph=base_ph)
    record = {
        "benchmark": "production_day",
        "profile": profile.name,
        "ok": not problems,
        "problems": problems,
        "planes": _plane_deltas(base_ev, chaos_ev),
        "slo": {"baseline": base_ev["summary"],
                "chaos": chaos_ev["summary"]},
        "verdicts": {"baseline": base_ev["verdicts"],
                     "chaos": chaos_ev["verdicts"]},
        "timeline": {
            # the REAL chaos timeline's plan (no dummy re-construction
            # whose action registry could drift out of sync)
            "planned": [{k: e[k] for k in ("at", "kind")}
                        for e in chaos_ph["planned"]],
            "executed": [{k: e.get(k) for k in
                          ("at", "fired_at", "kind", "ok", "result",
                           "error")}
                         for e in chaos_ph["executed"]],
        },
        "health": {"baseline": base_ph.get("health"),
                   "chaos": chaos_ph.get("health")},
        "interference": _interference(
            chaos_ph["spans"], chaos_ph["samples"],
            chaos_ph["executed"], chaos_ph["timeline_t0"]),
        "serve_traffic": {
            "baseline": {"offered": len(base_ph["samples"]),
                         "overload": base_ph["overload"]},
            "chaos": {"offered": len(chaos_ph["samples"]),
                      "overload": chaos_ph["overload"]},
        },
    }
    return record


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--profile", default="tier1", choices=sorted(PROFILES))
    ap.add_argument("--scenario", default=None,
                    help="JSON scenario file overriding the built-in "
                         "timeline (docs/fault_tolerance.md)")
    ap.add_argument("--disaggregated", action="store_true",
                    help="serve plane runs the disaggregated "
                         "prefill/decode topology (KV handoffs over the "
                         "channel plane) under the same chaos timeline")
    ap.add_argument("--degrade", action="store_true",
                    help="chaos phase silently slows one worker node "
                         "instead of killing things; the health plane "
                         "must detect and quarantine it "
                         "(docs/fault_tolerance.md, health plane)")
    ap.add_argument("--partition", action="store_true",
                    help="chaos phase cuts one worker off the control "
                         "plane for a transient netem window; the "
                         "planes must ride it out on the retry layer "
                         "(docs/fault_tolerance.md, partitions)")
    args = ap.parse_args()
    profile = PROFILES[args.profile]
    if args.disaggregated:
        # real engine replicas: give the open-loop client headroom over
        # the proxy-calibrated timeout (decode batches + two-stage hops)
        profile = dataclasses.replace(
            profile, serve_disaggregated=True,
            serve_timeout_s=max(profile.serve_timeout_s, 10.0))
    scenario = None
    if args.degrade:
        scenario = profile.scenario_degrade()
    if args.partition:
        scenario = profile.scenario_partition()
        # the partition window itself is dead air, not recovery time:
        # ingest cannot make progress against a cut control plane, so
        # the recovery clock only really starts once the link heals
        profile = dataclasses.replace(
            profile, ingest_recovery_s=(profile.ingest_recovery_s
                                        + profile.partition_duration_s))
    if args.scenario:
        with open(args.scenario) as f:
            scenario = json.load(f)
    record = run_production_day(profile, scenario)
    emit_final_record(record)
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
