"""DeploymentHandle + power-of-two-choices replica routing.

Reference: ``python/ray/serve/handle.py`` (``DeploymentHandle.remote :709``)
and ``serve/_private/replica_scheduler/pow_2_scheduler.py``
(``PowerOfTwoChoicesReplicaScheduler :52``, ``choose_replica_for_request
:816``): sample two replicas, probe queue lengths (with a short-lived
cache), send to the shorter queue.

Overload protection (reference: ``serve/_private/router.py``
queue-length-capped scheduling): the router is the serving path's
admission valve.  It tracks its own dispatched-but-unfinished count per
replica and never sends a replica more than ``max_ongoing_requests``;
excess requests wait in a bounded router-side queue
(``max_queued_requests``), and once THAT is full new arrivals fail fast
with ``BackPressureError`` instead of piling up without limit behind a
stalled replica.  Requests carry a deadline (``serve.context``): one
whose budget is already spent is rejected before dispatch rather than
executed for a client that stopped waiting.
"""

from __future__ import annotations

import collections
import random
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

import ray_tpu
from ray_tpu._private import resilience
from ray_tpu.exceptions import BackPressureError, DeadlineExceededError
from ray_tpu.serve.context import OverloadStats, current_context
from ray_tpu.util.fault_injection import fault_point


def _assign_retryable(err: BaseException) -> bool:
    """Dispatch-time failures worth a refresh+retry: transport loss to a
    replica (it died; the controller will repopulate the set) and the
    empty-replica window during a rolling update.  Application errors
    raised by the replica's own code surface through the returned ref,
    not here, so anything else at dispatch time is fatal.  Overload
    verdicts are explicitly NON-retryable: a shed (``BackPressureError``)
    means the queue is full — re-entering it from inside the router would
    defeat the bound (the PROXY owns the retry decision, via
    ``Retry-After``) — and a spent deadline (``DeadlineExceededError``)
    can only get more spent."""
    if isinstance(err, (BackPressureError, DeadlineExceededError)):
        return False
    return resilience.is_retryable(err) or "has no replicas" in str(err)


class DeploymentResponse:
    """Future-like result of handle.remote() (reference DeploymentResponse)."""

    def __init__(self, ref):
        self._ref = ref

    def result(self, timeout: Optional[float] = None):
        return ray_tpu.get(self._ref, timeout=timeout)

    @property
    def ref(self):
        return self._ref


class Router:
    """Pow-2 replica chooser with a queue-length cache and a bounded
    admission queue."""

    QUEUE_LEN_CACHE_S = 2.0
    # dispatch-time affinity entries are provisional for this long: the
    # replica only reports a model as loaded AFTER the load finishes, so
    # a probe racing a cold load must not strip the entry (that flap sent
    # concurrent same-model requests to different replicas, each paying a
    # duplicate load — exactly what model-aware routing exists to avoid)
    MODEL_LOAD_GRACE_S = 30.0
    # deployment-version polls ride the request path; uncapped they cost
    # one controller RPC PER REQUEST (measured: the largest serve-path
    # overhead after the replica call itself on a 1-vCPU box)
    VERSION_CHECK_INTERVAL_S = 0.5
    # how long a queued request sleeps between capacity re-checks (a
    # completion notifies the condition immediately; this only bounds the
    # staleness of the replica-set view while waiting)
    QUEUE_POLL_S = 0.05
    # an unchanged overload snapshot is still re-pushed this often so the
    # controller can tell idle-but-alive reporters from exited ones
    # (must stay well under Controller.OVERLOAD_RETIRE_S)
    REPORT_HEARTBEAT_S = 5.0

    def __init__(self, deployment_name: str, controller):
        self._deployment = deployment_name
        self._controller = controller
        self._replicas: List[Any] = []
        # concurrency knobs are SEEDED FROM THE DEPLOYMENT CONFIG by the
        # refresh() below, never from a magic default: early traffic
        # against a low-concurrency deployment must not over-dispatch
        # during the pre-refresh window
        self._max_ongoing: Optional[int] = None
        self._max_queued: int = -1
        self._version = -1
        self._qlen_cache: Dict[str, tuple] = {}  # actor id -> (len, expiry)
        # model-aware routing (reference multiplex.py): model id ->
        # replica cache keys that recently served / reported that model
        self._mux_affinity: Dict[str, List[str]] = {}
        # (model id, replica key) -> monotonic time of last dispatch;
        # consulted by _sync_models to keep provisional entries alive
        self._mux_dispatch_t: Dict[tuple, float] = {}
        self._lock = threading.Lock()
        # admission state: replica key -> dispatched-but-unfinished count,
        # resolved by the completion watcher; waiters block on the
        # condition until a slot frees (or their deadline expires)
        self._cond = threading.Condition(self._lock)
        self._inflight: Dict[str, int] = {}
        self._outstanding: Dict[Any, str] = {}  # ref -> replica key
        self._queued = 0
        # slot releases from _SlotReleasingStream.__del__: a GC finalizer
        # must not take the router lock (it could fire while THIS thread
        # holds it), so it appends here (deque.append is atomic) and the
        # next assign / watcher pass drains it
        self._orphan_releases: collections.deque = collections.deque()
        self._stopped = threading.Event()
        self._overload = OverloadStats(deployment_name)
        self._reporter_id = uuid.uuid4().hex[:12]
        self._last_reported: Optional[Dict[str, int]] = None
        self._last_report_t = 0.0
        self._rng = random.Random()
        self._last_version_check = 0.0
        self.refresh()
        # the completion watcher doubles as the overload-report
        # heartbeat, so it starts eagerly: a router whose traffic was
        # ALL shed (nothing ever dispatched) must still get its final
        # counters to the controller after the burst ends
        self._watcher = threading.Thread(
            target=self._watch_loop, daemon=True,
            name=f"serve-router-watch-{deployment_name}")
        self._watcher.start()

    @property
    def overload_stats(self) -> OverloadStats:
        return self._overload

    def refresh(self):
        # bounded: refresh runs on dispatch/watcher control threads — a
        # dead controller must surface as an error, not a permanent hang
        info = ray_tpu.get(
            self._controller.get_deployment_info.remote(self._deployment),
            timeout=30)
        if info is None:
            raise KeyError(f"no deployment {self._deployment!r}")
        with self._lock:
            self._replicas = info["replicas"]
            self._max_ongoing = info["max_ongoing_requests"]
            self._max_queued = info.get("max_queued_requests", -1)
            self._version = info["version"]
            self._qlen_cache.clear()  # cache keys are replica ids; drop stale
            self._cond.notify_all()  # new replicas may mean new capacity

    def _maybe_refresh(self):
        # long-poll analog: cheap version check piggybacked on the probe
        # path — throttled so the hot path isn't one controller RPC per
        # request (a replica-set change waits at most the interval)
        now = time.monotonic()
        with self._lock:
            if now - self._last_version_check < self.VERSION_CHECK_INTERVAL_S:
                return
            self._last_version_check = now
        try:
            v = ray_tpu.get(
                self._controller.get_version.remote(self._deployment),
                timeout=5)
        except Exception:
            return
        if v != self._version:
            try:
                self.refresh()
            except TimeoutError:
                return  # opportunistic refresh: the next interval retries

        self._report_overload()

    def _report_overload(self):
        """Snapshot-deduped fire-and-forget push of this router's
        shed/expired/cancelled/queued counters to the controller, which
        aggregates across reporter processes into the published serve
        status.  Called from the request path (rides _maybe_refresh) AND
        from the completion watcher — the watcher's calls are what land
        the final drained-to-zero ``queued`` gauge after traffic stops
        (a request-path-only report would leave the last mid-burst
        snapshot, with its phantom queued count, published forever)."""
        snap = self._overload.snapshot()
        now = time.monotonic()
        # dedup unchanged snapshots, but never go silent longer than the
        # heartbeat: the controller retires reporters it hasn't heard
        # from (folding their counters into a base) — a live-but-idle
        # router must keep proving it's alive or its eventual next
        # report would double-count against the folded base
        if (snap == self._last_reported
                and now - self._last_report_t < self.REPORT_HEARTBEAT_S):
            return
        self._last_reported = snap
        self._last_report_t = now
        try:
            self._controller.report_overload.remote(
                self._deployment, self._reporter_id, snap)
        except Exception:  # noqa: BLE001 — visibility never fails a request
            pass

    def _cache_key(self, replica) -> str:
        return replica._actor_id.hex()

    def _probe(self, replica) -> int:
        key = self._cache_key(replica)
        now = time.monotonic()
        with self._lock:
            hit = self._qlen_cache.get(key)
            if hit and hit[1] > now:
                return hit[0]
        try:
            # short: the probe rides the DISPATCH path, so an unreachable
            # replica (dying mid-drain, wedged in a long GIL hold) must
            # cost one bounded stall per cache window, not 5s per probe —
            # under open-loop load the old timeout alone inflated p99 by
            # seconds whenever a replica was killed (production-day
            # crucible).  The failure result is negative-cached below for
            # QUEUE_LEN_CACHE_S like any other probe answer.
            info = ray_tpu.get(replica.probe.remote(), timeout=1.5)
            qlen = info["qlen"]
            self._sync_models(key, info.get("models") or [])
        except Exception:
            qlen = 1 << 30  # unreachable replica: never prefer it
        with self._lock:
            self._qlen_cache[key] = (qlen, now + self.QUEUE_LEN_CACHE_S)
        return qlen

    def _sync_models(self, key: str, models: List[str]) -> None:
        """Reconcile the affinity map with a replica's AUTHORITATIVE
        loaded-model report: models it evicted stop routing to it, and
        the map is bounded (stale ids age out).  Entries dispatched
        within MODEL_LOAD_GRACE_S survive an "absent" report — the load
        the dispatch triggered may simply not have finished yet."""
        now = time.monotonic()
        with self._lock:
            loaded = set(models)
            for mid, lst in list(self._mux_affinity.items()):
                if mid in loaded:
                    if key not in lst:
                        lst.append(key)
                    self._mux_dispatch_t.pop((mid, key), None)
                elif key in lst:
                    t = self._mux_dispatch_t.get((mid, key))
                    if t is not None and now - t < self.MODEL_LOAD_GRACE_S:
                        continue  # provisional: cold load in progress
                    lst.remove(key)
                    self._mux_dispatch_t.pop((mid, key), None)
                    if not lst:
                        del self._mux_affinity[mid]
            while len(self._mux_affinity) > 1024:
                mid = next(iter(self._mux_affinity))
                for k in self._mux_affinity.pop(mid):
                    self._mux_dispatch_t.pop((mid, k), None)
            if len(self._mux_dispatch_t) > 8192:
                self._mux_dispatch_t = {
                    k: t for k, t in self._mux_dispatch_t.items()
                    if now - t < self.MODEL_LOAD_GRACE_S}

    # ------------------------------------------------------------- admission

    def _replicas_snapshot(self) -> List[Any]:
        with self._lock:
            reps = list(self._replicas)
        if not reps:
            self._maybe_refresh()
            with self._lock:
                reps = list(self._replicas)
            if not reps:
                raise RuntimeError(
                    f"deployment {self._deployment!r} has no replicas")
        return reps

    def _capacity_candidates(self, reps: List[Any]) -> List[Any]:
        """Replicas this router may still dispatch to (its own
        dispatched-but-unfinished count is under max_ongoing)."""
        with self._lock:
            limit = self._max_ongoing or 1
            return [r for r in reps
                    if self._inflight.get(self._cache_key(r), 0) < limit]

    def _acquire_replica(self, model_id: str, ctx):
        """Admission valve: pick a replica with spare capacity and reserve
        one slot on it.  When every replica is saturated the caller waits
        in the bounded router queue; a full queue sheds the request with
        ``BackPressureError`` and a spent deadline drops it with
        ``DeadlineExceededError`` — both BEFORE any replica sees it."""
        queued = False
        try:
            while True:
                self._drain_orphans()
                reps = self._replicas_snapshot()
                candidates = self._capacity_candidates(reps)
                if candidates:
                    pick = self.choose_replica(model_id, candidates)
                    with self._lock:
                        key = self._cache_key(pick)
                        if self._inflight.get(key, 0) < (self._max_ongoing
                                                         or 1):
                            self._inflight[key] = \
                                self._inflight.get(key, 0) + 1
                            return pick
                    continue  # lost the reservation race: re-pick
                # saturated: join (or keep) a bounded wait-queue slot
                with self._cond:
                    if not queued:
                        if 0 <= self._max_queued <= self._queued:
                            self._overload.note_shed()
                            raise BackPressureError(
                                deployment=self._deployment,
                                queued=self._queued,
                                limit=self._max_queued,
                                retry_after_s=self._retry_after_hint())
                        self._queued += 1
                        self._overload.note_queued(+1)
                        queued = True
                    if ctx is not None and ctx.expired():
                        self._overload.note_expired()
                        raise DeadlineExceededError(
                            request_id=ctx.request_id,
                            deployment=self._deployment,
                            stage="router-queue",
                            overrun_s=ctx.overrun_s())
                    wait_s = self.QUEUE_POLL_S
                    if ctx is not None:
                        remaining = ctx.remaining_s()
                        if remaining is not None:
                            wait_s = max(0.0, min(wait_s, remaining))
                    self._cond.wait(timeout=wait_s)
                self._maybe_refresh()  # autoscale may have added capacity
        finally:
            if queued:
                with self._cond:
                    self._queued -= 1
                    self._overload.note_queued(-1)

    def _retry_after_hint(self) -> float:
        """Rough time for one queue position to free: assume the oldest
        in-flight request completes within a second — intentionally a
        HINT (HTTP Retry-After), not a promise."""
        return 1.0

    def _release(self, key: str):
        with self._cond:
            n = self._inflight.get(key, 0)
            if n <= 1:
                self._inflight.pop(key, None)
            else:
                self._inflight[key] = n - 1
            self._cond.notify_all()

    def _drain_orphans(self):
        while True:
            try:
                key = self._orphan_releases.popleft()
            except IndexError:
                return
            self._release(key)

    def stop(self):
        """Settle the watcher thread (serve.shutdown); the router object
        is being dropped and must not pin a daemon thread forever."""
        self._stopped.set()
        with self._cond:
            self._cond.notify_all()

    def _track_completion(self, ref, key: str):
        """Register a dispatched ref with the completion watcher, which
        releases the replica slot when the task finishes (success, error,
        cancellation, or replica death — ``wait`` resolves them all)."""
        with self._cond:
            self._outstanding[ref] = key
            self._cond.notify_all()

    def _watch_loop(self):
        while not self._stopped.is_set():
            self._drain_orphans()
            self._report_overload()  # outside the lock: settles counters
            with self._cond:
                if not self._outstanding:
                    self._cond.wait(timeout=5.0)
                refs = list(self._outstanding)
            if not refs:
                continue  # idle tick: loop back (report) and wait again
            try:
                # num_returns=1: wake the moment the FIRST watched ref
                # resolves (a batch drains through instant follow-up
                # waits) instead of spinning at QUEUE_POLL_S granularity;
                # the timeout only bounds how long a ref dispatched AFTER
                # this wait started goes unwatched
                ready, _ = ray_tpu.wait(
                    refs, num_returns=1, timeout=0.1, fetch_local=False)
            except Exception:  # noqa: BLE001 — worker tearing down
                time.sleep(0.5)
                continue
            if not ready:
                continue
            with self._cond:
                keys = [self._outstanding.pop(r) for r in ready
                        if r in self._outstanding]
            for key in keys:
                self._release(key)

    def inflight_snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._inflight)

    # -------------------------------------------------------------- choosing

    def choose_replica(self, model_id: str = "",
                       reps: Optional[List[Any]] = None):
        # operate on a snapshot: a concurrent refresh() must not shift
        # indices under us
        if reps is None:
            reps = self._replicas_snapshot()
        if model_id:
            pick, has_holders = self._choose_for_model(model_id, reps)
            if pick is not None:
                return pick
            if not has_holders:
                # cold model: pick a candidate, then atomically
                # claim-or-adopt so CONCURRENT cold requests for the same
                # model coalesce onto one replica instead of each paying
                # a duplicate load (the race affinity-at-dispatch left
                # open)
                cand = self._pow2(reps)
                with self._lock:
                    keys = list(self._mux_affinity.get(model_id, ()))
                    by_key = {self._cache_key(r): r for r in reps}
                    for k in keys:
                        if k in by_key:  # someone claimed first: adopt
                            return by_key[k]
                    key = self._cache_key(cand)
                    lst = self._mux_affinity.setdefault(model_id, [])
                    lst.insert(0, key)
                    self._mux_dispatch_t[(model_id, key)] = time.monotonic()
                return cand
        return self._pow2(reps)

    def _pow2(self, reps: List[Any]):
        if len(reps) == 1:
            return reps[0]
        i, j = self._rng.sample(range(len(reps)), 2)
        return reps[i] if self._probe(reps[i]) <= self._probe(reps[j]) \
            else reps[j]

    def _choose_for_model(self, model_id: str, reps: List[Any]):
        """Prefer a replica that already holds ``model_id`` (avoids a
        load + possible LRU eviction elsewhere); fall back to pow-2 when
        none does or the holder is saturated.  Returns ``(pick,
        has_holders)`` — ``has_holders`` distinguishes "saturated holder,
        deliberately spill elsewhere" from "no holder at all" (only the
        latter may claim-coalesce).  Reference: ``multiplex.py``
        model-aware routing in the pow-2 scheduler."""
        with self._lock:
            keys = list(self._mux_affinity.get(model_id, ()))
            limit = self._max_ongoing or 1
        if keys:
            by_key = {self._cache_key(r): r for r in reps}
            holders = [by_key[k] for k in keys if k in by_key]
            if holders:
                best = min(holders, key=self._probe)
                if self._probe(best) < limit:
                    return best, True
                return None, True
        return None, False

    def note_model(self, model_id: str, replica) -> None:
        """Record that ``replica`` now holds ``model_id`` (front of the
        affinity list); trimmed to a handful — stale entries age out as
        other replicas take over."""
        if not model_id:
            return
        key = self._cache_key(replica)
        with self._lock:
            lst = self._mux_affinity.setdefault(model_id, [])
            if key in lst:
                lst.remove(key)
            lst.insert(0, key)
            for dropped in lst[4:]:
                self._mux_dispatch_t.pop((model_id, dropped), None)
            del lst[4:]
            # provisional until the replica's loaded-model report
            # confirms it (cleared there)
            self._mux_dispatch_t[(model_id, key)] = time.monotonic()

    def note_dispatch(self, replica):
        """Bump the cached queue length so back-to-back requests spread."""
        key = self._cache_key(replica)
        with self._lock:
            hit = self._qlen_cache.get(key)
            if hit:
                self._qlen_cache[key] = (hit[0] + 1, hit[1])

    def note_cancelled(self):
        """Proxy-observed client abandon: count it against this
        deployment (the proxy already issued ``ray_tpu.cancel``)."""
        self._overload.note_cancelled()

    def note_shed(self):
        """Proxy-level shed (its dispatch pool was fully pinned — the
        request never reached this router's queue)."""
        self._overload.note_shed()

    def note_expired(self, bump_metric: bool = True):
        """Proxy/handle-observed deadline expiry past dispatch (e.g. the
        replica reported the drop, or the result wait timed out).
        ``bump_metric=False`` when the originating process (a replica
        dropping a spent request) already bumped the registry counter."""
        self._overload.note_expired(bump_metric=bump_metric)

    # ------------------------------------------------------------- dispatch
    #
    # a dead replica refreshes the set and re-picks, with a short backoff
    # so a controller mid-update has time to land the new replica list
    # (the old bare 3x loop retried EVERY exception instantly, hammering
    # a deployment that was failing for real)
    ASSIGN_RETRY_POLICY = resilience.RetryPolicy(
        max_attempts=3, base_delay_s=0.05, max_delay_s=0.5)

    def _assign_with_retry(self, model_id: str, dispatch):
        """Shared retry harness for unary/streaming dispatch: classified
        errors refresh the replica set and retry with backoff; fatal
        errors (including overload verdicts) surface immediately.
        Returns ``(ref_or_gen, replica_key)``."""

        def _attempt():
            ctx = current_context()
            if ctx is not None and ctx.expired():
                # budget spent before we even touched a replica: reject
                # at the cheapest point instead of executing a discarded
                # answer
                self._overload.note_expired()
                raise DeadlineExceededError(
                    request_id=ctx.request_id, deployment=self._deployment,
                    stage="router", overrun_s=ctx.overrun_s())
            fault_point("serve.router.assign")
            self._maybe_refresh()
            replica = self._acquire_replica(model_id, ctx)
            key = self._cache_key(replica)
            try:
                ref = dispatch(replica,
                               None if ctx is None else ctx.to_dict())
            except BaseException:
                self._release(key)
                raise
            self.note_dispatch(replica)
            self.note_model(model_id, replica)
            return ref, key

        def _on_retry(attempt, err, delay):
            self.refresh()

        return resilience.retry_call(
            _attempt, policy=self.ASSIGN_RETRY_POLICY,
            classify=_assign_retryable, site="serve.router.assign",
            on_retry=_on_retry)

    def assign(self, method: str, args: tuple, kwargs: dict,
               model_id: str = ""):
        ref, key = self._assign_with_retry(
            model_id,
            lambda replica, ctx_d: replica.handle_request.remote(
                method, args, kwargs, multiplexed_model_id=model_id,
                request_context=ctx_d))
        self._track_completion(ref, key)
        return ref

    def assign_streaming(self, method: str, args: tuple, kwargs: dict,
                         model_id: str = ""):
        """Route one streaming request; returns an ObjectRefGenerator
        (wrapped so the replica slot is released when the stream ends,
        errors, or is dropped)."""
        gen, key = self._assign_with_retry(
            model_id,
            lambda replica, ctx_d: replica.handle_request_streaming.options(
                num_returns="streaming").remote(
                    method, args, kwargs,
                    multiplexed_model_id=model_id, request_context=ctx_d))
        return _SlotReleasingStream(gen, self, key)

    # ------------------------------------------------- targeted dispatch
    #
    # Two-stage (disaggregated) serving needs the replica CHOICE and the
    # dispatch to decouple: the decode replica must be reserved before
    # prefill starts, because the prefill stage ships KV blocks to that
    # specific replica's channel.  These helpers expose the admission
    # valve (reserve) and the dispatch separately, with the same
    # slot-accounting/queueing/shed semantics as assign().

    def acquire_replica(self, ctx=None):
        """Reserve one admission slot on a chosen replica; returns
        ``(replica, key)``.  Blocks in the bounded router queue when the
        pool is saturated; sheds with ``BackPressureError`` / expires
        with ``DeadlineExceededError`` exactly like ``assign``.  The
        caller MUST end the reservation via ``dispatch_to`` (slot
        released on completion) or ``release_replica``."""
        self._maybe_refresh()
        replica = self._acquire_replica("", ctx)
        return replica, self._cache_key(replica)

    def release_replica(self, key: str) -> None:
        """Give back a reservation acquired via ``acquire_replica``
        without dispatching (stage-1 failure)."""
        self._release(key)

    def dispatch_to(self, replica, key: str, method: str, args: tuple,
                    kwargs: dict, *, streaming: bool = False):
        """Dispatch to an already-reserved replica.  Unary returns the
        ref (completion watcher releases the slot); streaming returns a
        ``_SlotReleasingStream``.  On dispatch failure the reservation is
        released before the error surfaces."""
        ctx = current_context()
        ctx_d = None if ctx is None else ctx.to_dict()
        try:
            if streaming:
                out = replica.handle_request_streaming.options(
                    num_returns="streaming").remote(
                        method, args, kwargs, request_context=ctx_d)
            else:
                out = replica.handle_request.remote(
                    method, args, kwargs, request_context=ctx_d)
        except BaseException:
            self._release(key)
            raise
        self.note_dispatch(replica)
        if streaming:
            return _SlotReleasingStream(out, self, key)
        self._track_completion(out, key)
        return out


class _SlotReleasingStream:
    """Iterator proxy over a streaming dispatch that gives the replica's
    admission slot back exactly once — on exhaustion, error, explicit
    close, or garbage collection (a client that dropped the stream
    without draining it must not leak capacity forever)."""

    def __init__(self, gen, router: Router, key: str):
        self._gen = gen
        self._router = router
        self._key = key
        self._released = False

    def _release(self):
        if not self._released:
            self._released = True
            self._router._release(self._key)

    def __iter__(self):
        return self

    def __next__(self):
        try:
            return next(self._gen)
        except BaseException:
            self._release()
            raise

    def close(self):
        try:
            close = getattr(self._gen, "close", None)
            if close is not None:
                close()
        finally:
            self._release()

    def __del__(self):
        # GC context: must not take the router lock (the collector can
        # fire while the owning thread holds it) — hand the release to
        # the router's orphan queue instead
        if not self._released:
            self._released = True
            self._router._orphan_releases.append(self._key)

    def __getattr__(self, name):
        return getattr(self._gen, name)


class DeploymentHandle:
    """Client-side handle; composition-safe (picklable into replicas)."""

    # routers are shared per (deployment) across handle copies in one
    # process so model-affinity state survives handle.options() chains
    _routers: Dict[str, Router] = {}
    _routers_lock = threading.Lock()

    def __init__(self, deployment_name: str, method_name: str = "__call__",
                 multiplexed_model_id: str = ""):
        self._deployment = deployment_name
        self._method = method_name
        self._mux_id = multiplexed_model_id

    def __reduce__(self):
        return (DeploymentHandle,
                (self._deployment, self._method, self._mux_id))

    def options(self, method_name: Optional[str] = None, *,
                multiplexed_model_id: Optional[str] = None
                ) -> "DeploymentHandle":
        """Reference: ``handle.options(multiplexed_model_id="m1")``
        routes to a replica that already has model "m1" loaded."""
        return DeploymentHandle(
            self._deployment,
            method_name if method_name is not None else self._method,
            multiplexed_model_id if multiplexed_model_id is not None
            else self._mux_id)

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        return DeploymentHandle(self._deployment, name, self._mux_id)

    def _get_router(self) -> Router:
        with DeploymentHandle._routers_lock:
            router = DeploymentHandle._routers.get(self._deployment)
            if router is None:
                from ray_tpu.serve.controller import get_controller

                router = Router(self._deployment, get_controller())
                DeploymentHandle._routers[self._deployment] = router
            return router

    def remote(self, *args, **kwargs) -> DeploymentResponse:
        ref = self._get_router().assign(self._method, args, kwargs,
                                        model_id=self._mux_id)
        return DeploymentResponse(ref)

    def remote_streaming(self, *args, **kwargs) -> "DeploymentStreamingResponse":
        """Call a generator method of the deployment; iterate the result
        to receive items as the replica yields them (reference:
        handle.options(stream=True))."""
        gen = self._get_router().assign_streaming(
            self._method, args, kwargs, model_id=self._mux_id)
        return DeploymentStreamingResponse(gen)


class StreamBatch(list):
    """Items a streaming deployment method has ready at once, yielded as
    ONE value: the replica pays for one streamed return (serialization,
    store, notify) instead of one an item, and the caller's
    :class:`DeploymentStreamingResponse` hands the items out one by one,
    so a consumer never sees the batch.  The LLM deployments yield a
    decode window's tokens of a request this way."""


class DeploymentStreamingResponse:
    """Iterator over a streaming deployment call's yielded values (the
    items of a :class:`StreamBatch` one by one)."""

    def __init__(self, ref_gen):
        self._gen = ref_gen

    def __iter__(self):
        import ray_tpu

        for ref in self._gen:
            # consumer-facing streaming iterator: blocking for the next
            # yielded value on the caller's own thread IS the API
            value = ray_tpu.get(ref)  # raylint: disable=bounded-blocking -- caller-thread streaming consumption, not a control thread; replica death resolves the ref with an error
            if isinstance(value, StreamBatch):
                yield from value
            else:
                yield value

    @property
    def ref_generator(self):
        return self._gen


class TwoStageHandle:
    """Disaggregated two-stage dispatch: prefill → handoff token → decode.

    Stage 1 goes through the prefill deployment's ordinary router
    (queueing on the prefill pool is the autoscaler's queue-depth
    signal).  The decode replica is RESERVED first — the prefill stage
    ships KV blocks into that specific replica's landing channel — then
    stage 2 dispatches the handoff token to the reserved replica, unary
    or streaming, so the token fan-out the client sees is byte-identical
    to the colocated path.

    A decode replica that dies mid-request (or mid-stream) triggers a
    bounded **re-prefill**: the whole two-stage flow re-runs on a
    healthy pair within the request's remaining deadline, counted in
    ``reprefills``; already-delivered stream chunks are deduplicated by
    index.  Overload verdicts (``BackPressureError``,
    ``DeadlineExceededError``) from either stage surface unchanged —
    they are never retried here (the proxy owns that decision).
    """

    # generous stage-1 bound for deadline-less direct use: a wedged
    # prefill replica must surface as an error, not a permanent hang
    DEFAULT_STAGE_TIMEOUT_S = 300.0

    def __init__(self, prefill: "DeploymentHandle",
                 decode: "DeploymentHandle", *,
                 prefill_method: str = "prefill",
                 decode_method: str = "decode",
                 decode_stream_method: str = "decode_stream",
                 max_reprefills: int = 1):
        self._prefill = prefill
        self._decode = decode
        self._m1 = prefill_method
        self._m2 = decode_method
        self._m2s = decode_stream_method
        self._max_reprefills = max_reprefills
        self.stats = {"requests": 0, "reprefills": 0}

    def _remaining(self, ctx, deadline: Optional[float] = None) -> float:
        """Remaining budget: the tighter of the request context's
        deadline and the caller's explicit bound (monotonic)."""
        rem = self.DEFAULT_STAGE_TIMEOUT_S
        if ctx is not None:
            ctx_rem = ctx.remaining_s()
            if ctx_rem is not None:
                rem = max(0.0, ctx_rem)
        if deadline is not None:
            rem = min(rem, max(0.0, deadline - time.monotonic()))
        return rem

    def _dispatch(self, body, *, streaming: bool,
                  deadline: Optional[float] = None):
        """One full two-stage attempt; returns the stage-2 ref/stream."""
        ctx = current_context()
        r2 = self._decode._get_router()
        replica, key = r2.acquire_replica(ctx)
        try:
            token = self._prefill.options(method_name=self._m1).remote(
                body, replica).result(
                    timeout=self._remaining(ctx, deadline))
        except BaseException:
            r2.release_replica(key)
            raise
        return r2.dispatch_to(
            replica, key, self._m2s if streaming else self._m2,
            (token, body), {}, streaming=streaming)

    _reprefill_counter = None

    def _note_reprefill(self):
        self.stats["reprefills"] += 1
        try:
            from ray_tpu.util import metrics

            cls = TwoStageHandle
            if cls._reprefill_counter is None:
                # cached: Metric.__init__ re-registers (and would reset)
                cls._reprefill_counter = metrics.Counter(
                    "llm_reprefills",
                    "two-stage requests re-prefilled after a "
                    "decode-replica failure")
            cls._reprefill_counter.inc()
        except Exception:  # noqa: BLE001 — visibility never fails a request
            pass

    def _retryable(self, err: BaseException, ctx,
                   deadline: Optional[float] = None) -> bool:
        """A mid-flight replica/transport death is worth a re-prefill on
        a healthy pair; overload verdicts, spent budgets (request
        deadline OR the caller's explicit bound), and non-``Exception``
        BaseExceptions are not — a client disconnect surfaces as
        ``GeneratorExit`` at the yield, and re-dispatching a whole
        prefill+ship+decode nobody will read (then yielding into the
        closed generator) is exactly wrong."""
        if not isinstance(err, Exception):
            return False  # GeneratorExit / KeyboardInterrupt / SystemExit
        if isinstance(err, (BackPressureError, DeadlineExceededError)):
            return False
        if ctx is not None and ctx.expired():
            return False
        if deadline is not None and time.monotonic() >= deadline:
            return False
        return True

    def _pre_retry(self):
        """Refresh the decode replica set (the controller prunes a
        killed replica within a tick) and back off briefly so the next
        attempt doesn't land straight back on the corpse."""
        try:
            self._decode._get_router().refresh()
        except Exception:  # noqa: BLE001 — next attempt retries anyway
            pass
        time.sleep(0.25)

    def call(self, body, timeout: Optional[float] = None):
        """Blocking unary request through both stages.  ``timeout``
        bounds the WHOLE call including any re-prefill attempts — with
        no surrounding request scope, a deadline-carrying context is
        minted from it so the router-queue waits of BOTH pools honor
        the bound too (they block on the context, not the caller's
        clock)."""
        import contextlib

        import ray_tpu
        from ray_tpu.serve.context import RequestContext, scope

        self.stats["requests"] += 1
        ctx = current_context()
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        minted = contextlib.nullcontext()
        if ctx is None and timeout is not None:
            ctx = RequestContext(uuid.uuid4().hex,
                                 deadline_s=time.time() + timeout)
            minted = scope(ctx)
        attempts = self._max_reprefills + 1
        with minted:
            for attempt in range(attempts):
                try:
                    ref = self._dispatch(body, streaming=False,
                                         deadline=deadline)
                    return ray_tpu.get(
                        ref, timeout=self._remaining(ctx, deadline))
                except BaseException as e:  # noqa: BLE001 — classified
                    if attempt + 1 >= attempts \
                            or not self._retryable(e, ctx, deadline):
                        raise
                    self._note_reprefill()
                    self._pre_retry()

    @staticmethod
    def _stream_resumable(body) -> bool:
        """Resume-at-index after a mid-stream death splices chunks from
        TWO generations — only coherent when decoding is deterministic.
        Greedy (``temperature == 0``) requests resume; sampled ones
        surface the error once chunks were delivered (the engine's
        default temperature is 0.7, so an absent field counts as
        sampled)."""
        if not isinstance(body, dict):
            return False
        try:
            return float(body.get("temperature", 0.7) or 0.0) == 0.0
        except (TypeError, ValueError):
            return False

    def stream(self, body):
        """Streaming request: yields the decode replica's chunks (each
        carries ``index``; the final chunk carries ``done``).  A decode
        death mid-stream re-prefills and resumes from the first
        undelivered index — for greedy streams; a sampled stream that
        already delivered chunks cannot be coherently resumed and
        surfaces the error instead (an untouched stream always
        retries)."""
        self.stats["requests"] += 1
        ctx = current_context()
        attempts = self._max_reprefills + 1
        delivered = 0
        for attempt in range(attempts):
            try:
                stream = self._dispatch(body, streaming=True)
                for chunk in DeploymentStreamingResponse(stream):
                    if chunk.get("done"):
                        yield chunk
                        return
                    idx = chunk.get("index", delivered)
                    if idx < delivered:
                        continue  # replayed after a re-prefill: dedup
                    delivered = idx + 1
                    yield chunk
                return  # stream ended without a done marker: complete
            except BaseException as e:  # noqa: BLE001 — classified below
                if attempt + 1 >= attempts or not self._retryable(e, ctx) \
                        or (delivered > 0
                            and not self._stream_resumable(body)):
                    raise
                self._note_reprefill()
                self._pre_retry()
