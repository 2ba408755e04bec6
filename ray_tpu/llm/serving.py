"""LLM serving: engine-per-replica deployments over ray_tpu.serve.

Reference: ``python/ray/llm/_internal/serve/`` (vLLM deployments where
tensor_parallel_size maps to placement-group bundles,
``vllm_models.py:123-191``).  TPU-native: a replica owns a whole chip set
and shards the model over an in-process mesh (tp axis) — parallelism is a
sharding spec inside the replica, not a bundle of worker processes.

Two deployment topologies (``docs/llm_serving.md``):

- **Colocated** (:class:`LLMServer`): every replica runs prefill AND
  decode on the same chip — one long prompt steals decode cycles from
  every in-flight stream on that replica.
- **Disaggregated** (:class:`LLMPrefillServer` + :class:`LLMDecodeServer`
  behind :class:`LLMDisaggIngress` /
  :class:`~ray_tpu.serve.router.TwoStageHandle`): prefill replicas run
  chunked prefill only and ship finished KV blocks to decode replicas
  over negotiated tier-B device-frame channels
  (:mod:`ray_tpu.llm.kv_transfer`); decode replicas graft the blocks
  without re-prefill and serve the decode loop at full batch occupancy.
  The pools scale independently (the serve controller's signal-driven
  pool autoscaler reads the engine stats each replica publishes to the
  GCS KV namespace ``"llm"`` — surfaced at ``/api/llm``).
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

from ray_tpu import serve
from ray_tpu._private import tracing

# engine-stats publish cadence (GCS KV ns "llm", key
# engine/<deployment>/<replica>) — the pool autoscaler's engine-signal
# feed and the dashboard /api/llm panel's source
STATS_PUBLISH_INTERVAL_S = 2.0
KV_NAMESPACE = "llm"


def _build_engine(engine_kwargs: Optional[Dict[str, Any]],
                  tensor_parallel_size: int):
    """Shared engine construction (by-name config so the DRIVER never has
    to import jax; inference weights default to bf16).  ``model`` names a
    preset of any served model (``ray_tpu/models/served.py``)."""
    # a replica's set-up, from here to the engine's last line: the
    # engine's own parts (their sum is stats()["startup"]["total_s"]) are
    # this span's children, the imports below its self time, and every
    # program built under it an ``xla.build``
    with tracing.span("engine.startup", kind="startup"):
        from ray_tpu.llm.engine import LLMEngine
        from ray_tpu.models.served import preset

        tracing.watch_builds()
        kw = dict(engine_kwargs or {})
        cfg = kw.pop("cfg", None)
        model = kw.pop("model", None)
        if cfg is None:
            cfg = preset(model or "tiny", serve_max_len=kw.get("max_len", 0))
        mesh = None
        if tensor_parallel_size > 1:
            from ray_tpu.parallel import MeshConfig, create_mesh

            mesh = create_mesh(MeshConfig(dp=1, tp=tensor_parallel_size))
        return LLMEngine(cfg, mesh=mesh, **kw)


class _EngineHost:
    """Shared replica plumbing for every engine-hosting deployment.

    Concurrency model: request threads only SUBMIT into the engine (under
    a lock) and wait on per-request events; one background thread drives
    ``engine.step()``.  Concurrent requests therefore share decode
    batches (continuous batching across requests) instead of racing the
    engine's state.  The loop also publishes ``engine.stats()`` to the
    GCS KV every :data:`STATS_PUBLISH_INTERVAL_S` — the autoscaler /
    dashboard signal feed.

    Tracing: the engine thread is covered by ``tracing.annotate`` phases
    (``serve.lock_wait`` where it had to wait, the engine's own
    ``engine.step`` tree, ``serve.deliver``, ``serve.publish_stats``,
    ``serve.settle``, ``serve.idle``) and a request thread's wait for the
    lock is a ``serve.lock_wait`` with ``who=submit``.  They are written only while
    a profiler session runs: :meth:`start_profile` / :meth:`stop_profile`
    start one in this process, the only one that can trace its chip.
    """

    # Admission settle: when free slots remain and a submit landed within
    # this window, hold the next step briefly so CONCURRENT requests
    # (dribbling in one actor RPC at a time) coalesce into one batch.
    # Stepping on the first arrival alone burns a whole decode window at
    # batch arity 1 — seen on the CPU: replica throughput swung several
    # fold run-to-run purely on arrival/step interleaving.  A lone
    # request pays at most ~settle ms of extra latency.
    ADMISSION_SETTLE_S = 0.004

    # fallback generation budget when the request carries no deadline
    # (direct handle use without a request scope)
    DEFAULT_BUDGET_S = 600.0

    role = "colocated"

    def _init_engine_host(self, engine_kwargs, tensor_parallel_size):
        self.engine = _build_engine(engine_kwargs, tensor_parallel_size)
        self._lock = threading.Lock()
        self._waiters: Dict[int, Any] = {}  # request_id -> {event, output}
        # request_id -> queue.Queue of token lists (a step's tokens each)
        self._token_queues: Dict[int, Any] = {}
        self._fresh: Dict[int, List[int]] = {}  # this step's, by request
        self.engine.on_token = self._on_token
        self._stop = False
        self._last_submit = 0.0  # monotonic; admission-settle signal
        self._last_step = 0.0    # monotonic; bounds settle deferral
        self._last_publish = 0.0
        self._host_id = uuid.uuid4().hex[:10]
        from ray_tpu.serve.replica import get_replica_context

        rc = get_replica_context()
        self._deployment = rc.deployment if rc else self.role
        self._replica_id = rc.replica_id if rc else self._host_id
        self._loop = threading.Thread(target=self._engine_loop, daemon=True)
        self._loop.start()

    @contextlib.contextmanager
    def _submitting(self):
        """Hold ``_lock`` on a request thread that hands the engine a
        request; its wait for the lock is a ``serve.lock_wait`` phase
        with ``who=submit``."""
        with tracing.annotate("serve.lock_wait", who="submit"):
            self._lock.acquire()
        try:
            yield
        finally:
            self._lock.release()

    def start_profile(self, trace_dir: str) -> bool:
        """Start a JAX profiler session in THIS process (callable over
        the replica handle): device ops and every ``tracing`` span and
        annotation of the process land in one trace under ``trace_dir``
        until :meth:`stop_profile`."""
        import jax

        jax.profiler.start_trace(trace_dir)
        return True

    def stop_profile(self) -> bool:
        import jax

        jax.profiler.stop_trace()
        return True

    def _on_token(self, request_id: int, tok: int):
        # engine thread, lock held: a step's tokens wait here and go to
        # their streams at once (``_hand_out``)
        self._fresh.setdefault(request_id, []).append(tok)

    def _hand_out(self):
        """Lock held.  Give every stream the tokens its request got in
        the step just taken: ONE ``queue.put`` a request and step, not one
        a token (a put wakes the request's thread, and some tens of
        threads woken sixteen times a window keep the interpreter lock
        from the engine thread)."""
        fresh, self._fresh = self._fresh, {}
        for rid, toks in fresh.items():
            q = self._token_queues.get(rid)
            if q is not None:
                q.put(toks)

    def _engine_loop(self):
        while not self._stop:
            # take a free lock at once, as ``with self._lock`` would: the
            # hand-over between this thread and the submitters is a race
            # of microseconds (a Python lock is not fair), and a phase
            # opened before the attempt would tilt it.  Only a wait that
            # really happens is a ``serve.lock_wait``.
            if not self._lock.acquire(blocking=False):
                with tracing.annotate("serve.lock_wait", who="engine"):
                    self._lock.acquire()
            try:
                busy = self.engine.has_unfinished()
                settle = False
                outs = []
                now = time.monotonic()
                if not busy:
                    # idle: keep the deferral clock fresh so the bound
                    # measures time-without-a-step only while decodes
                    # are actually waiting
                    self._last_step = now
                else:
                    settle = (
                        self.engine.free_slot_count()
                        > self.engine.queued_count()
                        and now - self._last_submit
                        < self.ADMISSION_SETTLE_S
                        # deferral is BOUNDED: a steady sub-settle
                        # trickle of submits must not starve running
                        # decodes — force a step once 2x the settle
                        # window has passed without one, no matter how
                        # recent the last submit is
                        and now - self._last_step
                        <= 2 * self.ADMISSION_SETTLE_S)
                    if not settle:
                        outs = self.engine.step()
                        self._last_step = time.monotonic()
                if outs or self._fresh:
                    with tracing.annotate("serve.deliver", n=len(outs)):
                        self._hand_out()  # before its waiter is woken
                        for out in outs:
                            slot = self._waiters.pop(out.request_id, None)
                            if slot is not None:
                                slot["output"] = out
                                slot["event"].set()
            finally:
                self._lock.release()
            self._maybe_publish_stats()
            if settle:
                with tracing.annotate("serve.settle"):
                    time.sleep(0.001)
            elif not busy:
                with tracing.annotate("serve.idle"):
                    time.sleep(0.005)

    def _maybe_publish_stats(self):
        now = time.monotonic()
        if now - self._last_publish < STATS_PUBLISH_INTERVAL_S:
            return
        self._last_publish = now
        with tracing.annotate("serve.publish_stats",
                              **self._startup_snapshot()):
            self._publish_stats()

    def _startup_snapshot(self) -> Dict[str, Any]:
        """What ``serve.publish_stats`` carries into a profiler's trace:
        the process's build ledger and the engine's set-up, as scalars.
        The trace's last one says where the replica's set-up went, and what
        it has built since, to a reader that holds nothing but the trace."""
        if not tracing.is_enabled():
            return {}
        b = tracing.build_counters()
        up = getattr(self.engine, "startup", {})
        return {"built": b["built"], "loaded": b["loaded"],
                "build_ms": round(b["build_s"] * 1e3, 1),
                "load_ms": round(b["load_s"] * 1e3, 1),
                "lower_ms": round(b["lower_s"] * 1e3, 1),
                **{f"startup_{part}_s": up.get(f"{part}_s", 0.0)
                   for part in ("backend", "weights", "pool", "total")}}

    def _publish_stats(self):
        try:
            import ray_tpu
            from ray_tpu.experimental import internal_kv

            if not ray_tpu.is_initialized():
                return
            with self._lock:
                stats = self.engine.stats()
            rec = {"ts": time.time(), "role": self.role,
                   "deployment": self._deployment,
                   "replica": self._replica_id}
            rec.update(stats)
            rec.update(self._extra_stats())
            internal_kv._internal_kv_put(
                f"engine/{self._deployment}/{self._replica_id}".encode(),
                json.dumps(rec).encode(), namespace=KV_NAMESPACE)
        except Exception:  # noqa: BLE001 — visibility never kills the loop
            pass

    def _extra_stats(self) -> Dict[str, Any]:
        return {}

    def stats(self) -> Dict[str, Any]:
        """Engine + role stats over the handle (tests, debugging)."""
        with self._lock:
            out = {"role": self.role, "deployment": self._deployment,
                   "replica": self._replica_id}
            out.update(self.engine.stats())
        out.update(self._extra_stats())
        return out

    def _budget_s(self) -> float:
        """The request's remaining deadline budget (propagated from the
        proxy / nesting handle via serve.context), or DEFAULT_BUDGET_S
        without one."""
        from ray_tpu.serve.context import current_context

        ctx = current_context()
        if ctx is None:
            return self.DEFAULT_BUDGET_S
        remaining = ctx.remaining_s()
        return self.DEFAULT_BUDGET_S if remaining is None \
            else max(0.0, remaining)

    def _abort_abandoned(self, rid: int) -> None:
        """Lock held.  Drop an abandoned request from the engine: the
        client stopped waiting (budget expired / stream dropped), so
        free the slot instead of decoding an answer nobody reads."""
        self._waiters.pop(rid, None)
        abort = getattr(self.engine, "abort", None)
        if abort is not None:
            try:
                abort(rid)
            except Exception:  # noqa: BLE001 — already finished
                pass

    def _sampling_from_body(self, body: Dict[str, Any]):
        from ray_tpu.models.paged_generation import SamplingParams

        return SamplingParams(
            temperature=float(body.get("temperature", 0.7)),
            # clamp to what the engine can ever hold: an unclamped
            # client value must fail THIS request at most, not others
            max_tokens=min(int(body.get("max_tokens", 64)),
                           self.engine.max_len - 1),
            stop_token_id=self.engine.tokenizer.eos_id)

    # -- shared unary / streaming request paths -----------------------------

    def _generate(self, body: Dict[str, Any],
                  budget: Optional[float] = None) -> Dict[str, Any]:
        from ray_tpu.exceptions import DeadlineExceededError

        budget = self._budget_s() if budget is None else budget
        sp = self._sampling_from_body(body)
        slot = {"event": threading.Event(), "output": None}
        with self._submitting():
            rid = self.engine.submit(body["prompt"], sp)
            self._waiters[rid] = slot
            self._last_submit = time.monotonic()
        if not slot["event"].wait(timeout=budget):
            # budget spent: stop decoding for this client
            with self._lock:
                self._abort_abandoned(rid)
            raise DeadlineExceededError(
                deployment=self._deployment, stage="generation",
                overrun_s=0.0)
        out = slot["output"]
        if out.error:
            raise RuntimeError(out.error)
        return {"generated_text": out.text,
                "num_generated_tokens": len(out.token_ids)}

    def _stream_tokens(self, rid: int, slot: Dict[str, Any], tq,
                       deadline: float, seed_tokens: List[int]):
        """Yield one ``{"token_id", "text", "index"}`` chunk per decoded
        token and a final ``{"done": True, ...}`` summary; the chunks of
        one engine step (a decode window's tokens) leave as one
        :class:`~ray_tpu.serve.StreamBatch`, which the caller's handle
        takes apart again.  Decoding is incremental and holds back text
        that ends in a replacement char (an incomplete multi-byte
        sequence at the boundary) until the bytes completing it arrive —
        per-token decode would turn every multi-byte character into
        mojibake; it decodes only the ids since the last text that was
        streamed (with the ids of that text before them, for a
        tokenizer whose text depends on what precedes), never the whole
        answer a token.
        ``seed_tokens`` are tokens produced before this consumer attached
        (the disaggregated handoff's prefill-sampled first token)."""
        import queue as queue_mod

        from ray_tpu.exceptions import DeadlineExceededError

        decode = self.engine.tokenizer.decode
        index = 0
        all_ids: List[int] = []
        # ids[lead:read] gave the text streamed last, ids[read:] none yet
        lead = read = 0
        emitted = 0  # characters streamed so far
        fresh = list(seed_tokens)
        while True:
            if not fresh:
                if slot["event"].is_set() and tq.empty():
                    break
                if time.time() > deadline:
                    raise DeadlineExceededError(
                        deployment=self._deployment,
                        stage="generation-stream",
                        overrun_s=time.time() - deadline)
                if not self._loop.is_alive():
                    raise RuntimeError("engine loop died mid-generation")
                try:
                    fresh = tq.get(timeout=0.05)
                except queue_mod.Empty:
                    continue
            chunks = serve.StreamBatch()
            for tok in fresh:
                all_ids.append(int(tok))
                before = decode(all_ids[lead:read])
                text = decode(all_ids[lead:])
                if len(text) > len(before) and not text.endswith("\ufffd"):
                    chunks.append({"token_id": int(tok),
                                   "text": text[len(before):],
                                   "index": index})
                    index += 1
                    emitted += len(text) - len(before)
                    lead, read = read, len(all_ids)
            fresh = []
            if len(chunks) == 1:
                yield chunks[0]
            elif chunks:
                yield chunks
        out = slot["output"]
        if out.error:
            raise RuntimeError(out.error)
        tail = out.text[emitted:]
        if tail:  # flush any held-back suffix so chunks sum to text
            yield {"token_id": -1, "text": tail, "index": index}
        yield {"done": True, "generated_text": out.text,
               "num_generated_tokens": len(out.token_ids)}

    def _stream(self, body: Dict[str, Any],
                budget: Optional[float] = None):
        import queue as queue_mod

        budget = self._budget_s() if budget is None else budget
        sp = self._sampling_from_body(body)
        slot = {"event": threading.Event(), "output": None}
        tq: "queue_mod.Queue" = queue_mod.Queue()
        with self._submitting():
            rid = self.engine.submit(body["prompt"], sp)
            self._waiters[rid] = slot
            self._token_queues[rid] = tq
            self._last_submit = time.monotonic()
        try:
            yield from self._stream_tokens(rid, slot, tq,
                                           time.time() + budget, [])
        finally:
            with self._lock:
                self._token_queues.pop(rid, None)
                if not slot["event"].is_set():
                    # generation unfinished and the consumer is gone —
                    # deadline expiry, engine error, or the client
                    # dropped the stream (GeneratorExit)
                    self._abort_abandoned(rid)

    def check_health(self) -> bool:
        if not self._loop.is_alive():
            raise RuntimeError("engine loop died")
        return True

    def _teardown_engine_host(self):
        self._stop = True

        def drop(key):
            # best-effort: drop this replica's engine-stats record so a
            # scaled-down replica doesn't pin a KV entry until the
            # dashboard's stale sweep catches it
            try:
                from ray_tpu.experimental import internal_kv

                internal_kv._internal_kv_del(key, namespace=KV_NAMESPACE)
            except Exception:  # noqa: BLE001 — interpreter/cluster teardown
                pass

        # on a thread of its own: the host and its engine's ``on_token``
        # hook form a cycle, so ``__del__`` runs wherever the collector
        # does — also on a thread that holds a lock (the span buffer's)
        # which the RPC's loop thread is waiting for, and a blocking
        # delete there never returns
        try:
            key = f"engine/{self._deployment}/{self._replica_id}".encode()
            threading.Thread(target=drop, args=(key,), daemon=True,
                             name="llm-stats-drop").start()
        except Exception:  # noqa: BLE001 — half-built host, interpreter teardown
            pass

    def __del__(self):
        self._teardown_engine_host()


@serve.deployment(name="LLMServer", max_ongoing_requests=32,
                  max_queued_requests=64)
class LLMServer(_EngineHost):
    """Colocated HTTP/handle API: ``{"prompt": str, "max_tokens"?,
    "temperature"?} -> {"generated_text", "num_generated_tokens"}``."""

    role = "colocated"

    def __init__(self, engine_kwargs: Optional[Dict[str, Any]] = None,
                 tensor_parallel_size: int = 1):
        self._init_engine_host(engine_kwargs, tensor_parallel_size)

    def __call__(self, body: Dict[str, Any]) -> Dict[str, Any]:
        return self._generate(body)

    def stream(self, body: Dict[str, Any]):
        """Token-streaming twin of ``__call__``; served over SSE by the
        HTTP proxy (``?stream=1&method=stream``) and consumable directly
        via ``handle.stream.remote_streaming(body)``."""
        yield from self._stream(body)


@serve.deployment(name="LLMPrefill", max_ongoing_requests=8,
                  max_queued_requests=128)
class LLMPrefillServer(_EngineHost):
    """Prefill pool replica: runs chunked prefill ONLY (prefill-only
    requests retire after their first sampled token, before any decode
    window compiles), exports the KV blocks, and ships them to the
    decode replica reserved for the request over a negotiated tier-B /
    sticky tier-C channel (:class:`~ray_tpu.llm.kv_transfer.KVBlockShipper`).
    """

    role = "prefill"

    # bounded actor RPCs for channel setup: a dying decode replica must
    # fail the handoff (→ re-prefill fallback), not wedge the prefill
    CONNECT_TIMEOUT_S = 15.0

    def __init__(self, engine_kwargs: Optional[Dict[str, Any]] = None,
                 tensor_parallel_size: int = 1,
                 ship_timeout_s: float = 60.0):
        kw = dict(engine_kwargs or {})
        if not kw.get("prefill_chunk"):
            # chunked prefill is the pool's whole job: several long
            # prompts interleave block-aligned chunks instead of
            # serializing head-of-line
            kw["prefill_chunk"] = 4 * int(kw.get("block_size", 16))
        self._init_engine_host(kw, tensor_parallel_size)
        from ray_tpu.llm.kv_transfer import (KVBlockShipper,
                                             handoff_channel_bytes)

        self._shipper = KVBlockShipper(
            self._host_id,
            channel_bytes=handoff_channel_bytes(self.engine),
            ship_timeout_s=ship_timeout_s)

    def _extra_stats(self) -> Dict[str, Any]:
        return {"shipper": self._shipper.stats()}

    def _ensure_channel(self, peer_key: str, decode_replica) -> None:
        import ray_tpu

        if self._shipper.tier_of(peer_key) is not None:
            return
        info = ray_tpu.get(
            decode_replica.handle_request.remote("endpoint_info", (), {}),
            timeout=self.CONNECT_TIMEOUT_S)

        def register(tr):
            ray_tpu.get(
                decode_replica.handle_request.remote(
                    "open_kv_channel", (tr, self._host_id), {}),
                timeout=self.CONNECT_TIMEOUT_S)

        self._shipper.connect(peer_key, info, register)

    def prefill(self, body: Dict[str, Any], decode_replica
                ) -> Dict[str, Any]:
        """Stage 1 of the two-stage dispatch: prefill ``body["prompt"]``,
        ship the KV blocks to ``decode_replica``, return the handoff
        token stage 2 presents there.  A failed ship returns a tokenless
        handoff (``handoff_id=None``) — the decode stage falls back to
        an ordinary local re-prefill, so delivery failures degrade to
        the colocated cost instead of failing the request."""
        from ray_tpu.exceptions import DeadlineExceededError

        budget = self._budget_s()
        deadline = time.monotonic() + budget
        sp = self._sampling_from_body(body)
        slot = {"event": threading.Event(), "output": None}
        with self._submitting():
            rid = self.engine.submit(body["prompt"], sp,
                                     prefill_only=True)
            self._waiters[rid] = slot
            self._last_submit = time.monotonic()
        if not slot["event"].wait(timeout=budget):
            with self._lock:
                self._abort_abandoned(rid)
            raise DeadlineExceededError(
                deployment=self._deployment, stage="prefill",
                overrun_s=0.0)
        out = slot["output"]
        if out.error:
            raise RuntimeError(out.error)
        hid = f"{self._host_id}:{rid}"
        with self._lock:
            handoff = self.engine.export_kv(rid)
        handoff["handoff_id"] = hid
        peer_key = decode_replica._actor_id.hex()
        try:
            self._ensure_channel(peer_key, decode_replica)
            res = self._shipper.ship(
                peer_key, handoff,
                timeout=max(0.5, min(self._shipper.ship_timeout_s,
                                     deadline - time.monotonic())))
        except Exception as e:  # noqa: BLE001 — degrade to re-prefill
            return {"handoff_id": None, "reason": f"{type(e).__name__}: {e}",
                    "first_tokens": list(handoff["out_tokens"])}
        return {"handoff_id": hid, "tier": res["tier"],
                "bytes": res["bytes"],
                "first_tokens": list(handoff["out_tokens"])}


@serve.deployment(name="LLMDecode", max_ongoing_requests=32,
                  max_queued_requests=64)
class LLMDecodeServer(_EngineHost):
    """Decode pool replica: lands shipped KV blocks through the
    alias-guarded ``device_put`` path straight into its own block pool
    (``adopt_prefilled`` grafts blocks + prefix-cache keys without
    re-prefill) and serves the decode loop at full batch occupancy.  A
    handoff that never lands (shipper degraded, channel dead, pool
    pressure) falls back to an ordinary local generation — correctness
    never depends on the fast path."""

    role = "decode"

    # how long stage 2 waits for its handoff to land before falling back
    # to a local re-prefill (always also bounded by the request budget)
    HANDOFF_WAIT_S = 10.0

    # an unclaimed landed handoff (stage-2 caller gave up, or never
    # arrived — a TwoStageHandle retry presents a NEW id) is reaped
    # after this long: its adopted request is aborted so it stops
    # burning decode slots on an answer nobody reads
    LANDED_TTL_S = 60.0

    def __init__(self, engine_kwargs: Optional[Dict[str, Any]] = None,
                 tensor_parallel_size: int = 1):
        self._init_engine_host(engine_kwargs, tensor_parallel_size)
        from ray_tpu.llm.kv_transfer import KVLandingStrip

        # handoff_id -> {"request_id", "slot", "queue", "first_tokens",
        #                "t"}
        self._landed: Dict[str, Dict[str, Any]] = {}
        # handoff ids whose waiter already fell back to a local
        # re-prefill: a LATE landing must not adopt a duplicate request
        self._abandoned: Dict[str, float] = {}
        self._landed_cond = threading.Condition()
        self._fallback_reprefills = 0
        self._late_handoffs = 0
        self._strip = KVLandingStrip(self._adopt)

    def _extra_stats(self) -> Dict[str, Any]:
        self._reap_stale()  # rides the stats cadence (engine loop)
        with self._landed_cond:
            pending = len(self._landed)
            fallbacks = self._fallback_reprefills
            late = self._late_handoffs
        return {"landing": self._strip.stats(),
                "handoffs_pending": pending,
                "fallback_reprefills": fallbacks,
                "late_handoffs": late}

    # -- channel plumbing (called by the prefill side) ----------------------

    def endpoint_info(self):
        from ray_tpu.experimental.channel.transport import \
            local_endpoint_info

        return local_endpoint_info()

    def open_kv_channel(self, transport, peer_id: str) -> bool:
        self._strip.attach(transport, peer_id)
        return True

    def _adopt(self, handoff: Dict[str, Any]) -> bool:
        """Landing-thread callback: graft one shipped prefill into the
        engine and publish it under its handoff id.  A handoff whose
        waiter already gave up (fell back to local re-prefill) is
        dropped instead of adopted — grafting it would decode a
        duplicate answer nobody reads."""
        import queue as queue_mod

        hid = str(handoff.get("handoff_id")
                  or handoff.get("request_id"))
        with self._landed_cond:
            if self._abandoned.pop(hid, None) is not None:
                self._late_handoffs += 1
                return False
        entry: Dict[str, Any] = {"request_id": None, "first_tokens":
                                 list(handoff.get("out_tokens", [])),
                                 "t": time.monotonic()}
        with self._submitting():
            try:
                rid = self.engine.adopt_prefilled(handoff)
            except Exception:  # noqa: BLE001 — incompatible handoff
                # (pool layout mismatch): still PUBLISH the failed entry
                # so the stage-2 waiter falls back instantly instead of
                # polling out the full handoff wait
                rid = None
            if rid is not None:
                slot = {"event": threading.Event(), "output": None}
                tq: "queue_mod.Queue" = queue_mod.Queue()
                self._waiters[rid] = slot
                self._token_queues[rid] = tq
                self._last_submit = time.monotonic()
                entry.update(request_id=rid, slot=slot, queue=tq)
        with self._landed_cond:
            # re-check at publish time: the waiter may have given up
            # DURING the graft (first-adopt jit compile takes seconds) —
            # publishing now would leave a duplicate decoding next to
            # the waiter's re-prefill
            went_late = self._abandoned.pop(hid, None) is not None
            if went_late:
                self._late_handoffs += 1
            else:
                self._landed[hid] = entry
                self._landed_cond.notify_all()
        if went_late:
            rid = entry.get("request_id")
            if rid is not None:
                with self._lock:
                    self._abort_abandoned(rid)
                    self._token_queues.pop(rid, None)
            return False
        return entry["request_id"] is not None

    def _reap_stale(self) -> None:
        """Engine-loop housekeeping (rides the stats cadence): abort
        adopted requests whose handoff was never claimed and age out
        abandoned-id markers — neither may grow forever."""
        now = time.monotonic()
        with self._landed_cond:
            stale = [hid for hid, e in self._landed.items()
                     if now - e.get("t", now) > self.LANDED_TTL_S]
            entries = [self._landed.pop(hid) for hid in stale]
            for hid in [h for h, t in self._abandoned.items()
                        if now - t > self.LANDED_TTL_S]:
                del self._abandoned[hid]
        for e in entries:
            rid = e.get("request_id")
            if rid is not None:
                with self._lock:
                    self._abort_abandoned(rid)
                    self._token_queues.pop(rid, None)

    def _wait_handoff(self, token: Optional[Dict[str, Any]],
                      budget: float) -> Optional[Dict[str, Any]]:
        """Bounded wait for this request's handoff to land; None means
        the caller must re-prefill locally.  The ``llm.handoff`` fault
        site rides this edge (delay → fallback; chaos coverage)."""
        from ray_tpu.util.fault_injection import fault_point

        fault_point("llm.handoff")
        hid = (token or {}).get("handoff_id")
        if hid is None:
            return None
        deadline = time.monotonic() + min(self.HANDOFF_WAIT_S, budget)
        with self._landed_cond:
            while hid not in self._landed:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._landed_cond.wait(timeout=min(0.05, remaining))
            entry = self._landed.pop(hid, None)
            if entry is None:
                # giving up: a LATE landing must drop this handoff, not
                # adopt a duplicate of the re-prefill we fall back to
                self._abandoned[str(hid)] = time.monotonic()
        if entry is None or entry["request_id"] is None:
            return None
        return entry

    # -- stage-2 request paths ----------------------------------------------

    def decode(self, token: Optional[Dict[str, Any]],
               body: Dict[str, Any]) -> Dict[str, Any]:
        from ray_tpu.exceptions import DeadlineExceededError

        budget = self._budget_s()
        deadline = time.monotonic() + budget
        entry = self._wait_handoff(token, budget)
        if entry is None:
            with self._landed_cond:
                self._fallback_reprefills += 1
            return self._generate(body,
                                  budget=max(0.0,
                                             deadline - time.monotonic()))
        rid, slot = entry["request_id"], entry["slot"]
        with self._lock:
            self._token_queues.pop(rid, None)  # unary: nobody drains it
        if not slot["event"].wait(
                timeout=max(0.0, deadline - time.monotonic())):
            with self._lock:
                self._abort_abandoned(rid)
            raise DeadlineExceededError(
                deployment=self._deployment, stage="decode", overrun_s=0.0)
        out = slot["output"]
        if out.error:
            raise RuntimeError(out.error)
        return {"generated_text": out.text,
                "num_generated_tokens": len(out.token_ids)}

    def decode_stream(self, token: Optional[Dict[str, Any]],
                      body: Dict[str, Any]):
        budget = self._budget_s()
        deadline = time.time() + budget
        entry = self._wait_handoff(token, budget)
        if entry is None:
            with self._landed_cond:
                self._fallback_reprefills += 1
            yield from self._stream(body,
                                    budget=max(0.0,
                                               deadline - time.time()))
            return
        rid, slot, tq = entry["request_id"], entry["slot"], entry["queue"]
        try:
            yield from self._stream_tokens(rid, slot, tq, deadline,
                                           entry["first_tokens"])
        finally:
            with self._lock:
                self._token_queues.pop(rid, None)
                if not slot["event"].is_set():
                    self._abort_abandoned(rid)

    def __del__(self):
        self._teardown_engine_host()
        try:
            self._strip.stop(join_timeout_s=0.5)
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


@serve.deployment(name="LLMIngress", max_ongoing_requests=64,
                  max_queued_requests=128)
class LLMDisaggIngress:
    """HTTP-facing ingress for the disaggregated topology: relays the
    client API of :class:`LLMServer` (unary ``__call__`` + SSE
    ``stream``) through the router's two-stage dispatch, so streaming
    token fan-out is unchanged from the client's view."""

    def __init__(self, prefill_handle, decode_handle,
                 max_reprefills: int = 1):
        from ray_tpu.serve.router import TwoStageHandle

        self._two = TwoStageHandle(prefill_handle, decode_handle,
                                   max_reprefills=max_reprefills)

    def __call__(self, body: Dict[str, Any]) -> Dict[str, Any]:
        return self._two.call(body)

    def stream(self, body: Dict[str, Any]):
        yield from self._two.stream(body)

    def stats(self) -> Dict[str, Any]:
        return dict(self._two.stats)


def build_llm_deployment(engine_kwargs: Optional[Dict[str, Any]] = None,
                         *, num_replicas: int = 1,
                         tensor_parallel_size: int = 1,
                         num_tpus_per_replica: float = 0,
                         autoscaling_config=None):
    """Configured colocated LLM deployment (reference: ``serve/llm
    build_llm_deployment``)."""
    opts: Dict[str, Any] = {"num_replicas": num_replicas}
    if num_tpus_per_replica:
        opts["ray_actor_options"] = {"num_tpus": num_tpus_per_replica}
    if autoscaling_config is not None:
        opts["autoscaling_config"] = autoscaling_config
    # a replica admits as many requests as its engine has slots (and
    # queues twice that), never fewer than the class's own 32 / 64: behind
    # 32 admitted requests a 128-slot engine decodes 32 at a time while the
    # rest wait upstream of it
    slots = int((engine_kwargs or {}).get("batch_slots") or 0)
    if slots > LLMServer.config.max_ongoing_requests:
        opts.update(max_ongoing_requests=slots,
                    max_queued_requests=2 * slots)
    return LLMServer.options(**opts).bind(engine_kwargs, tensor_parallel_size)


def build_disaggregated_llm_deployment(
        engine_kwargs: Optional[Dict[str, Any]] = None, *,
        prefill_replicas: int = 1, decode_replicas: int = 1,
        tensor_parallel_size: int = 1, num_tpus_per_replica: float = 0,
        max_reprefills: int = 1,
        prefill_autoscaling=None, decode_autoscaling=None):
    """The disaggregated topology as one application graph: ingress →
    (prefill pool, decode pool).  ``serve.run`` deploys the pools first
    and hands the ingress their DeploymentHandles."""
    actor_opts = {"num_tpus": num_tpus_per_replica} \
        if num_tpus_per_replica else None
    p_opts: Dict[str, Any] = {"num_replicas": prefill_replicas}
    d_opts: Dict[str, Any] = {"num_replicas": decode_replicas}
    if actor_opts:
        p_opts["ray_actor_options"] = dict(actor_opts)
        d_opts["ray_actor_options"] = dict(actor_opts)
    if prefill_autoscaling is not None:
        p_opts["autoscaling_config"] = prefill_autoscaling
    if decode_autoscaling is not None:
        d_opts["autoscaling_config"] = decode_autoscaling
    prefill = LLMPrefillServer.options(**p_opts).bind(
        engine_kwargs, tensor_parallel_size)
    decode = LLMDecodeServer.options(**d_opts).bind(
        engine_kwargs, tensor_parallel_size)
    return LLMDisaggIngress.options(
        name="LLMIngress").bind(prefill, decode,
                                max_reprefills=max_reprefills)


def disaggregated_handle(prefill_name: str = "LLMPrefill",
                         decode_name: str = "LLMDecode", *,
                         max_reprefills: int = 1):
    """Driver-side :class:`~ray_tpu.serve.router.TwoStageHandle` over an
    already-deployed disaggregated pair — skips the ingress hop (the
    open-loop serving bench's client path)."""
    from ray_tpu.serve.router import DeploymentHandle, TwoStageHandle

    return TwoStageHandle(DeploymentHandle(prefill_name),
                          DeploymentHandle(decode_name),
                          max_reprefills=max_reprefills)
