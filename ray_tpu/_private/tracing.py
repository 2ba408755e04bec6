"""Causal distributed tracing: a zero-dependency trace-context layer.

Dapper-style context propagation (reference: ``ray.util.tracing``'s
OpenTelemetry integration, here dependency-free): a ``trace_id`` names one
causal tree (a serve request, a training step, a driver session), every
unit of work gets a ``span_id``, and ``parent_span_id`` links the tree.
The context rides

* ``TaskSpec.trace_ctx`` for task/actor submissions (minted in
  ``remote_function.remote`` / ``actor._invoke``, installed by the
  executor around the user function, so nested submissions chain);
* ``serve.context.RequestContext.trace_ctx`` for the serving plane;
* the contextvar in this module for everything in-process (collective
  ops, compiled-DAG submits, RLHF loop phases).

Finished spans land in a bounded per-process buffer, published through
the GCS internal KV (namespace ``"trace"``, key ``spans/<worker>``) by a
background publisher — the same channel the metrics registry uses — and
merged into the chrome://tracing export by ``util.state.timeline()``,
which also synthesizes submit/queue/execute phase spans from the task
event feed (``_record_task_event`` stamps the trace context onto every
event).

Two sinks, one span function.  :func:`span` writes the host record above
AND enters a ``jax.profiler.TraceAnnotation`` for its duration, so while a
profiler session runs (``train.profile()``, ``_EngineHost.start_profile``)
every span lies in the profiler's trace on the device's clock, on its
thread's line of ``/host:CPU``, with its ``attrs`` as the event's stats —
an idle gap of the chip can be put down to the span that covers it.
:func:`annotate` is the annotation alone, no host record: for phases that
repeat every step (the serve loop's ``engine.*``/``serve.*`` phases, the
step ledger's ``train.step``/``train.<bucket>``) and would flood the
buffer.  Both look ``jax`` up in ``sys.modules`` and never import it: this
module is imported by the driver, the raylet and the GCS, which must not
pay JAX's import or initialise a backend.

Overhead contract: with ``RAY_TPU_TRACING=0`` every hook is one dict/env
check (no allocation, no lock) and neither sink is written.  Enabled, a
span is one ``time.time()`` pair plus a deque append, and an annotation
one ``TraceMe`` that goes nowhere unless a profiler session runs.  Read on
the v5e host's CPU (PERF.md section 6, PR 54; PR 24 read the same):
``annotate`` 2.1 us without a session, 7.6 us inside one with the Python
tracer on, 0.9 us switched off (``serve.publish_stats`` with its nine
stats: 2.8 / 11.8 us, once in 2 s); ``span`` 23 us, 2.1 us switched off;
``build_counters`` 0.4 us; the build ledger's listener 34 us a call, three
calls a program built, none on a step that builds nothing.  The serve loop
makes ~16 annotations a decode window of ~225 ms, and three ``--trace 0``
runs each of the ``serve-chat-steady`` cell with tracing on and switched
off read medians of 14.38 and 14.43 ms a token (on 14.29-14.46, off
13.99-14.82: the cell's own spread is larger than any difference).

A third face, for code that JAX traces: :func:`scope` is a name scope
INSIDE a device program (``engine.decode`` / ``attn.proj``: the program,
then the part of the model).  It is metadata of the compiled instructions,
so it has no run-time path and nothing to switch off; the profiler's trace
carries it on every device instruction (``docs/observability.md`` has the
vocabulary, ``cells/parts.py`` the readers).

A fourth, for the programs JAX builds: :func:`watch_builds` is the
process's build ledger.  One pair of ``jax.monitoring`` listeners counts
every function traced, lowered, compiled or loaded from the persistent
cache (:func:`build_counters`, always on: a listener runs only while JAX
builds) and, with tracing on, writes each backend compile into both sinks
as ``xla.build``, under the context that paid for it.  With the start-up
spans (``init``, ``worker.chip_acquire``, ``engine.startup``,
``train.startup``: plain :func:`span` calls where a process starts) it
says where set-up goes and which program was built while requests waited
(``docs/observability.md``, ``cells/startup.py``).

Span-hygiene (enforced by the ``span-hygiene`` raylint rule): ``span()``
and ``trace()`` are context managers and must be entered with ``with``;
one stashed in an attribute is never entered and never closes.
"""

from __future__ import annotations

import contextlib
import contextvars
import json
import os
import re
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterator, List, Optional

ENV_ENABLED = "RAY_TPU_TRACING"
ENV_BUFFER = "RAY_TPU_TRACE_BUFFER"
# shared cadence with the metrics publisher (util/metrics.py)
ENV_PUBLISH_INTERVAL = "RAY_TPU_METRICS_INTERVAL_S"

KV_NAMESPACE = "trace"
KV_PREFIX = "spans/"
# dashboard/state-side cutoff: span records from publishers silent longer
# than this are swept (matches the metrics/data namespace policy)
KV_STALE_S = 600.0


def is_enabled() -> bool:
    return os.environ.get(ENV_ENABLED, "1") not in ("0", "false", "no")


def _buffer_cap() -> int:
    try:
        return max(64, int(os.environ.get(ENV_BUFFER, "4096") or 4096))
    except ValueError:
        return 4096


def new_trace_id() -> str:
    return os.urandom(8).hex()


def new_span_id() -> str:
    return os.urandom(6).hex()


class SpanContext:
    """Immutable (trace_id, span_id, parent_span_id) triple."""

    __slots__ = ("trace_id", "span_id", "parent_span_id")

    def __init__(self, trace_id: str, span_id: str,
                 parent_span_id: Optional[str] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_span_id = parent_span_id

    def child(self) -> "SpanContext":
        return SpanContext(self.trace_id, new_span_id(), self.span_id)

    def to_dict(self) -> Dict[str, Any]:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_span_id": self.parent_span_id}

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]
                  ) -> Optional["SpanContext"]:
        if not d or not d.get("trace_id") or not d.get("span_id"):
            return None
        return cls(d["trace_id"], d["span_id"], d.get("parent_span_id"))

    def __repr__(self):
        return (f"SpanContext({self.trace_id}, {self.span_id}, "
                f"parent={self.parent_span_id})")


_current: contextvars.ContextVar[Optional[SpanContext]] = \
    contextvars.ContextVar("ray_tpu_trace_ctx", default=None)

_buffer_lock = threading.Lock()
_finished: deque = deque(maxlen=_buffer_cap())
# the lazy process root: the one span that never closes, published with its
# current duration and ``open: True`` so a trace is never missing its
# ancestor
_root_ctx: Optional[SpanContext] = None
_root_entry: Optional[Dict[str, Any]] = None
_publisher_started = False

# pluggable duration sinks: the train step ledger registers here so
# layers that must not import train/ (collective supervision, the data
# iterator) can still attribute wall time to step buckets.  Keyed by an
# opaque token for removal.
_sink_lock = threading.Lock()
_duration_sinks: Dict[int, Callable[[str, float], None]] = {}
_sink_token = 0


def register_duration_sink(fn: Callable[[str, float], None]) -> int:
    """Register ``fn(bucket, seconds)`` to receive attributed durations
    (collective-wait, data-wait, H2D, ...).  Returns a token for
    :func:`unregister_duration_sink`."""
    global _sink_token
    with _sink_lock:
        _sink_token += 1
        _duration_sinks[_sink_token] = fn
        return _sink_token


def unregister_duration_sink(token: int) -> None:
    with _sink_lock:
        _duration_sinks.pop(token, None)


def note_duration(bucket: str, seconds: float) -> None:
    """Attribute ``seconds`` of wall time to ``bucket`` in every
    registered sink.  One dict check when nothing is registered — safe
    on hot paths."""
    if not _duration_sinks:
        return
    with _sink_lock:
        sinks = list(_duration_sinks.values())
    for fn in sinks:
        try:
            fn(bucket, seconds)
        except Exception:  # noqa: BLE001 — attribution must never fail work
            pass


# ---------------------------------------------------------------------------
# context accessors
# ---------------------------------------------------------------------------


def current() -> Optional[SpanContext]:
    """The in-flight span context, or None outside any traced scope."""
    return _current.get()


def set_current(ctx: Optional[SpanContext]):
    """Install ``ctx`` as the current span context; returns the reset
    token (pair with :func:`reset_current`)."""
    return _current.set(ctx)


def reset_current(token) -> None:
    _current.reset(token)


def _process_kind() -> str:
    try:
        from ray_tpu._private.worker import global_worker

        if global_worker is not None:
            from ray_tpu._private.worker import WorkerMode

            return ("driver" if global_worker.mode == WorkerMode.DRIVER
                    else "worker")
    except Exception:  # noqa: BLE001 — no runtime yet
        pass
    return "process"


def _ensure_root() -> SpanContext:
    """The lazy per-process root span: work submitted outside any scope
    (a bare driver script) still forms one connected tree per process."""
    global _root_ctx, _root_entry
    if _root_ctx is not None:
        return _root_ctx
    with _buffer_lock:
        if _root_ctx is None:
            ctx = SpanContext(new_trace_id(), new_span_id(), None)
            _root_entry = {
                "name": f"{_process_kind()}-root", "kind": "root",
                "trace_id": ctx.trace_id, "span_id": ctx.span_id,
                "parent_span_id": None, "start": time.time(), "end": None,
                "pid": os.getpid(),
            }
            _root_ctx = ctx
    return _root_ctx


def current_or_root() -> SpanContext:
    return _current.get() or _ensure_root()


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------


class _NoAnnotation:
    """What :func:`annotate` hands back when there is nothing to write
    into: tracing off, or ``jax`` not imported by this process."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats) -> None:
        pass


_NO_ANNOTATION = _NoAnnotation()


def annotate(name: str, **stats):
    """A span in the PROFILER's trace only (``jax.profiler.TraceAnnotation``
    under ``name``, ``stats`` as the event's stats; numbers and short
    strings): no host record, no context.  Use as a context manager;
    ``set_metadata(**more)`` on the entered object adds stats known only
    inside the block::

        with tracing.annotate("engine.admit", rid=rid) as a:
            ...
            a.set_metadata(kind="full")

    Without a profiler session it costs about two microseconds, with one
    about three (the module docstring has the readings).  Does nothing
    when tracing is off or ``jax`` is not in ``sys.modules`` (it is never
    imported from here)."""
    if not is_enabled():
        return _NO_ANNOTATION
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    if profiler is None:
        return _NO_ANNOTATION
    return profiler.TraceAnnotation(name, **stats)


# The vocabulary of the device programs' name scopes, two levels
# (docs/observability.md has the table; tests hold the models to it).
# ``engine.verify`` is a reserved word: no program emits it since PR 46, but
# ``cells/parts.py:PROGRAMS`` holds it and ``tests/test_device_scopes.py``
# holds the two tuples equal (ROADMAP B2 queues dropping it from both).
PROGRAM_SCOPES = ("engine.decode", "engine.prefill", "engine.verify",
                  "train.step")
PART_SCOPES = ("embed", "attn.proj", "attn.cache", "attn.core", "attn.out",
               "ffn", "router", "experts", "experts.combine", "head",
               "sample", "loss", "optimizer")
# A third level, inside ``attn.core``, where a model's token mixer is not
# attention alone (models/phi4flash.py): the convolution in front of a
# state-space scan, the scan (prefill) or its one-position update (decode),
# the gated memory unit, the differential combination and its norm.  A
# reader that knows two levels still finds ``attn.core``.  The same for a
# Gated DeltaNet layer (models/gigachat3_5.py: ``gdn.*``) and for the gate
# on a latent block's output (models/mla.py: ``attn.gate``).  And inside
# ``experts``: the shared expert beside the routed ones
# (models/deepseek_v3.py).
DETAIL_SCOPES = ("ssm.conv", "ssm.scan", "ssm.update", "gmu", "diff",
                 "gdn.conv", "gdn.scan", "gdn.update", "attn.gate",
                 "experts.shared")


def scope(name: str):
    """A name scope INSIDE a device program, for code that JAX traces::

        with tracing.scope("attn.proj"):
            q = heads_projection(y, wq, heads)

    A thin face of ``jax.named_scope``: every instruction traced under it
    carries ``.../<name>/...`` in its ``op_name``, which rides into the
    compiled program and from there into the profiler's trace (XProf's
    "Framework Name Scope" rows), on the device's own clock.  It is
    metadata: the compiled program's schedule does not see it, nothing
    runs when the program does, and so (unlike :func:`annotate`) there is
    nothing for ``RAY_TPU_TRACING`` to switch off.  Two levels, one
    vocabulary for every model (``docs/observability.md``): the program
    (``engine.decode``, ``engine.prefill``, ``train.step``), put once where
    the program is built, then the part (``attn.proj``, ``experts``,
    ``head``, ...)."""
    jax = sys.modules.get("jax")
    return jax.named_scope(name) if jax is not None else _NO_ANNOTATION


def scoped(name: str, fn: Callable, *args, **kwargs):
    """``fn(*args, **kwargs)`` traced under :func:`scope` ``name``.  For a
    program scope: ``jax.jit(functools.partial(tracing.scoped,
    "engine.decode", decode_sample, cfg=cfg))`` puts the whole program
    under it and leaves the program's module name what a jitted partial's
    is."""
    with scope(name):
        return fn(*args, **kwargs)


def _scalar_stats(attrs: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    """The attrs an annotation can carry: the trace encodes stats as
    ``name#k=v,k=v#`` text, so containers stay in the host record only."""
    if not attrs:
        return {}
    return {k: v for k, v in attrs.items()
            if isinstance(v, (bool, int, float))
            or (isinstance(v, str) and not set(v) & set("#,="))}


def record_span(name: str, start: float, end: float,
                ctx: SpanContext, *, kind: str = "",
                attrs: Optional[Dict[str, Any]] = None) -> None:
    """Append one completed span to the process buffer."""
    if not is_enabled():
        return
    entry: Dict[str, Any] = {
        "name": name, "kind": kind, "trace_id": ctx.trace_id,
        "span_id": ctx.span_id, "parent_span_id": ctx.parent_span_id,
        "start": start, "end": end, "pid": os.getpid(),
    }
    if attrs:
        entry["attrs"] = attrs
    with _buffer_lock:
        _finished.append(entry)
    _ensure_publisher()


@contextlib.contextmanager
def span(name: str, *, kind: str = "",
         attrs: Optional[Dict[str, Any]] = None) -> Iterator[Optional[SpanContext]]:
    """Record a span around the block and make it the current context, so
    work submitted inside (tasks, collectives) parents to it.  The block
    also runs inside :func:`annotate`, so the span is in a profiler trace
    too whenever one is being taken."""
    if not is_enabled():
        yield None
        return
    ctx = current_or_root().child()
    token = _current.set(ctx)
    start = time.time()
    try:
        with annotate(name, **_scalar_stats(attrs)):
            yield ctx
    finally:
        _current.reset(token)
        record_span(name, start, time.time(), ctx, kind=kind, attrs=attrs)


@contextlib.contextmanager
def trace(name: str, *, attrs: Optional[Dict[str, Any]] = None
          ) -> Iterator[Optional[SpanContext]]:
    """Start a FRESH trace (new ``trace_id``) rooted at this block — one
    causal tree per request/step/iteration::

        with tracing.trace("rlhf-iteration", attrs={"iter": it}):
            ...  # everything submitted here shares one trace_id
    """
    if not is_enabled():
        yield None
        return
    ctx = SpanContext(new_trace_id(), new_span_id(), None)
    token = _current.set(ctx)
    start = time.time()
    try:
        with annotate(name, **_scalar_stats(attrs)):
            yield ctx
    finally:
        _current.reset(token)
        record_span(name, start, time.time(), ctx, kind="root", attrs=attrs)


# ---------------------------------------------------------------------------
# the build ledger: the programs JAX builds or loads in this process
# ---------------------------------------------------------------------------

_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
_LOWER_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration")
# the persistent cache's hit, fired inside the compile's span on its thread
# (the whole name starts with JAX's cache module; node.py alone names that)
_CACHE_HIT_SUFFIX = "/cache_hits"
# JAX's own rule for a module's name (``jit(<unknown>)`` -> ``jit__unknown``):
# what the compile cache's files and the profiler's ``XLA Modules`` say
_MODULE_NAME_RE = re.compile(r"[^\w.-]")

_build_lock = threading.Lock()
_builds_watched = False
_builds: Dict[str, float] = {"built": 0, "loaded": 0, "build_s": 0.0,
                             "load_s": 0.0, "lower_s": 0.0}


class _BuildThread(threading.local):
    """A building thread's side of the ledger: ``spans`` the phases counted
    so far, innermost last (a phase that ends later and began earlier holds
    them, and counts only what they left); ``lower_s`` traced and lowered
    since the thread's last build; ``hit`` the cache answered inside the
    build that is ending."""

    def __init__(self):
        self.spans: List[tuple] = []
        self.lower_s = 0.0
        self.hit = False


_build_tls = _BuildThread()


def build_counters() -> Dict[str, float]:
    """What :func:`watch_builds` has counted in this process: ``built``
    (programs the backend compiled), ``loaded`` (programs the persistent
    cache handed back), the seconds of either (``build_s``, ``load_s``) and
    ``lower_s``, the seconds spent tracing functions and lowering them to
    MLIR.  Nested phases are counted once (a function traced while another
    is, a constant folded by a program built inside a trace), so the three
    sum to the time the building threads spent on programs."""
    with _build_lock:
        return dict(_builds)


def _own_seconds(start: float, end: float) -> float:
    """``end - start`` less the phases already counted inside it, on this
    thread; the phase takes their place."""
    held = _build_tls.spans
    inner = 0.0
    while held and held[-1][0] >= start:
        s, e = held.pop()
        inner += e - s
    held.append((start, end))
    if len(held) > 256:  # siblings of phases long closed
        del held[:128]
    return max(0.0, end - start - inner)


def _on_build_span(event: str, start: float, end: float, **kw) -> None:
    try:
        if event in _LOWER_EVENTS:
            own = _own_seconds(start, end)
            _build_tls.lower_s += own
            with _build_lock:
                _builds["lower_s"] += own
            return
        if event != _BUILD_EVENT:
            return
        own = _own_seconds(start, end)
        cached, _build_tls.hit = _build_tls.hit, False
        lower_ms = round(_build_tls.lower_s * 1e3, 3)
        _build_tls.lower_s = 0.0
        with _build_lock:
            _builds["loaded" if cached else "built"] += 1
            _builds["load_s" if cached else "build_s"] += own
        if not is_enabled():
            return
        program = _MODULE_NAME_RE.sub(
            "_", str(kw.get("fun_name", ""))).rstrip("_")
        record_span("xla.build", start, end, current_or_root().child(),
                    kind="build", attrs={"program": program,
                                         "cached": int(cached),
                                         "lower_ms": lower_ms})
        # an instant at the build's end, on the building thread's line of a
        # profiler trace: a reader takes [end - ms, end]
        with annotate("xla.build", program=program,
                      ms=round((end - start) * 1e3, 3), cached=int(cached)):
            pass
    except Exception:  # noqa: BLE001 — a listener must never fail a build
        pass


def _on_build_event(event: str, **kw) -> None:
    if event.endswith(_CACHE_HIT_SUFFIX):
        _build_tls.hit = True


def watch_builds() -> bool:
    """Start the process's build ledger: ONE time-span listener and ONE
    event listener with ``jax.monitoring``, which deliver every function
    traced, lowered and compiled (or loaded from the persistent cache) with
    its stamps and name.  Idempotent; ``jax`` is looked up in
    ``sys.modules`` and never imported, so a process without it registers
    nothing and returns False (call again once it has).

    The counters (:func:`build_counters`) are always on: a listener runs
    only while JAX builds, nothing is added to a step's path.  With tracing
    enabled every backend compile is also an ``xla.build`` span in the host
    buffer under the current context (``program``, ``cached``,
    ``lower_ms``: what its thread traced and lowered since its last build)
    and one instant ``xla.build`` annotation at its end (``program``,
    ``ms``, ``cached``) for a profiler session.  Trace and lower phases are
    counted, not recorded: an eager initialisation makes hundreds."""
    global _builds_watched
    monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
    if monitoring is None:
        return False
    with _build_lock:
        if _builds_watched:
            return True
        _builds_watched = True
    monitoring.register_event_time_span_listener(_on_build_span)
    monitoring.register_event_listener(_on_build_event)
    return True


# ---------------------------------------------------------------------------
# task-submission face (TaskSpec.trace_ctx)
# ---------------------------------------------------------------------------


def mint_task_context(name: str) -> Optional[Dict[str, Any]]:
    """The wire dict a submission stamps onto ``TaskSpec.trace_ctx``:
    a fresh span for the task, parented to the submitter's current
    context (or the lazy process root).  ``submitted_at`` anchors the
    submit→queue→execute phase synthesis in the timeline export."""
    if not is_enabled():
        return None
    parent = current_or_root()
    _ensure_publisher()
    return {
        "trace_id": parent.trace_id, "span_id": new_span_id(),
        "parent_span_id": parent.span_id, "name": name,
        "submitted_at": time.time(),
    }


@contextlib.contextmanager
def task_scope(trace_ctx: Optional[Dict[str, Any]]) -> Iterator[None]:
    """Executor-side: install the spec-carried context around the user
    function so nested submissions/collectives parent to this task."""
    ctx = SpanContext.from_dict(trace_ctx)
    if ctx is None:
        yield
        return
    token = _current.set(ctx)
    try:
        yield
    finally:
        _current.reset(token)


# ---------------------------------------------------------------------------
# buffer access + KV publication
# ---------------------------------------------------------------------------


def local_spans(include_open: bool = True) -> List[Dict[str, Any]]:
    """Snapshot of this process's span buffer: the finished spans and,
    with ``include_open``, the process root (the one span left open)."""
    with _buffer_lock:
        out = [dict(e) for e in _finished]
        if include_open and _root_entry is not None:
            out.append(dict(_root_entry, end=time.time(), open=True))
    return out


def clear_local() -> None:
    """Drop buffered spans (test isolation)."""
    global _root_ctx, _root_entry
    with _buffer_lock:
        _finished.clear()
        _root_ctx = _root_entry = None


def publish_kv() -> None:
    """Best-effort publish of the local span buffer into the GCS KV.
    Bounded (5s) so a wedged control plane can never turn a shutdown
    flush into a hang."""
    import ray_tpu

    if not ray_tpu.is_initialized():
        return
    from ray_tpu._private.worker import get_global_worker

    w = get_global_worker(required=False)
    if w is None:
        return
    spans = local_spans()
    if not spans:
        return
    wid = w.worker_id.hex()[:12]
    payload = json.dumps({"ts": time.time(), "worker": wid, "spans": spans})
    w.run_coro(
        w.gcs.call("kv_put", ns=KV_NAMESPACE, key=f"{KV_PREFIX}{wid}",
                   value=payload.encode(), overwrite=True, timeout=2),
        timeout=4)


def flush() -> None:
    """Synchronous best-effort publish (used by ``timeline()`` for the
    local process and by worker shutdown so short-lived workers' spans
    are not lost to the publish interval)."""
    try:
        publish_kv()
    except Exception:  # noqa: BLE001 — flush must never fail the caller
        pass


def publish_interval_s() -> float:
    # ONE cadence knob: the metrics module owns the parse (env name,
    # floor, default); a drifted duplicate here would silently
    # desynchronize the two publishers
    from ray_tpu.util.metrics import publish_interval_s as _interval

    return _interval()


def _ensure_publisher() -> None:
    global _publisher_started
    if _publisher_started:
        return
    with _buffer_lock:
        if _publisher_started:
            return
        _publisher_started = True

    def loop():
        while True:
            time.sleep(publish_interval_s())
            flush()

    threading.Thread(target=loop, daemon=True, name="rtpu-trace-pub").start()


def chrome_trace_events(task_events: List[Dict[str, Any]],
                        spans: List[Dict[str, Any]] = (),
                        ) -> List[Dict[str, Any]]:
    """Render task events + published spans as chrome://tracing events.

    Trace-stamped task events become a causally-linked tree: one ph=X box
    for the task (``ts`` anchored at SUBMIT time, so owner-side latency is
    visible) plus synthesized ``submit`` / ``queue`` / ``execute`` phase
    children — submit is the owner-side pipeline (enqueue + lease + push
    flight), queue is the executor-side wait for a thread/loop slot,
    execute is the user function.  Phase spans carry deterministic ids
    (``<task-span>.<phase>``) so parent links always resolve.  Events
    without a trace context render exactly as before (execution box only).
    """
    events: List[Dict[str, Any]] = []
    for e in task_events:
        pid = e.get("node_id", "node")[:8]
        tid = e.get("worker_id", "worker")
        base_args = {"ok": e.get("ok"), "task_id": e.get("task_id")}
        tr = e.get("trace") or {}
        if not tr.get("trace_id"):
            events.append({
                "name": e["name"], "cat": e.get("kind", "TASK"), "ph": "X",
                "ts": e["start"] * 1e6,
                "dur": max(e["end"] - e["start"], 1e-6) * 1e6,
                "pid": pid, "tid": tid, "args": base_args,
            })
            continue
        sid = tr["span_id"]
        # clocks cross hosts: clamp each phase boundary into [prev, end]
        submitted = min(tr.get("submitted_at") or e["start"], e["start"])
        received = min(max(tr.get("received_at") or e["start"], submitted),
                       e["start"])
        events.append({
            "name": e["name"], "cat": e.get("kind", "TASK"), "ph": "X",
            "ts": submitted * 1e6,
            "dur": max(e["end"] - submitted, 1e-6) * 1e6,
            "pid": pid, "tid": tid,
            "args": {**base_args, "trace_id": tr["trace_id"],
                     "span_id": sid,
                     "parent_span_id": tr.get("parent_span_id"),
                     "phase": "task"},
        })
        for phase, t0, t1 in (("submit", submitted, received),
                              ("queue", received, e["start"]),
                              ("execute", e["start"], e["end"])):
            events.append({
                "name": phase, "cat": "PHASE", "ph": "X",
                "ts": t0 * 1e6, "dur": max(t1 - t0, 1e-6) * 1e6,
                "pid": pid, "tid": tid,
                "args": {"task": e["name"], "task_id": e.get("task_id"),
                         "trace_id": tr["trace_id"],
                         "span_id": f"{sid}.{phase}",
                         "parent_span_id": sid, "phase": phase},
            })
    for s in spans:
        args = {"trace_id": s.get("trace_id"), "span_id": s.get("span_id"),
                "parent_span_id": s.get("parent_span_id"),
                "phase": s.get("kind") or "span"}
        if s.get("open"):
            args["open"] = True
        if s.get("attrs"):
            args.update(s["attrs"])
        events.append({
            "name": s["name"], "cat": s.get("kind") or "SPAN", "ph": "X",
            "ts": s["start"] * 1e6,
            "dur": max((s.get("end") or s["start"]) - s["start"], 1e-6) * 1e6,
            "pid": f"spans-{s.get('pid', 0)}", "tid": s.get("pid", 0),
            "args": args,
        })
    return events


def merge_span_payloads(raw_payloads) -> List[Dict[str, Any]]:
    """Merge raw KV span records (JSON bytes/str) into a deduplicated
    span list: a span republished across publish ticks keeps one record,
    and an open span is superseded by its closed record.  Shared by the
    state-API timeline (worker-side KV reads) and the dashboard (direct
    head-side table reads) so the two exports can never diverge."""
    by_id: Dict[str, Dict[str, Any]] = {}
    for raw in raw_payloads:
        try:
            payload = json.loads(raw)
        except (ValueError, TypeError):
            continue
        for s in payload.get("spans", []):
            sid = s.get("span_id")
            if not sid:
                continue
            prev = by_id.get(sid)
            if prev is None or (prev.get("open") and not s.get("open")):
                by_id[sid] = s
    return list(by_id.values())


def collect_cluster_spans() -> List[Dict[str, Any]]:
    """All published spans cluster-wide (see :func:`merge_span_payloads`)."""
    from ray_tpu.experimental.internal_kv import _internal_kv_get_prefix

    try:
        table = _internal_kv_get_prefix(KV_PREFIX, namespace=KV_NAMESPACE)
    except Exception:  # noqa: BLE001 — no cluster
        return []
    return merge_span_payloads((table or {}).values())
