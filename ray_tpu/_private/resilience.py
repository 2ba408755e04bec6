"""Unified retry / error-classification layer (the "unkillable control
paths" seam).

Reference: lineage-based retry with explicit retryable-vs-fatal error
classification is a core primitive of the source system (Moritz et al.,
OSDI'18 §4.2.3; ``RetryableGrpcClient``, ``src/ray/rpc/retryable_grpc_
client.h``).  Before this module every subsystem hand-rolled its own
reconnect loop, or had none: one transient PJRT ``UNAVAILABLE`` ended
a run.  All control-path retries now share ONE taxonomy, ONE backoff
policy, and ONE place to inject faults (``ray_tpu.util.fault_injection``):

- :func:`is_retryable` — the classifier: transport loss (socket/EOF/
  raylet RPC disconnect) and PJRT ``UNAVAILABLE`` are retryable;
  application errors are fatal and surface on the first throw.
- :func:`retry_call` / :func:`retry_call_async` — bounded exponential
  backoff with jitter around any callable.
- :func:`run_staged` — the degradation ladder: try config A, on
  compile-reject / HBM-OOM fall back to B, C, …, and on total failure
  return a structured record (never a bare traceback) carrying the last
  successful in-session measurement.

Import discipline: this module must stay importable from anywhere in the
tree (store client, worker, serve, scripts), so it imports nothing
from ray_tpu at module scope.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import random
import time
from typing import Any, Callable, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)


class RetryableTransportError(Exception):
    """A transient transport/backend failure, safe to retry.

    Raise (or wrap into) this to force the retryable classification at a
    site where the underlying exception type is ambiguous.
    """


# Substrings that mark a message as transient regardless of exception
# type: PJRT/absl status codes surface as RuntimeError/XlaRuntimeError
# text, and the jax backend-init path raises plain RuntimeError("Unable
# to initialize backend ...") on a flaky TPU driver.
_RETRYABLE_MARKERS = (  # matched case-insensitively
    "unavailable",
    "connection reset",
    "connection refused",
    "broken pipe",
    "socket closed",
    "store unreachable",
    "unable to initialize backend",
)

# Degradation (not retry) signals: the config is too big for the backend,
# so retrying the same config is futile but a smaller one may fit.
_DEGRADE_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "out of memory",
    "Out of memory",
    "OOM",
    "exceeds the memory",
    "compile",
    "Compilation",
)


def is_retryable(err: BaseException) -> bool:
    """True iff ``err`` is a transient transport/backend failure.

    Retryable: explicit :class:`RetryableTransportError`; socket-level
    loss (``ConnectionError``/``BrokenPipeError``/``EOFError``/plain
    ``OSError`` at a transport boundary); raylet-socket loss
    (``RpcConnectionError`` incl. mid-call ``RpcDisconnectedError``);
    PJRT ``UNAVAILABLE`` / backend-init failures by message.  Everything
    else — application exceptions, server-reported errors re-raised
    client-side — is fatal and must surface immediately.
    """
    if isinstance(err, RetryableTransportError):
        return True
    # raylet / peer RPC loss (lazy import: rpc.py must not be a hard dep
    # of a caller that only classifies)
    try:
        from ray_tpu._private.rpc import RpcConnectionError

        if isinstance(err, RpcConnectionError):
            return True
    except Exception:  # noqa: BLE001 — partial install / early boot
        pass
    if isinstance(err, (TimeoutError, asyncio.TimeoutError)):
        # NOT retryable, despite TimeoutError being an OSError subclass
        # (and THE asyncio.TimeoutError on Python >= 3.11): a timed-out
        # RPC may have executed — and its server-side waiter may still
        # be queued — so re-issuing it can double-apply (ghost lease
        # grants); timeouts surface to the caller, which owns the
        # deadline semantics
        return False
    if isinstance(err, (ConnectionError, EOFError, BrokenPipeError)):
        return True
    if isinstance(err, asyncio.IncompleteReadError):
        return True
    if isinstance(err, OSError):
        return True
    msg = str(err).lower()
    if any(m in msg for m in _RETRYABLE_MARKERS):
        # but an explicit degrade signal wins (RESOURCE_EXHAUSTED often
        # embeds "while allocating" text that is NOT transient)
        return not is_degradable(err)
    return False


def is_degradable(err: BaseException) -> bool:
    """True iff ``err`` signals the CONFIG is too demanding (compile
    reject, HBM OOM) — retrying the same config is futile, but a staged
    fallback to a smaller config may succeed."""
    msg = str(err)
    if "unable to initialize backend" in msg.lower():
        # backend-INIT failure: there is no config to degrade — nothing
        # compiled yet.  The detail after "Unable to initialize backend
        # 'tpu':" is the runtime's own text and can carry a degrade
        # marker (one outage on record read "setup/compile error"),
        # which would otherwise misclassify an outage as a config
        # rejection (and, via the degrade veto in is_retryable, block
        # its retry).
        return False
    return any(m in msg for m in _DEGRADE_MARKERS)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded exponential backoff with jitter.

    ``delay(attempt)`` for attempt 1.. is ``base * multiplier**(n-1)``
    capped at ``max_delay_s``, plus up to ``jitter`` fraction of that.
    ``jitter=0`` makes schedules deterministic (tests).
    """

    max_attempts: int = 4
    base_delay_s: float = 0.05
    max_delay_s: float = 2.0
    multiplier: float = 2.0
    jitter: float = 0.25

    def delay_s(self, attempt: int, rng: Optional[random.Random] = None) -> float:
        d = min(self.base_delay_s * self.multiplier ** max(attempt - 1, 0),
                self.max_delay_s)
        if self.jitter:
            d += d * self.jitter * (rng or _rng).random()
        return d


DEFAULT_POLICY = RetryPolicy()
# control-plane RPCs: fail over fast, the caller is often on a hot path
FAST_POLICY = RetryPolicy(max_attempts=4, base_delay_s=0.02, max_delay_s=0.5)

_rng = random.Random()


def retry_call(
    fn: Callable[..., Any],
    *args: Any,
    policy: RetryPolicy = DEFAULT_POLICY,
    classify: Callable[[BaseException], bool] = is_retryable,
    site: str = "",
    on_retry: Optional[Callable[[int, BaseException, float], None]] = None,
    sleep: Callable[[float], None] = time.sleep,
    **kwargs: Any,
) -> Any:
    """Call ``fn`` with bounded backoff on retryable errors.

    Fatal (unclassified) errors raise immediately; retryable errors raise
    only after ``policy.max_attempts`` tries.  ``on_retry(attempt, err,
    delay)`` observes each retry.
    """
    attempt = 0
    while True:
        attempt += 1
        try:
            return fn(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001 — classified below
            if not classify(e) or attempt >= policy.max_attempts:
                raise
            d = policy.delay_s(attempt)
            logger.warning("retryable failure at %s (attempt %d/%d, "
                           "retry in %.2fs): %r",
                           site or getattr(fn, "__name__", "?"), attempt,
                           policy.max_attempts, d, e)
            if on_retry is not None:
                on_retry(attempt, e, d)
            sleep(d)


async def retry_call_async(
    fn: Callable[..., Any],
    *args: Any,
    policy: RetryPolicy = DEFAULT_POLICY,
    classify: Callable[[BaseException], bool] = is_retryable,
    site: str = "",
    on_retry: Optional[Callable[[int, BaseException, float], None]] = None,
    **kwargs: Any,
) -> Any:
    """Async twin of :func:`retry_call` (awaits ``fn``; backoff via
    ``asyncio.sleep`` so the event loop keeps servicing heartbeats)."""
    attempt = 0
    while True:
        attempt += 1
        try:
            return await fn(*args, **kwargs)
        except asyncio.CancelledError:
            raise
        except BaseException as e:  # noqa: BLE001 — classified below
            if not classify(e) or attempt >= policy.max_attempts:
                raise
            d = policy.delay_s(attempt)
            logger.warning("retryable failure at %s (attempt %d/%d, "
                           "retry in %.2fs): %r",
                           site or getattr(fn, "__name__", "?"), attempt,
                           policy.max_attempts, d, e)
            if on_retry is not None:
                on_retry(attempt, e, d)
            await asyncio.sleep(d)


# ---------------------------------------------------------------------------
# staged fallback
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StageOutcome:
    """What happened to one rung of the degradation ladder."""

    name: str
    ok: bool
    attempts: int = 1
    error: str = ""
    error_kind: str = ""  # "retryable" | "degradable" | "fatal" | ""


@dataclasses.dataclass
class StagedResult:
    """Structured record of a staged run — ALWAYS produced, so callers
    can emit an honest rc-0 report instead of dying with a traceback."""

    ok: bool
    stage: str = ""          # name of the stage that succeeded
    degraded: bool = False   # succeeded, but not on the first stage
    value: Any = None
    outcomes: List[StageOutcome] = dataclasses.field(default_factory=list)
    # most recent partial measurement note()'d by any stage, surviving
    # even when every stage ultimately failed
    last_measurement: Any = None

    def to_record(self) -> dict:
        return {
            "ok": self.ok,
            "stage": self.stage,
            "degraded": self.degraded,
            "stages": [dataclasses.asdict(o) for o in self.outcomes],
        }


class StageContext:
    """Handed to each stage's ``run(cfg, ctx)``: ``ctx.note(m)`` records
    a partial in-session measurement that survives a later failure."""

    def __init__(self, result: StagedResult):
        self._result = result

    def note(self, measurement: Any) -> None:
        self._result.last_measurement = measurement


def run_staged(
    stages: Sequence[Tuple[str, Any]],
    run: Callable[[Any, StageContext], Any],
    *,
    policy: RetryPolicy = DEFAULT_POLICY,
    classify: Callable[[BaseException], bool] = is_retryable,
    degrade_on: Callable[[BaseException], bool] = is_degradable,
    sleep: Callable[[float], None] = time.sleep,
) -> StagedResult:
    """Walk the degradation ladder ``stages`` = [(name, cfg), ...].

    Per stage: retryable errors retry in place (bounded backoff);
    degradable errors (or retry exhaustion) fall through to the next
    stage; anything unclassified is fatal for the whole ladder but is
    still captured in the returned record rather than raised.
    """
    result = StagedResult(ok=False)
    ctx = StageContext(result)
    for i, (name, cfg) in enumerate(stages):
        outcome = StageOutcome(name=name, ok=False)
        result.outcomes.append(outcome)

        def _on_retry(attempt, err, delay, _o=outcome):
            _o.attempts = attempt + 1

        try:
            value = retry_call(run, cfg, ctx, policy=policy,
                               classify=classify, site=f"stage:{name}",
                               on_retry=_on_retry, sleep=sleep)
        except BaseException as e:  # noqa: BLE001 — recorded, not raised
            outcome.error = repr(e)
            if not isinstance(e, Exception):
                # KeyboardInterrupt / SystemExit: record for the caller's
                # crash handler, but never swallow into an rc-0 result
                outcome.error_kind = "fatal"
                raise
            if degrade_on(e):
                outcome.error_kind = "degradable"
                logger.warning("stage %s rejected (degrading): %r", name, e)
                continue
            if classify(e):
                outcome.error_kind = "retryable"
                logger.warning("stage %s exhausted retries: %r", name, e)
                continue
            outcome.error_kind = "fatal"
            logger.error("stage %s failed fatally: %r", name, e)
            break
        outcome.ok = True
        result.ok = True
        result.stage = name
        result.degraded = i > 0
        result.value = value
        break
    return result
