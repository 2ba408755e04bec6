"""Process-wide deterministic fault-injection registry.

Control paths hardened by ``ray_tpu._private.resilience`` declare named
**sites** by calling :func:`fault_point("<site>")` on their hot edge
(right before the fallible I/O).  Tests arm a site to fail on its Nth
call — via the API::

    from ray_tpu.util import fault_injection as fi
    with fi.armed("gcs_store.call", nth=2, exc=ConnectionError("boom")):
        ...  # the 2nd store RPC in this process raises

or, for subprocesses (spawned workers, crucibles), via the environment::

    RAY_TPU_FAULT_INJECT="gcs_store.call:1:2:unavailable"
    #                      site          :nth:count:kind[:arg]

Spec grammar: ``site:nth[:count[:kind[:arg...]]][@start+duration]`` —
calls ``nth .. nth+count-1`` to the site trigger the ``kind`` (see
``_KINDS``); ``delay`` takes an ``arg`` (seconds) and ``slow`` takes
``factor[:duration_s]``.  Multiple specs join with ``;``.  Arming is
deterministic — a site fires on exact call indices, never randomly — so
chaos tests reproduce bit-for-bit.

The optional ``@start+duration`` suffix is **windowed (scheduled)
arming**: the site is armed ``start`` seconds after the spec is loaded
and disarms itself ``duration`` seconds later (``gcs_store.call:1:9999:
connection@10+5`` = every store RPC between t=10s and t=15s fails).
Calls outside the window neither count nor fire, so the ``nth``/
``count`` indices are *window-relative* and a scenario replays
identically however much traffic preceded its window.  Via the API use
:func:`arm_window`; scenario files script whole fault timelines through
``ray_tpu.util.chaos.ChaosTimeline``, which arms these windows (and
fires cluster-level actions like node drains) at scheduled offsets.

Sites currently wired (see docs/fault_tolerance.md):

==========================  =================================================
site                        guards
==========================  =================================================
``gcs_store.call``          every ``ExternalStoreClient`` RPC attempt
``gcs_store.wal_append``    the file-store WAL write (torn-write tests)
``worker.lease``            the owner's ``lease_worker`` raylet RPC
``serve.router.assign``     replica dispatch in the serve router
``serve.proxy.admit``       proxy-side request-context mint (HTTP + gRPC)
``serve.replica.call``      the replica's pre-execution admission edge
``gcs.drain_broadcast``     the GCS ``drain_node`` handler's hot edge
``raylet.drain_ack``        the raylet's ``drain_self`` ack (lost-RPC path)
``train.checkpoint.commit``  between checkpoint staging and rename-commit
``train.checkpoint.persist_async``  the background shard serialize+fsync edge
``train.checkpoint.peer_push``  the peer-RAM replica push (emergency tier)
``train.checkpoint.restore``  entry of the tiered restore ladder
``collective.op``           every supervised collective op, before dispatch
``collective.leader.recv``  the TCP leader's per-connection serve edge
``collective.rendezvous``   the epoch/leader KV legs of group rendezvous
``rl.weight_sync.publish``  between weight-payload put and version commit
``rl.rollout.sample``       the rollout actor's sample edge (RLHF loop)
``rl.reward.score``         the RLHF reward-scoring leg, before any mutation
``llm.kv_ship``             every KV-handoff write on the prefill replica
``llm.handoff``             the decode replica's wait-for-handoff edge
``gang.reserve``            each bundle's reserve RPC in a gang reservation
``gang.preempt.drain``      the per-node drain leg of a gang preemption
``slice.provision``         the slice provider's create_node edge
``health.probe``            the health plane's active-probe dispatch edge
``health.quarantine``       the health plane's quarantine actuation edge
``gcs.mutation_dedup``      a deduped GCS mutation, after the cache miss
``raylet.fence_rejoin``     the fenced raylet's re-register, post-cleanup
==========================  =================================================

Three kinds are special:

- ``sigkill``: instead of raising, the armed call SIGKILLs the current
  process — a real mid-operation crash, for testing that on-disk state
  (checkpoint commits, WAL tails) survives a writer dying at the worst
  instruction.  Use it via the env var in a subprocess, never in-process
  in a test runner.
- ``delay:<seconds>``: instead of raising, the armed call SLEEPS —
  injecting a hang, not an error, so watchdog/timeout paths (the
  collective supervision layer) are testable deterministically.  In the
  env spec the seconds ride the 5th field
  (``collective.op:1:1:delay:30``); via the API pass ``exc="delay:30"``.
- ``slow:<factor>[:<duration_s>]``: a *relative* hang — each armed call
  sleeps ``(factor - 1) ×`` the site's **measured baseline** inter-call
  interval (an EWMA over the site's own cadence, net of the sleeps we
  inject, so the slowdown never compounds on itself).  A 3×-slow rank is
  then rehearsable on any hardware without knowing absolute step times:
  ``collective.op:1:999999:slow:3`` makes every supervised collective in
  the process take ~3× its natural period.  The optional ``duration_s``
  auto-expires the effect that many seconds after the first firing call.
  Via the API pass ``exc="slow:3"`` or ``exc="slow:3:20"``.  The first
  counted call only seeds the baseline and passes clean.

When nothing is armed, :func:`fault_point` is a single dict lookup —
cheap enough to leave in production paths.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Dict, Iterator, Optional, Union

ENV_VAR = "RAY_TPU_FAULT_INJECT"


def _unavailable(site: str) -> Exception:
    # mirrors how a PJRT backend outage surfaces (absl status text inside
    # a RuntimeError) — classified retryable by resilience.is_retryable
    return RuntimeError(
        f"UNAVAILABLE: fault injected at {site} "
        "(simulated TPU backend outage)")


def _sigkill(site: str) -> Exception:
    # a REAL crash, not an exception: the process dies mid-operation,
    # exactly like a preempted host — never returns
    import signal

    os.kill(os.getpid(), signal.SIGKILL)
    return RuntimeError(f"unreachable: sigkill at {site}")  # pragma: no cover


_KINDS = {
    "oserror": lambda site: OSError(f"fault injected at {site}"),
    "connection": lambda site: ConnectionError(f"fault injected at {site}"),
    "eof": lambda site: EOFError(f"fault injected at {site}"),
    "runtime": lambda site: RuntimeError(f"fault injected at {site}"),
    "unavailable": _unavailable,
    "sigkill": _sigkill,
}


class _Arm:
    __slots__ = ("nth", "count", "make", "delay", "calls", "fired",
                 "start", "until", "factor", "slow_dur", "baseline",
                 "last_call", "last_injected")

    def __init__(self, nth: int, count: int, make, delay=None,
                 start=None, until=None, factor=None, slow_dur=None):
        self.nth = nth      # 1-based call index of the first failure
        self.count = count  # how many consecutive calls fail
        self.make = make    # site -> Exception (None for delay kind)
        self.delay = delay  # seconds to sleep instead of raising
        self.calls = 0      # total fault_point() hits at this site
        self.fired = 0      # how many times the fault actually fired
        # windowed arming (monotonic deadlines): calls before `start`
        # are invisible (not counted); past `until` the arm is spent
        self.start = start
        self.until = until
        # slow kind: sleep (factor-1) x the site's measured baseline
        # inter-call interval; slow_dur auto-expires it after first fire
        self.factor = factor
        self.slow_dur = slow_dur
        self.baseline = None       # EWMA of natural inter-call seconds
        self.last_call = None      # monotonic ts of the previous call
        self.last_injected = 0.0   # sleep we added on the previous call

    def in_window(self, now: float) -> bool:
        if self.start is not None and now < self.start:
            return False
        if self.until is not None and now >= self.until:
            return False
        return True


_lock = threading.Lock()
_armed: Dict[str, _Arm] = {}


def _parse_window(part: str):
    """Split the optional ``@start+duration`` suffix off one spec part.
    Returns ``(spec_without_suffix, start_s, duration_s)`` where the
    times are None when no window rides the spec."""
    if "@" not in part:
        return part, None, None
    body, _, win = part.rpartition("@")
    start_s, plus, dur = win.partition("+")
    if not plus:
        raise ValueError(
            f"{ENV_VAR}: bad window {win!r} (want @start+duration)")
    return body, float(start_s), float(dur)


def _monotonic() -> float:
    import time

    return time.monotonic()


def _load_env() -> None:
    spec = os.environ.get(ENV_VAR, "")
    if not spec:
        return
    now = _monotonic()
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        part, win_start, win_dur = _parse_window(part)
        fields = part.split(":")
        if len(fields) < 2:
            raise ValueError(
                f"{ENV_VAR}: bad spec {part!r} (want site:nth[:count[:kind]])")
        site = fields[0]
        nth = int(fields[1])
        count = int(fields[2]) if len(fields) > 2 else 1
        kind = fields[3] if len(fields) > 3 else "connection"
        start = until = None
        if win_start is not None:
            start = now + win_start
            until = start + win_dur
        if kind == "delay":
            seconds = float(fields[4]) if len(fields) > 4 else 30.0
            _armed[site] = _Arm(nth, count, None, delay=seconds,
                                start=start, until=until)
            continue
        if kind == "slow":
            factor = float(fields[4]) if len(fields) > 4 else 3.0
            slow_dur = float(fields[5]) if len(fields) > 5 else None
            _armed[site] = _Arm(nth, count, None, factor=factor,
                                slow_dur=slow_dur, start=start, until=until)
            continue
        if kind not in _KINDS:
            raise ValueError(
                f"{ENV_VAR}: unknown kind {kind!r} "
                f"(expected 'delay', 'slow' or one of {sorted(_KINDS)})")
        _armed[site] = _Arm(nth, count, _KINDS[kind], start=start,
                            until=until)


_load_env()


def _resolve_exc(exc: Union[BaseException, type, str, None]):
    """``exc`` vocabulary -> ``(make, delay, factor, slow_dur)`` for an
    ``_Arm``."""
    if isinstance(exc, str) and (exc == "delay"
                                 or exc.startswith("delay:")):
        _, _, arg = exc.partition(":")
        return None, (float(arg) if arg else 30.0), None, None
    if isinstance(exc, str) and (exc == "slow" or exc.startswith("slow:")):
        _, _, arg = exc.partition(":")
        factor_s, _, dur_s = arg.partition(":")
        factor = float(factor_s) if factor_s else 3.0
        slow_dur = float(dur_s) if dur_s else None
        return None, None, factor, slow_dur
    if exc is None:
        return _KINDS["connection"], None, None, None
    if isinstance(exc, str):
        return _KINDS[exc], None, None, None
    if isinstance(exc, BaseException):
        return (lambda site, _e=exc: _e), None, None, None
    return (lambda site, _c=exc: _c(f"fault injected at {site}")), \
        None, None, None


def arm(site: str, *, nth: int = 1, count: int = 1,
        exc: Union[BaseException, type, str, None] = None) -> None:
    """Arm ``site`` so calls ``nth .. nth+count-1`` raise.

    ``exc`` may be an exception instance (raised as-is, repeatedly), an
    exception class (instantiated with a site message), a kind string
    from the env-var vocabulary (incl. ``"delay:<seconds>"`` — the armed
    calls SLEEP instead of raising, injecting a hang), or None
    (ConnectionError).
    """
    make, delay, factor, slow_dur = _resolve_exc(exc)
    with _lock:
        _armed[site] = _Arm(nth, count, make, delay=delay, factor=factor,
                            slow_dur=slow_dur)


def arm_window(site: str, start_s: float, duration_s: float, *,
               nth: int = 1, count: int = 1 << 30,
               exc: Union[BaseException, type, str, None] = None) -> None:
    """Windowed (scheduled) arming: ``site`` arms ``start_s`` seconds
    from now and disarms itself ``duration_s`` later.  Within the window
    the usual ``nth``/``count`` indices apply, counted from the window's
    first call (default: every in-window call fires).  The chaos
    timeline uses this to script "flake the GCS for 5s at t=20s" without
    a babysitting disarm thread."""
    if duration_s <= 0:
        raise ValueError(f"arm_window: duration must be > 0, "
                         f"got {duration_s}")
    # the _Arm is built with its window in ONE publication: a two-step
    # arm-then-attach-window would leave the site live (windowless) for
    # a racing fault_point between the two lock acquisitions
    make, delay, factor, slow_dur = _resolve_exc(exc)
    start = _monotonic() + start_s
    with _lock:
        _armed[site] = _Arm(nth, count, make, delay=delay, factor=factor,
                            slow_dur=slow_dur, start=start,
                            until=start + duration_s)


def disarm(site: Optional[str] = None) -> None:
    """Disarm one site (or all, when ``site`` is None)."""
    with _lock:
        if site is None:
            _armed.clear()
        else:
            _armed.pop(site, None)


@contextlib.contextmanager
def armed(site: str, *, nth: int = 1, count: int = 1,
          exc: Union[BaseException, type, str, None] = None) -> Iterator[None]:
    """Context-managed :func:`arm` — always disarms on exit."""
    arm(site, nth=nth, count=count, exc=exc)
    try:
        yield
    finally:
        disarm(site)


def call_count(site: str) -> int:
    """How many times ``fault_point(site)`` ran while the site was armed
    (0 for never-armed sites) — lets tests assert a site was exercised."""
    with _lock:
        a = _armed.get(site)
        return a.calls if a is not None else 0


def fired_count(site: str) -> int:
    """How many times the armed fault actually raised at ``site``."""
    with _lock:
        a = _armed.get(site)
        return a.fired if a is not None else 0


def fault_point(site: str) -> None:
    """Declare an injection site.  No-op unless ``site`` is armed; armed
    sites raise — or, for the ``delay`` kind, sleep — on their configured
    call indices (deterministic)."""
    if not _armed:  # fast path: nothing armed anywhere in the process
        return
    with _lock:
        a = _armed.get(site)
        if a is None:
            return
        now = None
        if a.start is not None or a.until is not None \
                or a.factor is not None:
            now = _monotonic()
        if a.start is not None or a.until is not None:
            if not a.in_window(now):
                return  # outside the window: invisible, not counted
        a.calls += 1
        if a.factor is not None:
            # track the site's natural cadence, net of our own injected
            # sleeps, so the baseline never compounds on the slowdown
            if a.last_call is not None:
                dt = max(0.0, now - a.last_call - a.last_injected)
                a.baseline = dt if a.baseline is None \
                    else 0.7 * a.baseline + 0.3 * dt
            a.last_call = now
            a.last_injected = 0.0
            if not (a.nth <= a.calls < a.nth + a.count):
                return
            if a.baseline is None or a.baseline <= 0.0:
                return  # first counted call only seeds the baseline
            a.fired += 1
            injected = (a.factor - 1.0) * a.baseline
            a.last_injected = injected
            if a.slow_dur is not None and a.until is None:
                # the effect auto-expires slow_dur after its first fire
                a.until = now + a.slow_dur
            delay, err = injected, None
        elif a.nth <= a.calls < a.nth + a.count:
            a.fired += 1
            if a.delay is not None:
                delay, err = a.delay, None
            else:
                err = a.make(site)
        else:
            return
    if err is None:
        import time

        time.sleep(delay)  # an injected hang, outside the lock
        return
    raise err
