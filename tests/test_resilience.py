"""The unified retry/error-classification layer and its fault-injection
harness: classifier taxonomy, backoff executor, staged fallback, the
deterministic fault-injection registry, and chaos tests driving every
rewired call site (external store client, GCS
compaction/shutdown race, torn WAL tails)."""

import asyncio
import json
import os
import struct
import subprocess
import sys
import threading

import pytest

from ray_tpu._private import resilience
from ray_tpu.util import fault_injection as fi


# ---------------------------------------------------------------------------
# classifier
# ---------------------------------------------------------------------------


def test_classifier_retryable_transport():
    from ray_tpu._private.rpc import RpcConnectionError, RpcDisconnectedError

    for err in [
        ConnectionError("boom"),
        ConnectionResetError("reset"),
        BrokenPipeError("pipe"),
        EOFError("eof"),
        OSError("socket closed"),
        RpcConnectionError("cannot connect"),
        RpcDisconnectedError("connection to raylet lost"),
        resilience.RetryableTransportError("forced"),
        RuntimeError("UNAVAILABLE: TPU backend not responding"),
        RuntimeError("Unable to initialize backend 'tpu'"),
        ConnectionError("gcs external store unreachable"),
    ]:
        assert resilience.is_retryable(err), err


def test_classifier_fatal_application_errors():
    for err in [
        ValueError("bad arg"),
        KeyError("missing"),
        RuntimeError("placement group removed or never created"),
        ZeroDivisionError(),
        # timeouts are NOT transport loss: the call may have executed,
        # and TimeoutError is an OSError subclass (and THE
        # asyncio.TimeoutError on Python >= 3.11) — must not fall into
        # the blanket-OSError retry bucket
        TimeoutError("deadline"),
        asyncio.TimeoutError(),
    ]:
        assert not resilience.is_retryable(err), err


def test_classifier_degradable_beats_retryable():
    # HBM OOM / compile rejects must degrade, never retry-in-place: the
    # same config will fail the same way forever
    for err in [
        RuntimeError("RESOURCE_EXHAUSTED: while allocating 4.5G"),
        RuntimeError("XLA Compilation failure: unsupported fusion"),
        MemoryError("out of memory"),
    ]:
        assert resilience.is_degradable(err), err
        assert not resilience.is_retryable(err), err


# ---------------------------------------------------------------------------
# retry executor
# ---------------------------------------------------------------------------


def test_retry_call_recovers_after_transients():
    sleeps = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionError("transient")
        return "ok"

    policy = resilience.RetryPolicy(max_attempts=4, base_delay_s=0.01,
                                    multiplier=2.0, jitter=0)
    out = resilience.retry_call(flaky, policy=policy, sleep=sleeps.append)
    assert out == "ok"
    assert calls["n"] == 3
    # exponential: 0.01, 0.02 (jitter disabled -> deterministic)
    assert sleeps == [0.01, 0.02]


def test_retry_call_fatal_raises_immediately():
    calls = {"n": 0}

    def fatal():
        calls["n"] += 1
        raise ValueError("application bug")

    with pytest.raises(ValueError):
        resilience.retry_call(fatal, sleep=lambda s: None)
    assert calls["n"] == 1  # no retries burned on a fatal error


def test_retry_call_exhaustion_raises_last_error():
    policy = resilience.RetryPolicy(max_attempts=3, base_delay_s=0, jitter=0)
    calls = {"n": 0}

    def always_down():
        calls["n"] += 1
        raise ConnectionError(f"down #{calls['n']}")

    with pytest.raises(ConnectionError, match="down #3"):
        resilience.retry_call(always_down, policy=policy,
                              sleep=lambda s: None)
    assert calls["n"] == 3


def test_retry_call_async_recovers():
    async def main():
        calls = {"n": 0}

        async def flaky():
            calls["n"] += 1
            if calls["n"] < 2:
                raise ConnectionResetError("transient")
            return calls["n"]

        policy = resilience.RetryPolicy(max_attempts=3, base_delay_s=0.001,
                                        jitter=0)
        return await resilience.retry_call_async(flaky, policy=policy)

    assert asyncio.run(main()) == 2


def test_backoff_is_bounded():
    policy = resilience.RetryPolicy(max_attempts=10, base_delay_s=0.5,
                                    max_delay_s=2.0, multiplier=4.0, jitter=0)
    assert policy.delay_s(1) == 0.5
    assert policy.delay_s(2) == 2.0  # capped
    assert policy.delay_s(9) == 2.0


# ---------------------------------------------------------------------------
# staged fallback
# ---------------------------------------------------------------------------


def test_run_staged_degrades_then_succeeds():
    ran = []

    def run(cfg, ctx):
        ran.append(cfg)
        if cfg == "big":
            raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")
        ctx.note({"mfu": 0.1})
        return {"mfu": 0.1, "cfg": cfg}

    res = resilience.run_staged(
        [("big", "big"), ("small", "small")], run, sleep=lambda s: None)
    assert res.ok and res.degraded
    assert res.stage == "small"
    assert res.value["cfg"] == "small"
    assert ran == ["big", "small"]
    rec = res.to_record()
    assert [o["name"] for o in rec["stages"]] == ["big", "small"]
    assert rec["stages"][0]["error_kind"] == "degradable"


def test_run_staged_retries_transients_in_place():
    calls = {"n": 0}

    def run(cfg, ctx):
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionError("UNAVAILABLE")
        return "ok"

    policy = resilience.RetryPolicy(max_attempts=4, base_delay_s=0, jitter=0)
    res = resilience.run_staged([("only", None)], run, policy=policy,
                                sleep=lambda s: None)
    assert res.ok and not res.degraded
    assert res.outcomes[0].attempts == 3


def test_run_staged_total_failure_is_structured_not_raised():
    def run(cfg, ctx):
        ctx.note({"partial": cfg})  # in-session measurement before dying
        raise RuntimeError("RESOURCE_EXHAUSTED")

    res = resilience.run_staged([("a", 1), ("b", 2)], run,
                                sleep=lambda s: None)
    assert not res.ok
    assert res.last_measurement == {"partial": 2}  # last stage's note survives
    assert all(o.error_kind == "degradable" for o in res.outcomes)


def test_run_staged_fatal_stops_ladder():
    ran = []

    def run(cfg, ctx):
        ran.append(cfg)
        raise ValueError("bug in the harness itself")

    res = resilience.run_staged([("a", "a"), ("b", "b")], run,
                                sleep=lambda s: None)
    assert not res.ok
    assert ran == ["a"]  # fatal must not walk the whole ladder
    assert res.outcomes[0].error_kind == "fatal"


# ---------------------------------------------------------------------------
# fault injection registry
# ---------------------------------------------------------------------------


def test_fault_point_noop_when_unarmed():
    fi.fault_point("nonexistent.site")  # must not raise


def test_fault_injection_nth_call_determinism():
    with fi.armed("t.site", nth=2, count=2, exc=ConnectionError):
        fi.fault_point("t.site")  # call 1: clean
        with pytest.raises(ConnectionError):
            fi.fault_point("t.site")  # call 2: fires
        with pytest.raises(ConnectionError):
            fi.fault_point("t.site")  # call 3: fires
        fi.fault_point("t.site")  # call 4: clean again
        assert fi.call_count("t.site") == 4
        assert fi.fired_count("t.site") == 2
    fi.fault_point("t.site")  # disarmed on exit


def test_fault_injection_exception_instance_and_kind():
    marker = OSError("exact instance")
    with fi.armed("t.inst", exc=marker):
        with pytest.raises(OSError) as ei:
            fi.fault_point("t.inst")
        assert ei.value is marker
    with fi.armed("t.kind", exc="unavailable"):
        with pytest.raises(RuntimeError, match="UNAVAILABLE"):
            fi.fault_point("t.kind")


def test_fault_injection_delay_kind_sleeps_instead_of_raising():
    """``delay:<seconds>`` injects a HANG: the armed call sleeps (never
    raises), on exactly its configured call indices — the knob the
    collective watchdog chaos tests turn."""
    import time

    with fi.armed("t.delay", nth=2, exc="delay:0.3"):
        t0 = time.monotonic()
        fi.fault_point("t.delay")  # call 1: clean (nth=2)
        assert time.monotonic() - t0 < 0.2
        t0 = time.monotonic()
        fi.fault_point("t.delay")  # call 2: sleeps, no exception
        assert time.monotonic() - t0 >= 0.25
        t0 = time.monotonic()
        fi.fault_point("t.delay")  # call 3: clean again (count=1)
        assert time.monotonic() - t0 < 0.2
        assert fi.fired_count("t.delay") == 1


def test_fault_injection_delay_env_spec():
    """Env grammar leg: ``site:nth:count:delay:<seconds>``."""
    code = (
        "import time\n"
        "from ray_tpu.util import fault_injection as fi\n"
        "t0 = time.monotonic(); fi.fault_point('env.delay')\n"
        "assert time.monotonic() - t0 >= 0.25, 'did not sleep'\n"
        "t0 = time.monotonic(); fi.fault_point('env.delay')\n"
        "assert time.monotonic() - t0 < 0.2, 'slept past count'\n"
        "print('DELAY_OK')\n"
    )
    env = dict(os.environ, RAY_TPU_FAULT_INJECT="env.delay:1:1:delay:0.3")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "DELAY_OK" in out.stdout


def test_fault_injection_slow_kind_proportional_sleep():
    """``slow:<factor>`` is a RELATIVE hang: each armed call sleeps
    ``(factor-1) x`` the site's measured inter-call baseline, so the
    site runs ``factor`` x slower at whatever its natural cadence is —
    the silent-degradation knob the health plane rehearses with."""
    import time

    with fi.armed("t.slow", count=10, exc="slow:3"):
        period = 0.05
        t0 = time.monotonic()
        fi.fault_point("t.slow")  # call 1: seeds the baseline, no sleep
        assert time.monotonic() - t0 < 0.03
        durations = []
        for _ in range(4):
            time.sleep(period)
            t0 = time.monotonic()
            fi.fault_point("t.slow")
            durations.append(time.monotonic() - t0)
        # steady state: injected sleep ~ (3-1) x 0.05s = 0.1s per call
        assert durations[-1] >= 0.05, durations
        assert durations[-1] <= 0.4, durations
        assert fi.fired_count("t.slow") == 4
    fi.fault_point("t.slow")  # disarmed on exit


def test_fault_injection_slow_baseline_nets_out_injected_sleep():
    """The baseline EWMA measures the site's NATURAL cadence net of the
    sleeps the registry itself injected — a 3x slowdown stays ~3x
    instead of compounding toward 9x, 27x, ..."""
    import time

    with fi.armed("t.slowc", count=100, exc="slow:3"):
        period = 0.04
        total = []
        for _ in range(8):
            time.sleep(period)
            t0 = time.monotonic()
            fi.fault_point("t.slowc")
            total.append(time.monotonic() - t0)
        # compounding would grow the sleep geometrically; netted-out it
        # converges near (factor-1) x period = 0.08s
        assert total[-1] < 4 * period + 0.05, total


def test_fault_injection_slow_duration_expires():
    """``slow:<factor>:<duration_s>``: the effect self-expires that many
    seconds after its first firing call."""
    import time

    with fi.armed("t.slowd", count=1000, exc="slow:5:0.25"):
        fi.fault_point("t.slowd")            # seeds baseline
        time.sleep(0.05)
        fi.fault_point("t.slowd")            # fires, starts the clock
        assert fi.fired_count("t.slowd") >= 1
        time.sleep(0.4)                      # expiry passes
        t0 = time.monotonic()
        fi.fault_point("t.slowd")            # outside window: clean
        assert time.monotonic() - t0 < 0.05
        fired_after = fi.fired_count("t.slowd")
        fi.fault_point("t.slowd")
        assert fi.fired_count("t.slowd") == fired_after


def test_fault_injection_slow_env_spec():
    """Env grammar leg: ``site:nth:count:slow:<factor>[:<duration_s>]``."""
    code = (
        "import time\n"
        "from ray_tpu.util import fault_injection as fi\n"
        "fi.fault_point('env.slow')\n"  # seeds the baseline
        "time.sleep(0.1)\n"
        "t0 = time.monotonic(); fi.fault_point('env.slow')\n"
        "dt = time.monotonic() - t0\n"
        "assert dt >= 0.1, f'no proportional sleep: {dt}'\n"
        "assert fi.fired_count('env.slow') == 1\n"
        "print('SLOW_OK')\n"
    )
    env = dict(os.environ,
               RAY_TPU_FAULT_INJECT="env.slow:1:99:slow:3")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "SLOW_OK" in out.stdout


def test_fault_injection_env_arming_in_subprocess():
    code = (
        "from ray_tpu.util import fault_injection as fi\n"
        "fi.fault_point('env.site')\n"        # call 1: clean (nth=2)
        "try:\n"
        "    fi.fault_point('env.site')\n"    # call 2: fires
        "    raise SystemExit('fault did not fire')\n"
        "except EOFError:\n"
        "    pass\n"
        "fi.fault_point('env.site')\n"        # call 3: clean (count=1)
        "print('ENV_OK')\n"
    )
    env = dict(os.environ, RAY_TPU_FAULT_INJECT="env.site:2:1:eof")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "ENV_OK" in out.stdout


# ---------------------------------------------------------------------------
# the classifier against what a backend that cannot come up really raises
# ---------------------------------------------------------------------------


# What the installed JAX raises when backend 'tpu' cannot come up (here:
# a host with no chip): the classifier is tested against what production
# actually throws.
_BACKEND_INIT_ERROR = (
    "Unable to initialize backend 'tpu': UNKNOWN: TPU initialization "
    "failed: No jellyfish device found. (set JAX_PLATFORMS='' to "
    "automatically choose an available backend)"
)


@pytest.mark.parametrize("detail", [
    "No jellyfish device found.",
    # the detail is the runtime's own text: one that carries a degrade
    # marker is still an outage, not a config the ladder can shrink
    "setup/compile error (Unavailable).",
])
def test_backend_init_error_classified_retryable(detail):
    from ray_tpu._private import resilience

    err = RuntimeError(_BACKEND_INIT_ERROR.replace(
        "No jellyfish device found.", detail))
    assert resilience.is_retryable(err)
    assert not resilience.is_degradable(err)


# ---------------------------------------------------------------------------
# chaos: external store client
# ---------------------------------------------------------------------------


def _start_store(tmp):
    p = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.gcs_store",
         "--port", "0", "--path", os.path.join(tmp, "store.pkl")],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        start_new_session=True)
    line = p.stdout.readline().decode().strip()
    assert line.startswith("GCS_STORE_ADDR "), line
    return p, line.split(" ", 1)[1]


@pytest.mark.chaos
def test_store_client_retries_injected_transport_faults(tmp_path):
    """``gcs_store.call`` injection site: the first transport attempt of
    a call dies; the client must reconnect with backoff and the offset-
    checked append must land exactly once."""
    from ray_tpu._private.gcs_store import ExternalStoreClient

    proc, addr = _start_store(str(tmp_path))
    try:
        c = ExternalStoreClient(addr)
        c.wal_append(b"aaa", at=0)
        with fi.armed("gcs_store.call", nth=1, count=1,
                      exc=ConnectionError("injected link loss")):
            c.wal_append(b"bbbb", at=3)  # retried transparently
            assert fi.fired_count("gcs_store.call") == 1
        assert c.wal_read() == b"aaabbbb"
        c.close()
    finally:
        proc.kill()
        proc.wait(timeout=10)


@pytest.mark.chaos
def test_store_server_error_not_retried_as_connection_failure():
    """Satellite: a SERVER-reported error (e.g. disk-full OSError from
    the store's own write) must surface as itself, exactly once — not be
    caught by the transport-retry scope and converted into
    ConnectionError('store unreachable') after pointless re-sends."""
    from ray_tpu._private.gcs_store import ExternalStoreClient
    from ray_tpu._private.rpc import RpcServer

    calls = {"n": 0}

    async def handle_store_wal_append(data, at=None):
        calls["n"] += 1
        raise OSError(28, "No space left on device")

    loop = asyncio.new_event_loop()
    started = threading.Event()
    info = {}

    def serve():
        asyncio.set_event_loop(loop)

        async def main():
            server = RpcServer("diskfull-store")
            server.register("store_wal_append", handle_store_wal_append)
            host, port = await server.listen_tcp("127.0.0.1", 0)
            info["addr"] = f"tcp:{host}:{port}"
            started.set()
            await asyncio.Event().wait()

        try:
            loop.run_until_complete(main())
        except RuntimeError:
            pass  # loop stopped from outside at teardown

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    assert started.wait(10)
    try:
        c = ExternalStoreClient(info["addr"], timeout_s=10)
        with pytest.raises(OSError) as ei:
            c.wal_append(b"data", at=0)
        assert not isinstance(ei.value, ConnectionError)
        assert "No space left" in str(ei.value)
        # the mutation was sent ONCE: server errors must not be re-sent
        # (a non-idempotent op would double-apply)
        assert calls["n"] == 1
        c.close()
    finally:
        loop.call_soon_threadsafe(loop.stop)
        t.join(timeout=10)


# ---------------------------------------------------------------------------
# torn-write protection in the file-backed WAL
# ---------------------------------------------------------------------------


def _frame(payload: bytes) -> bytes:
    return struct.pack("<I", len(payload)) + payload


def test_wal_torn_tail_truncated_on_reopen(tmp_path):
    """Writer killed mid-frame: on reopen the journal is truncated to
    the last whole record, acked records before the tear survive, and
    the cursor resyncs so new appends land cleanly."""
    from ray_tpu._private.gcs_store import FileStoreClient

    path = str(tmp_path / "gcs.pkl")
    c = FileStoreClient(path)
    r1, r2 = _frame(b"record-one"), _frame(b"record-two")
    c.wal_append(r1, at=0)
    c.wal_append(r2, at=len(r1))
    c.close()

    # simulate the mid-frame SIGKILL: a frame header claiming 64 bytes
    # with only 3 of them down
    with open(path + ".wal", "ab") as f:
        f.write(struct.pack("<I", 64) + b"abc")

    c2 = FileStoreClient(path)
    # the repaired length excludes the torn tail even before any append
    assert c2.wal_size() == len(r1) + len(r2)
    r3 = _frame(b"record-three")
    c2.wal_append(r3, at=len(r1) + len(r2))  # cursor-checked: must fit
    data = c2.wal_read()
    assert data == r1 + r2 + r3  # tear gone, no acked record lost
    c2.close()


def test_wal_fully_torn_header_truncated(tmp_path):
    from ray_tpu._private.gcs_store import FileStoreClient

    path = str(tmp_path / "gcs.pkl")
    with open(path + ".wal", "wb") as f:
        f.write(b"\x99\x00")  # not even a whole length header
    c = FileStoreClient(path)
    assert c.wal_size() == 0
    c.wal_append(_frame(b"x"), at=0)
    assert c.wal_read() == _frame(b"x")
    c.close()


def test_gcs_survives_torn_wal_tail(tmp_path):
    """End to end: a GCS journals kv writes, its WAL gains a torn tail
    (writer died mid-write), and a restarted GCS still replays every
    whole record."""
    from ray_tpu._private.config import config
    from ray_tpu._private.gcs import GcsServer

    sd = str(tmp_path)
    # NOTE: config attributes resolve via __getattr__ over a dict, so
    # monkeypatch.setattr would pin a shadowing instance attribute
    # forever — reload/restore like the other persistence tests
    config.reload({"gcs_storage": "file"})

    async def run_one(writes, tear):
        g = GcsServer(sd)
        g._load_snapshot()
        g._replay_wal()
        for k, v in writes:
            g.kv[("default", k)] = v
        blobs, commits = g._collect_deltas()
        g._wal_append(blobs)
        g._apply_commits(commits)
        g._store.close()
        if tear:
            with open(g._wal_path(), "ab") as f:
                f.write(struct.pack("<I", 512) + b"torn")
        return g

    try:
        asyncio.run(run_one([("a", b"1"), ("b", b"2")], tear=True))

        async def restart():
            g = GcsServer(sd)
            g._load_snapshot()
            g._replay_wal()
            return g

        g2 = asyncio.run(restart())
        assert g2.kv[("default", "a")] == b"1"
        assert g2.kv[("default", "b")] == b"2"
        # and the repaired journal accepts new appends at the synced cursor
        g2.kv[("default", "c")] = b"3"
        blobs, commits = g2._collect_deltas()
        g2._wal_append(blobs)
        g2._store.close()

        g3 = asyncio.run(restart())
        assert g3.kv[("default", "c")] == b"3"
        g3._store.close()
    finally:
        config.reload()


# ---------------------------------------------------------------------------
# GCS compaction/shutdown race
# ---------------------------------------------------------------------------


def test_stale_compact_skipped_after_final_snapshot(tmp_path):
    """The shutdown race, deterministically: a compaction prepared its
    snapshot, then stop()'s final _write_snapshot landed first.  The
    stale compact must skip BOTH its commit (state rollback) and the
    WAL truncate (would orphan the newer snapshot's journal)."""
    from ray_tpu._private.config import config
    from ray_tpu._private.gcs import GcsServer

    config.reload({"gcs_storage": "file"})
    try:
        g = GcsServer(str(tmp_path))
        g.kv[("default", "k")] = b"old"
        blob, kv_state = g._prepare_snapshot()
        prepared_against = g._last_snapshot

        # stop()'s final snapshot wins the race
        g.kv[("default", "k")] = b"new"
        g._write_snapshot()
        final = g._store.read_snapshot()

        assert g._compact_locked(blob, kv_state, prepared_against) is False
        assert g._store.read_snapshot() == final  # no rollback
        g._store.close()

        # and the non-racing path still compacts
        (tmp_path / "x").mkdir()
        g2 = GcsServer(str(tmp_path / "x"))
        g2.kv[("default", "k")] = b"v"
        blob2, kv2 = g2._prepare_snapshot()
        assert g2._compact_locked(blob2, kv2, g2._last_snapshot) is True
        assert g2._store.read_snapshot() == blob2
        g2._store.close()
    finally:
        config.reload()


# ---------------------------------------------------------------------------
# scheduling: soft avoidance of just-died nodes
# ---------------------------------------------------------------------------


def test_pick_node_soft_exclusion():
    from ray_tpu._private.scheduling import NodeView, ResourceSet, pick_node

    nodes = [
        NodeView("n1", {"CPU": 4}, {"CPU": 4}),
        NodeView("n2", {"CPU": 4}, {"CPU": 4}),
    ]
    demand = ResourceSet({"CPU": 1})
    # excluded node avoided while an alternative exists
    assert pick_node(nodes, demand, exclude_node_ids={"n1"}) == "n2"
    assert pick_node(nodes, demand, exclude_node_ids={"n2"}) == "n1"
    # soft: excluding EVERYTHING falls back to scheduling anyway
    assert pick_node(nodes, demand,
                     exclude_node_ids={"n1", "n2"}) is not None
    # hard affinity beats avoidance (explicit user placement)
    assert pick_node(nodes, demand, strategy_kind="NODE_AFFINITY",
                     affinity_node_id="n1", soft=False,
                     exclude_node_ids={"n1"}) == "n1"
    # soft affinity to an excluded node re-routes
    assert pick_node(nodes, demand, strategy_kind="NODE_AFFINITY",
                     affinity_node_id="n1", soft=True,
                     exclude_node_ids={"n1"}) == "n2"


def test_run_staged_does_not_swallow_keyboard_interrupt():
    def run(cfg, ctx):
        raise KeyboardInterrupt()

    with pytest.raises(KeyboardInterrupt):
        resilience.run_staged([("a", 1)], run, sleep=lambda s: None)


def test_release_lease_token_reclaims_unreceived_grant(tmp_path):
    """A lease grant whose reply was lost mid-socket can be released by
    token: the worker returns to the idle pool and its resources free,
    instead of being stranded forever on a live node (the owner never
    received — and so can never use — that grant)."""
    from ray_tpu._private.raylet import Raylet, WorkerHandle
    from ray_tpu._private.scheduling import ResourceSet

    r = Raylet(str(tmp_path), "tcp:127.0.0.1:1", {"CPU": 4})
    h = WorkerHandle(b"wid1", "unix:/tmp/w1", 123, None)
    h.lease = {"demand": ResourceSet({"CPU": 1}), "pg_id": None,
               "bundle_index": -1, "owner": "", "granted_at": 0.0,
               "token": "tok-1"}
    r.workers[b"wid1"] = h
    r._lease_tokens["tok-1"] = h
    r.available.subtract(ResourceSet({"CPU": 1}))  # as the grant did

    assert asyncio.run(r.handle_release_lease_token("tok-1")) is True
    assert h.lease is None
    assert h in r.idle  # back in the pool
    assert r.available.get("CPU") == 4.0  # resources freed
    assert "tok-1" not in r._lease_tokens
    # idempotent: a duplicate release is a no-op
    assert asyncio.run(r.handle_release_lease_token("tok-1")) is False
