"""Causal distributed tracing + step-time attribution (ISSUE 9).

Tiers:

1. **Core units** (no cluster): span/trace context-manager parenting,
   task-context minting, the chrome-trace renderer's phase synthesis,
   disabled-mode no-ops, the span-buffer bound.
2. **Exposition** — Prometheus histogram rendering (cumulative
   ``_bucket`` counts, ``le`` ordering, ``+Inf``, ``_sum``/``_count``
   consistency) and label-value escaping, plus the publisher interval
   env and the dashboard aggregator's stale sweep.
3. **E2E** — a driver→actor→nested-task→collective-op chain exports ONE
   connected trace: shared trace_id, every parent link resolves,
   submit/queue/execute phases present, owner-side lease span present.
4. **Bench attribution** — ``bench.measure_step_breakdown`` buckets sum
   to the step wall within 10% and the instrumentation overhead with
   tracing off stays <2%.
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import pytest

import ray_tpu
from ray_tpu._private import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh_tracing(monkeypatch):
    """Enabled tracing + a clean span buffer, restored afterwards."""
    monkeypatch.setenv(tracing.ENV_ENABLED, "1")
    tracing.clear_local()
    yield
    tracing.clear_local()


# ---------------------------------------------------------------------------
# 1. core units
# ---------------------------------------------------------------------------


class TestTraceCore:
    def test_span_nesting_parents(self, fresh_tracing):
        with tracing.trace("root") as root:
            assert root.parent_span_id is None
            with tracing.span("outer") as outer:
                assert outer.trace_id == root.trace_id
                assert outer.parent_span_id == root.span_id
                with tracing.span("inner") as inner:
                    assert inner.parent_span_id == outer.span_id
        spans = {s["name"]: s for s in tracing.local_spans()}
        assert spans["inner"]["parent_span_id"] == spans["outer"]["span_id"]
        assert spans["outer"]["parent_span_id"] == spans["root"]["span_id"]
        assert spans["root"]["parent_span_id"] is None
        assert len({s["trace_id"] for s in
                    (spans["root"], spans["outer"], spans["inner"])}) == 1
        # completed spans have sane timestamps
        assert all(s["end"] >= s["start"] for s in spans.values())

    def test_trace_mints_fresh_trace_ids(self, fresh_tracing):
        with tracing.trace("a") as a:
            pass
        with tracing.trace("b") as b:
            pass
        assert a.trace_id != b.trace_id

    def test_mint_task_context_parents_to_current(self, fresh_tracing):
        with tracing.trace("root") as root:
            tc = tracing.mint_task_context("fn")
        assert tc["trace_id"] == root.trace_id
        assert tc["parent_span_id"] == root.span_id
        assert tc["span_id"] != root.span_id
        assert tc["submitted_at"] <= time.time()

    def test_mint_without_scope_uses_process_root(self, fresh_tracing):
        tc = tracing.mint_task_context("fn")
        assert tc["parent_span_id"] is not None
        # the lazy root is exported as an open span so the link resolves
        roots = [s for s in tracing.local_spans()
                 if s["span_id"] == tc["parent_span_id"]]
        assert roots and roots[0].get("open")

    def test_task_scope_installs_context(self, fresh_tracing):
        tc = {"trace_id": "t" * 16, "span_id": "s" * 12,
              "parent_span_id": None}
        with tracing.task_scope(tc):
            cur = tracing.current()
            assert cur.trace_id == tc["trace_id"]
            assert cur.span_id == tc["span_id"]
            child = tracing.mint_task_context("nested")
            assert child["parent_span_id"] == tc["span_id"]
        assert tracing.current() is None

    def test_disabled_mode_records_nothing(self, monkeypatch):
        monkeypatch.setenv(tracing.ENV_ENABLED, "0")
        tracing.clear_local()
        assert tracing.mint_task_context("fn") is None
        with tracing.span("y") as ctx:
            assert ctx is None
        with tracing.trace("z") as ctx:
            assert ctx is None
        assert tracing.local_spans() == []
        tracing.clear_local()

    def test_note_duration_sink_routing(self):
        got = []
        token = tracing.register_duration_sink(
            lambda b, s: got.append((b, s)))
        try:
            tracing.note_duration("compute", 0.5)
        finally:
            tracing.unregister_duration_sink(token)
        tracing.note_duration("compute", 0.25)  # after unregister: dropped
        assert got == [("compute", 0.5)]

    def test_chrome_renderer_synthesizes_phases(self):
        now = time.time()
        ev = {
            "task_id": "ab" * 8, "name": "myfn", "kind": "NORMAL_TASK",
            "start": now - 1.0, "end": now, "ok": True,
            "worker_id": "w1", "node_id": "n1",
            "trace": {"trace_id": "t1", "span_id": "s1",
                      "parent_span_id": "p1",
                      "submitted_at": now - 3.0, "received_at": now - 2.0},
        }
        legacy = {"task_id": "cd" * 8, "name": "oldfn", "start": now - 1.0,
                  "end": now, "ok": True, "worker_id": "w1",
                  "node_id": "n1"}
        out = tracing.chrome_trace_events([ev, legacy])
        by_phase = {e["args"].get("phase"): e for e in out
                    if "phase" in e.get("args", {})}
        assert set(by_phase) == {"task", "submit", "queue", "execute"}
        task = by_phase["task"]
        assert task["ts"] == pytest.approx((now - 3.0) * 1e6)
        assert task["args"]["parent_span_id"] == "p1"
        for phase in ("submit", "queue", "execute"):
            assert by_phase[phase]["args"]["parent_span_id"] == "s1"
            assert by_phase[phase]["args"]["span_id"] == f"s1.{phase}"
        assert by_phase["submit"]["dur"] == pytest.approx(1e6)
        assert by_phase["queue"]["dur"] == pytest.approx(1e6)
        assert by_phase["execute"]["dur"] == pytest.approx(1e6)
        # legacy event renders exactly as the old execution box
        old = [e for e in out if e["name"] == "oldfn"]
        assert len(old) == 1 and "trace_id" not in old[0]["args"]

    def test_span_buffer_bounded(self, fresh_tracing):
        cap = tracing._buffer_cap()
        with tracing.trace("flood"):
            for i in range(cap + 50):
                with tracing.span(f"s{i}"):
                    pass
        assert len(tracing.local_spans()) <= cap + 1  # + the open root


# ---------------------------------------------------------------------------
# 2. exposition + publisher satellites
# ---------------------------------------------------------------------------


class TestPrometheusExposition:
    def test_histogram_exposition_contract(self):
        from ray_tpu.util import metrics

        h = metrics.Histogram("tt_hist_contract", "hist under test",
                              boundaries=[0.1, 1.0, 5.0],
                              tag_keys=("route",))
        for v in (0.05, 0.5, 0.7, 2.0, 50.0):
            h.observe(v, tags={"route": "/a"})
        text = metrics.prometheus_text(metrics.collect_local())
        lines = [ln for ln in text.splitlines()
                 if ln.startswith("tt_hist_contract")]
        bucket_lines = [ln for ln in lines if "_bucket" in ln]
        # le ordering: finite ascending then +Inf
        les = [ln.split('le="')[1].split('"')[0] for ln in bucket_lines]
        assert les == ["0.1", "1.0", "5.0", "+Inf"]
        counts = [float(ln.rsplit(" ", 1)[1]) for ln in bucket_lines]
        # cumulative and monotone: 1 obs <=0.1, 3 <=1.0, 4 <=5.0, 5 total
        assert counts == [1, 3, 4, 5]
        inf_count = counts[-1]
        count_line = next(ln for ln in lines if "_count" in ln)
        sum_line = next(ln for ln in lines if "_sum" in ln)
        assert float(count_line.rsplit(" ", 1)[1]) == inf_count == 5
        assert float(sum_line.rsplit(" ", 1)[1]) == pytest.approx(53.25)
        # TYPE declared before samples
        assert text.index("# TYPE tt_hist_contract histogram") \
            < text.index(bucket_lines[0])

    def test_label_value_escaping(self):
        from ray_tpu.util import metrics

        c = metrics.Counter("tt_escape_counter", "desc", tag_keys=("path",))
        c.inc(1.0, tags={"path": 'a\\b"c\nd'})
        text = metrics.prometheus_text(metrics.collect_local())
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("tt_escape_counter{"))
        assert '\\\\b' in line          # backslash escaped
        assert '\\"c' in line           # quote escaped
        assert "\n" not in line         # newline never raw inside a line
        assert "\\n" in line            # ... it is escaped instead
        # the label block still parses as one balanced {...} token
        assert line.count("{") == 1 and line.count("}") == 1

    def test_histogram_label_escaping(self):
        from ray_tpu.util import metrics

        h = metrics.Histogram("tt_escape_hist", "h", boundaries=[1.0],
                              tag_keys=("q",))
        h.observe(0.5, tags={"q": 'x"y'})
        text = metrics.prometheus_text(metrics.collect_local())
        assert 'q="x\\"y"' in text

    def test_publish_interval_env(self, monkeypatch):
        from ray_tpu.util import metrics

        monkeypatch.delenv(metrics.ENV_PUBLISH_INTERVAL, raising=False)
        assert metrics.publish_interval_s() == 5.0
        monkeypatch.setenv(metrics.ENV_PUBLISH_INTERVAL, "0.7")
        assert metrics.publish_interval_s() == pytest.approx(0.7)
        monkeypatch.setenv(metrics.ENV_PUBLISH_INTERVAL, "0.01")
        assert metrics.publish_interval_s() == 0.2  # floored
        monkeypatch.setenv(metrics.ENV_PUBLISH_INTERVAL, "junk")
        assert metrics.publish_interval_s() == 5.0

    def test_final_publish_lands_in_kv(self, ray_start):
        from ray_tpu.experimental.internal_kv import _internal_kv_get_prefix
        from ray_tpu.util import metrics

        c = metrics.Counter("tt_final_publish", "final-flush proof")
        c.inc(3.0)
        metrics.final_publish()  # no interval wait
        table = _internal_kv_get_prefix("metrics/", namespace="metrics")
        found = [json.loads(raw) for raw in table.values()]
        assert any("tt_final_publish" in rec.get("metrics", {})
                   for rec in found)

    def test_aggregator_sweeps_stale_workers(self):
        import types

        from ray_tpu.dashboard.modules.metrics import (STALE_S,
                                                       aggregate_metrics)

        now = time.time()
        fresh = json.dumps({"ts": now, "metrics": {
            "m": {"kind": "gauge", "series": [{"tags": {}, "value": 1.0}]}}})
        stale = json.dumps({"ts": now - STALE_S - 60, "metrics": {
            "dead": {"kind": "gauge",
                     "series": [{"tags": {}, "value": 9.0}]}}})
        stale_trace = json.dumps({"ts": now - STALE_S - 60, "spans": []})
        gcs = types.SimpleNamespace(kv={
            ("metrics", "metrics/live"): fresh,
            ("metrics", "metrics/dead"): stale,
            ("trace", "spans/dead"): stale_trace,
            ("other", "key"): b"untouched",
        }, _dirty=False)
        merged = aggregate_metrics(gcs)
        assert "m" in merged and "dead" not in merged
        # stale records deleted from the KV itself, fresh ones kept
        assert ("metrics", "metrics/dead") not in gcs.kv
        assert ("trace", "spans/dead") not in gcs.kv
        assert ("metrics", "metrics/live") in gcs.kv
        assert ("other", "key") in gcs.kv
        assert gcs._dirty


# ---------------------------------------------------------------------------
# 3. e2e: one connected trace across driver→actor→nested task→collective
# ---------------------------------------------------------------------------


def _trace_events(events, trace_id):
    return [e for e in events
            if (e.get("args") or {}).get("trace_id") == trace_id]


def _connected(events, trace_id):
    """True when every span of the trace is reachable from its root."""
    mine = _trace_events(events, trace_id)
    ids = {e["args"]["span_id"] for e in mine}
    roots = [e for e in mine if e["args"].get("parent_span_id") is None]
    if not roots:
        return False
    children = {}
    for e in mine:
        p = e["args"].get("parent_span_id")
        if p is not None:
            children.setdefault(p, []).append(e["args"]["span_id"])
    seen = set()
    stack = [r["args"]["span_id"] for r in roots]
    while stack:
        s = stack.pop()
        if s in seen:
            continue
        seen.add(s)
        stack.extend(children.get(s, ()))
    return seen == ids


def test_connected_trace_driver_actor_nested_collective(
        no_cluster, monkeypatch):
    """The acceptance chain: driver→actor→nested-task→collective-op must
    export ONE connected trace — shared trace_id, every parent link
    resolving, submit/queue/execute phases and an owner-side lease span
    present."""
    import uuid

    monkeypatch.setenv("RAY_TPU_METRICS_INTERVAL_S", "0.5")
    monkeypatch.setenv(tracing.ENV_ENABLED, "1")
    tracing.clear_local()
    ray_tpu.init(num_cpus=8, num_tpus=0)
    from ray_tpu.util import state as state_api

    @ray_tpu.remote
    class ChainWorker:
        def __init__(self, rank, world):
            self.rank = rank
            self.world = world

        def setup(self, name):
            from ray_tpu.util import collective as col

            col.init_collective_group(self.world, self.rank, "tcp", name)
            return self.rank

        def run_chain(self, name):
            import numpy as np

            import ray_tpu as rt
            from ray_tpu.util import collective as col

            @rt.remote
            def nested(x):
                return x + 1

            val = rt.get(nested.remote(self.rank), timeout=60)
            out = col.allreduce(np.ones(4), name)
            return val + float(out[0])

    group = f"trace-{uuid.uuid4().hex[:8]}"
    workers = [ChainWorker.remote(i, 2) for i in range(2)]
    ray_tpu.get([w.setup.remote(group) for w in workers], timeout=120)

    with tracing.trace("e2e-chain") as root:
        outs = ray_tpu.get([w.run_chain.remote(group) for w in workers],
                           timeout=120)
    assert sorted(outs) == [3.0, 4.0]
    trace_id = root.trace_id

    deadline = time.time() + 45
    last = []
    while time.time() < deadline:
        last = state_api.timeline()
        mine = _trace_events(last, trace_id)
        names = [e["name"] for e in mine]
        phases = {e["args"].get("phase") for e in mine}
        if (names.count("run_chain") >= 2
                and any(n.endswith("nested") for n in names)
                and any(n.startswith("collective.") for n in names)
                and "lease" in names
                and {"submit", "queue", "execute"} <= phases
                and _connected(last, trace_id)):
            break
        time.sleep(0.5)

    mine = _trace_events(last, trace_id)
    names = [e["name"] for e in mine]
    assert names.count("run_chain") >= 2, names
    assert any(n.endswith("nested") for n in names), names
    assert any(n.startswith("collective.") for n in names), names
    assert "lease" in names, names
    phases = {e["args"].get("phase") for e in mine}
    assert {"submit", "queue", "execute"} <= phases, phases
    # ONE connected tree: every parent link resolves from the root
    assert _connected(last, trace_id), \
        [(e["name"], e["args"].get("span_id"),
          e["args"].get("parent_span_id")) for e in mine]
    # the nested task's parent is one of the actor-task spans
    chain_ids = {e["args"]["span_id"] for e in mine
                 if e["name"] == "run_chain"
                 and e["args"].get("phase") == "task"}
    nested_parents = {e["args"]["parent_span_id"] for e in mine
                      if e["name"].endswith("nested")
                      and e["args"].get("phase") == "task"}
    assert nested_parents and nested_parents <= chain_ids
    # the collective spans hang off the actor-task spans too
    coll_parents = {e["args"]["parent_span_id"] for e in mine
                    if e["name"].startswith("collective.")}
    assert coll_parents <= chain_ids, (coll_parents, chain_ids)
    ray_tpu.shutdown()


def test_timeline_file_is_valid_chrome_trace(ray_start, tmp_path):
    """timeline(filename) writes loadable chrome-trace JSON whose traced
    tasks carry the new phase spans."""
    from ray_tpu.util import state as state_api

    @ray_tpu.remote
    def traced_for_phases():
        return 1

    assert ray_tpu.get([traced_for_phases.remote() for _ in range(2)],
                       timeout=120) == [1, 1]
    out = str(tmp_path / "timeline.json")
    deadline = time.time() + 30
    phases = set()
    while time.time() < deadline:  # task events flush every ~2s
        events = state_api.timeline(out)
        phases = {e["args"].get("phase") for e in events
                  if isinstance(e.get("args"), dict)
                  and str(e["args"].get("task", "")).endswith(
                      "traced_for_phases")}
        if {"submit", "queue", "execute"} <= phases:
            break
        time.sleep(0.5)
    assert {"submit", "queue", "execute"} <= phases, phases
    loaded = json.load(open(out))
    assert isinstance(loaded, list) and loaded
    for e in loaded:
        assert "ph" in e and "ts" in e and "name" in e
        if e["ph"] == "X":
            assert e["dur"] > 0


def test_serve_request_context_carries_trace(fresh_tracing):
    """The serving plane: a request scope installs the request's trace
    root, so handle calls made inside parent to it."""
    from ray_tpu.serve import context as serve_ctx

    with serve_ctx.request_scope(timeout_s=5.0) as rc:
        assert rc.trace_ctx is not None
        cur = tracing.current()
        assert cur is not None
        assert cur.trace_id == rc.trace_ctx["trace_id"]
        minted = tracing.mint_task_context("replica_call")
        assert minted["trace_id"] == rc.trace_ctx["trace_id"]
        assert minted["parent_span_id"] == rc.trace_ctx["span_id"]
    # the request root span was recorded, so the parent link resolves
    roots = [s for s in tracing.local_spans()
             if s["name"] == "serve.request"
             and s["span_id"] == rc.trace_ctx["span_id"]]
    assert roots
    # round-trips through the wire dict (proxy→router→replica hop)
    again = serve_ctx.RequestContext.from_dict(rc.to_dict())
    assert again.trace_ctx == rc.trace_ctx


# ---------------------------------------------------------------------------
# 4. step-time attribution: ledger units + the bench contract
# ---------------------------------------------------------------------------


class TestStepLedger:
    def test_buckets_and_other(self, fresh_tracing):
        from ray_tpu.train.session import StepLedger

        led = StepLedger(group_name="t", publish=False)
        with led.step():
            with led.bucket("compute"):
                time.sleep(0.05)
            t0 = time.perf_counter()
            time.sleep(0.02)
            # the sink route every auto-attributed subsystem uses
            tracing.note_duration("collective_wait",
                                  time.perf_counter() - t0)
        bd = led.last_breakdown()
        assert bd["step"] == 1
        b = bd["buckets"]
        assert b["compute"] >= 0.05
        assert b["collective_wait"] >= 0.02
        assert b["other"] >= 0.0
        # every second of the step is accounted: buckets (incl. other)
        # reconstruct the measured wall
        assert sum(b.values()) == pytest.approx(bd["wall_s"], rel=0.05)

    def test_no_charge_between_steps(self, fresh_tracing):
        from ray_tpu.train.session import StepLedger

        led = StepLedger(publish=False)
        tracing.note_duration("collective_wait", 5.0)  # no step: dropped
        with led.step():
            pass
        assert led.last_breakdown()["buckets"].get(
            "collective_wait", 0.0) == 0.0

    def test_step_does_not_nest(self, fresh_tracing):
        from ray_tpu.train.session import StepLedger

        led = StepLedger(publish=False)
        with led.step():
            with pytest.raises(RuntimeError):
                with led.step():
                    pass

    def test_step_emits_span_and_histogram(self, fresh_tracing):
        from ray_tpu.train.session import StepLedger
        from ray_tpu.util import metrics

        led = StepLedger(group_name="span-check", publish=False)
        with led.step():
            with led.bucket("compute"):
                pass
        spans = [s for s in tracing.local_spans()
                 if s["name"] == "train.step"]
        assert spans and spans[-1]["attrs"]["group"] == "span-check"
        snap = metrics.collect_local()
        hist = snap["train_step_bucket_s"]["histogram"]
        assert any(h["tags"].get("group") == "span-check" for h in hist)


# ---------------------------------------------------------------------------
# 5. the build ledger (watch_builds) and the start-up spans
# ---------------------------------------------------------------------------


def _builds(name=None):
    return [s for s in tracing.local_spans(include_open=False)
            if s["name"] == "xla.build"
            and (name is None or s["attrs"]["program"] == name)]


def _built_or_loaded():
    c = tracing.build_counters()
    return c["built"] + c["loaded"]


class TestBuildLedger:
    def test_watching_twice_registers_one_listener_of_each_kind(self):
        from jax._src import monitoring

        assert tracing.watch_builds() is True
        assert tracing.watch_builds() is True
        assert monitoring.get_event_time_span_listeners().count(
            tracing._on_build_span) == 1
        assert monitoring.get_event_listeners().count(
            tracing._on_build_event) == 1

    def test_a_new_shape_is_one_build_under_its_name(self, fresh_tracing):
        import jax.numpy as jnp
        import numpy as np

        tracing.watch_builds()

        def ledger_probe(x):
            return jnp.tanh(x) * 2 + 1

        fn = jax.jit(ledger_probe)
        x = np.ones((3, 11), np.float32)
        before, c0 = _built_or_loaded(), tracing.build_counters()
        with tracing.span("probe.caller") as ctx:
            t0 = time.time()
            fn(x).block_until_ready()
            t1 = time.time()
        assert _built_or_loaded() == before + 1
        (b,) = _builds("jit_ledger_probe")
        assert t0 <= b["start"] <= b["end"] <= t1
        assert b["attrs"]["cached"] in (0, 1)
        assert 0 < b["attrs"]["lower_ms"] <= (b["start"] - t0) * 1e3 + 1
        # under the context that paid for it, as the timeline shows it
        assert b["trace_id"] == ctx.trace_id
        assert b["parent_span_id"] == ctx.span_id
        c1 = tracing.build_counters()
        grown = (c1["build_s"] + c1["load_s"] + c1["lower_s"]
                 - c0["build_s"] - c0["load_s"] - c0["lower_s"])
        assert 0 < grown <= t1 - t0
        # the same shape again: nothing is traced, lowered or built
        fn(x).block_until_ready()
        assert tracing.build_counters() == c1
        assert len(_builds("jit_ledger_probe")) == 1

    def test_a_program_from_the_persistent_cache_is_loaded(
            self, fresh_tracing, tmp_path):
        import numpy as np
        from jax._src import compilation_cache

        tracing.watch_builds()
        keep = {k: getattr(jax.config, k) for k in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")}
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        try:
            def cached_probe(x):
                return x * 3 - 1

            x = np.ones((5, 13), np.float32)
            c0 = tracing.build_counters()
            jax.jit(cached_probe)(x).block_until_ready()
            c1 = tracing.build_counters()
            assert (c1["built"], c1["loaded"]) == (
                c0["built"] + 1, c0["loaded"])
            assert any(f.startswith("jit_cached_probe-")
                       for f in os.listdir(tmp_path))
            jax.clear_caches()  # what a new process starts with
            jax.jit(cached_probe)(x).block_until_ready()
            c2 = tracing.build_counters()
            assert (c2["built"], c2["loaded"]) == (
                c1["built"], c1["loaded"] + 1)
            assert c2["load_s"] > c1["load_s"]
            assert c2["build_s"] == c1["build_s"]
            assert [b["attrs"]["cached"]
                    for b in _builds("jit_cached_probe")] == [0, 1]
        finally:
            for k, v in keep.items():
                jax.config.update(k, v)
            compilation_cache.reset_cache()

    def test_switched_off_the_counters_rise_and_no_span_is_written(
            self, fresh_tracing, monkeypatch):
        import numpy as np

        tracing.watch_builds()
        monkeypatch.setenv(tracing.ENV_ENABLED, "0")
        before = _built_or_loaded()
        jax.jit(lambda x: x + 7)(np.ones((2, 17), np.float32))
        assert _built_or_loaded() == before + 1
        assert tracing.local_spans() == []

    def test_nested_phases_are_counted_once(self, fresh_tracing):
        """A constant folded inside a trace: its own trace, lowering and
        build lie inside the outer function's trace, which counts only
        what they left."""
        compile_ = "/jax/core/compile/"
        c0 = tracing.build_counters()

        def on_a_thread():
            for event, s, e in (("jaxpr_trace_duration", 2, 3),
                                ("jaxpr_to_mlir_module_duration", 3, 4),
                                ("backend_compile_duration", 4, 6),
                                ("jaxpr_trace_duration", 1, 10),
                                ("jaxpr_to_mlir_module_duration", 10, 11),
                                ("backend_compile_duration", 11, 15)):
                tracing._on_build_span(compile_ + event, float(s), float(e),
                                       fun_name="jit(<unknown>)")

        t = threading.Thread(target=on_a_thread)
        t.start()
        t.join()
        c1 = tracing.build_counters()
        assert c1["built"] - c0["built"] == 2
        assert c1["build_s"] - c0["build_s"] == pytest.approx(6.0)
        assert c1["lower_s"] - c0["lower_s"] == pytest.approx(8.0)
        inner, outer = _builds("jit__unknown")
        assert inner["attrs"]["lower_ms"] == pytest.approx(2000.0)
        assert outer["attrs"]["lower_ms"] == pytest.approx(6000.0)

    def test_a_process_without_jax_registers_nothing(self):
        code = ("import sys\n"
                "from ray_tpu._private import tracing\n"
                "assert tracing.watch_builds() is False\n"
                "assert tracing.build_counters()['built'] == 0\n"
                "assert not [m for m in sys.modules if m == 'jax' "
                "or m.startswith('jax.')]\n"
                "print('ok')\n")
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, env=dict(os.environ, PYTHONPATH=ROOT))
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert proc.stdout.strip() == "ok"


def test_chip_acquire_is_recorded_once_by_whoever_first_touches_the_backend(
        fresh_tracing, monkeypatch):
    from ray_tpu._private import accelerators

    accelerators.record_chip_acquire()  # not bound to chips: nothing
    assert tracing.local_spans() == []
    now = time.time()
    monkeypatch.setattr(accelerators, "worker_started_at", now - 2.0)
    monkeypatch.setattr(accelerators, "_bound", (4, now - 0.5))
    monkeypatch.setenv(tracing.ENV_ENABLED, "0")
    accelerators.record_chip_acquire()  # switched off: consumed, no span
    assert tracing.local_spans() == [] and accelerators._bound is None
    monkeypatch.setenv(tracing.ENV_ENABLED, "1")
    monkeypatch.setattr(accelerators, "_bound", (4, now - 0.5))
    jax.devices()
    accelerators.record_chip_acquire()
    accelerators.record_chip_acquire()  # the second toucher: nothing
    (s,) = [s for s in tracing.local_spans(include_open=False)
            if s["name"] == "worker.chip_acquire"]
    assert s["attrs"] == {"chips": 4, "bound_s": 1.5}
    assert s["start"] == now - 2.0 and now <= s["end"] < now + 5.0
    assert not [t for t in threading.enumerate() if "chip" in t.name]


def _children(spans, parent_name):
    (parent,) = [s for s in spans if s["name"] == parent_name]
    return parent, [s for s in spans
                    if s.get("parent_span_id") == parent["span_id"]]


def test_init_fit_and_shutdown_leave_their_spans_in_the_buffer(
        no_cluster, fresh_tracing):
    """The driver's buffer outlives ``shutdown()`` (nothing is left to
    publish to): the benchmark's ``init_s`` reads it there."""
    from ray_tpu.train import JaxTrainer, ScalingConfig

    def loop():
        from ray_tpu import train

        train.get_context().get_mesh()
        train.report({"spans": [
            s["name"] for s in tracing.local_spans(include_open=False)]})

    t0 = time.time()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    t1 = time.time()
    result = JaxTrainer(loop, scaling_config=ScalingConfig(
        num_workers=1)).fit()
    assert result.error is None
    # a worker bound to no chip records no ``worker.chip_acquire``
    assert "worker.chip_acquire" not in result.metrics["spans"]
    ray_tpu.shutdown()
    assert not ray_tpu.is_initialized()
    spans = tracing.local_spans(include_open=False)

    init, parts = _children(spans, "init")
    assert t0 <= init["start"] and init["end"] <= t1
    assert [s["name"] for s in sorted(parts, key=lambda s: s["start"])] == [
        "init.start_head", "init.gcs", "init.raylet", "init.connect"]
    # the phases account for the call: their self times sum to it
    assert sum(s["end"] - s["start"] for s in parts) == pytest.approx(
        init["end"] - init["start"], rel=0.05, abs=0.1)
    head, head_parts = _children(spans, "init.start_head")
    assert [s["name"].rsplit(".", 1)[1] for s in sorted(
        head_parts, key=lambda s: s["start"])] == [
        "python", "imports", "gcs", "raylet", "services"]
    assert sum(s["end"] - s["start"] for s in head_parts) == pytest.approx(
        head["end"] - head["start"], rel=0.05, abs=0.1)

    startup, parts = _children(spans, "train.startup")
    assert startup["attrs"]["workers"] == 1
    assert [s["name"] for s in sorted(parts, key=lambda s: s["start"])] == [
        "train.startup.workers", "train.startup.mesh"]

    down, (wait,) = _children(spans, "shutdown")
    assert wait["name"] == "shutdown.wait"
    assert wait["attrs"]["what"] == "head" and wait["attrs"]["pid"] > 0
    assert down["start"] <= wait["start"] <= wait["end"] <= down["end"]
    # a second shutdown has nothing to do and records nothing
    ray_tpu.shutdown()
    assert len([s for s in tracing.local_spans()
                if s["name"] == "shutdown"]) == 1
