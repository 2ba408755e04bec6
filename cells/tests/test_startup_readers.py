"""The eight readers of the program's build ledger and start-up spans
(``cells/startup.py``) on a span set built by hand.  CPU only:

    JAX_PLATFORMS=cpu python -m pytest cells/tests -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.dirname(HERE)
ROOT = os.path.dirname(CELLS)
sys.path.insert(0, ROOT)

from cells import startup, trace  # noqa: E402
from cells.run import metrics_of, reader  # noqa: E402

MS = 1_000_000
SERVE = ("engine_startup_s", "engine_weights_s", "setup_programs_s",
         "setup_programs_built", "builds_in_trace", "build_ms_in_trace",
         "idle_build_pct")
SNAPSHOT = {"built": 3, "loaded": 212, "build_ms": 4100.0, "load_ms": 900.5,
            "lower_ms": 12000.0, "startup_backend_s": 5.25,
            "startup_weights_s": 21.5, "startup_pool_s": 0.75,
            "startup_total_s": 28.0}


def read(name, ctx):
    return reader("layer_metrics", name)(ctx)


def ev(name, start, end, **stats):
    return (name, start * MS, (end - start) * MS, stats)


def ctx_of(engine, builds, busy=((0, 100), (150, 300), (340, 400),
                                 (460, 500))):
    """The chip is busy 0-100, 150-300, 340-400, 460-500 ms: gaps of 50,
    40 and 60 ms in a window of 500."""
    tr = {"device": {0: {trace.OPS_LINE: [
        (f"%fusion.{i} = f32[2] fusion()", s * MS, (e - s) * MS)
        for i, (s, e) in enumerate(busy)], trace.MODULES_LINE: []}},
        "host": {}}
    first, last = trace.span(tr)
    return {"trace": tr, "spans": {"engine#3": engine} if engine else {},
            "builds": sorted(builds), "trace_window_s": (last - first) / 1e9}


def build(end, ms, program="jit__unknown", cached=0, thread="engine#3"):
    return (end * MS, float(ms), program, cached, thread)


@pytest.fixture
def engine():
    return [ev("engine.step", 10, 330, queued=0, slots_used=2),
            ev("engine.dispatch_window", 20, 60, k=8, active=2),
            ev("serve.publish_stats", 335, 338,
               **dict(SNAPSHOT, built=2, startup_total_s=27.0)),
            ev("engine.step", 340, 480, queued=0, slots_used=2),
            ev("serve.publish_stats", 485, 490, **SNAPSHOT)]


def test_the_last_snapshot_is_the_replicas_setup(engine):
    ctx = ctx_of(engine, [])
    assert read("engine_startup_s.steady", ctx) == 28.0
    assert read("engine_weights_s.steady", ctx) == 21.5
    assert read("setup_programs_built.steady", ctx) == 3
    assert read("setup_programs_s.steady", ctx) == pytest.approx(17.0005)


def test_a_trace_without_a_build_reads_zero(engine):
    ctx = ctx_of(engine, [])
    assert read("builds_in_trace.steady", ctx) == 0
    assert read("build_ms_in_trace.steady", ctx) == 0
    assert read("idle_build_pct.steady", ctx) == 0


def test_builds_in_the_window_and_the_idle_time_under_them(engine):
    """Three builds: 30 ms ending at 140 (the gap 100-150 holds all of it),
    20 ms ending at 310 on a request's thread (10 of them in the gap
    300-340), and one that ended before the first device operation."""
    ctx = ctx_of(engine, [
        build(140, 30), build(310, 20, "jit__lambda", 1, "request#9"),
        build(-5, 400, "jit_before_the_window")])
    assert read("builds_in_trace.steady", ctx) == 2
    assert read("build_ms_in_trace.steady", ctx) == pytest.approx(50.0)
    assert read("idle_build_pct.steady", ctx) == pytest.approx(
        100.0 * (30 + 10) / 500)
    # overlapping builds on two threads are idle time once
    ctx = ctx_of(engine, [build(140, 30), build(145, 30, thread="other#1")])
    assert read("idle_build_pct.steady", ctx) == pytest.approx(
        100.0 * 35 / 500)


def test_without_a_snapshot_the_setup_reads_none_and_builds_still_count():
    engine = [ev("engine.step", 10, 330, queued=0, slots_used=2),
              ev("serve.publish_stats", 335, 338)]  # a program before PR 54
    ctx = ctx_of(engine, [build(140, 30)])
    for name in SERVE[:4]:
        assert read(name + ".steady", ctx) is None
    assert read("builds_in_trace.steady", ctx) == 1


def test_a_program_without_the_ledger_reads_none(engine, monkeypatch):
    """Laid over the parent commit the readers find no ``watch_builds``:
    a zero there would be a count nobody made."""
    monkeypatch.setattr(startup, "has_ledger", lambda: False)
    ctx = ctx_of(engine, [])
    for name in SERVE[4:]:
        assert read(name + ".steady", ctx) is None


def test_without_a_trace_or_spans_every_reader_reads_none(engine):
    for ctx in ({"trace": None, "trace_window_s": None},
                ctx_of(None, [build(140, 30)])):
        for name in SERVE:
            assert read(name + ".steady", ctx) is None


def test_init_s_is_the_driving_process_own_span():
    from ray_tpu._private import tracing

    tracing.clear_local()
    assert read("init_s", {}) is None
    parent = tracing.current_or_root()
    tracing.record_span("init", 100.0, 101.5, parent.child())
    tracing.record_span("init", 200.0, 204.25, parent.child())
    try:
        assert read("init_s", {}) == 4.25  # the last one
    finally:
        tracing.clear_local()


def test_builds_are_read_from_a_profilers_trace(tmp_path):
    """``load_builds`` on a real (CPU) profiler session: the instant lies at
    the build's end and carries its milliseconds and its program."""
    import glob
    import time

    import jax
    import numpy as np

    from ray_tpu._private import tracing

    tracing.watch_builds()

    def startup_probe(x):
        return x * 5 + 2

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        t0 = time.time()
        jax.jit(startup_probe)(np.ones((3, 19), np.float32))
        wall_ms = (time.time() - t0) * 1e3
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    (b,) = [b for b in startup.load_builds(path)
            if b[2] == "jit_startup_probe"]
    assert 0 < b[1] <= wall_ms and b[3] in (0, 1)


@pytest.mark.parametrize("name,cells", [("init_s", 7)] + [
    (n + ".steady", 6) for n in SERVE])
def test_each_reader_has_its_entry_and_its_file(name, cells):
    """Membership, never position: a later PR appends after these."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entry["workloads"]) == cells
    assert entry["moves"] in ("setup_s", "tpot_ms_p50")
    assert callable(reader("layer_metrics", name))
    for cell in entry["workloads"]:
        assert entry in metrics_of(bench, "per_layer", cell)
        moved = [m for m in bench["end_to_end"]
                 if m["name"] == entry["moves"]]
        assert moved and cell in moved[0].get("workloads", [cell])
