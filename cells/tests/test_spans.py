"""The span readers (``cells/spans.py`` and the ten ``*.steady`` readers
that use it) on a trace built by hand and on a miniature cut from a real
v5e trace of ``serve-chat-steady`` (PR 24).  CPU only:

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

from cells import spans, trace  # noqa: E402
from cells.run import reader  # noqa: E402

MS = 1_000_000  # the hand-built trace is written in ms, kept in ns
SHARES = ("idle_host_prepare_pct", "idle_sync_pct", "idle_emit_pct",
          "idle_between_steps_pct", "idle_unattributed_pct")


def read(name, ctx):
    return reader("layer_metrics", name + ".steady")(ctx)


def ctx_of(tr, threads):
    first, last = trace.span(tr)
    return {"trace": tr, "spans": threads,
            "trace_window_s": (last - first) / 1e9}


def ev(name, start, end, **stats):
    return (name, start * MS, (end - start) * MS, stats)


@pytest.fixture
def by_hand():
    """The chip is busy 0-100, 150-300, 340-400, 460-500 ms: three gaps of
    50, 40 and 60 ms in a window of 500.  The engine thread runs two steps
    around them; a request thread waits for the lock twice."""
    busy = [(0, 100), (150, 300), (340, 400), (460, 500)]
    tr = {"device": {0: {trace.OPS_LINE: [
        (f"%fusion.{i} = f32[2] fusion()", s * MS, (e - s) * MS)
        for i, (s, e) in enumerate(busy)], trace.MODULES_LINE: []}},
        "host": {}}
    engine = [
        ev("serve.lock_wait", 90, 120, who="engine"),
        ev("engine.step", 120, 345, queued=1, slots_used=2),
        ev("engine.admit", 125, 160, kind="full", rid=7, queue_wait_ms=30.0,
           prompt_tokens=500, cached_tokens=0, bucket=512),
        ev("engine.dispatch_window", 160, 200, k=4, active=3),
        ev("engine.fetch_window", 200, 320),
        ev("engine.emit", 320, 335, tokens=12),
        ev("engine.retire", 335, 338, n=0),
        ev("serve.deliver", 346, 350, n=1),
        ev("serve.lock_wait", 352, 410, who="engine"),
        ev("engine.step", 410, 480, queued=2, slots_used=3),
        ev("engine.admit", 410, 412, kind="full", rid=8, queue_wait_ms=50.0,
           prompt_tokens=90, cached_tokens=0, bucket=128),
        ev("engine.admit", 412, 412, kind="none"),
        ev("engine.prepare_window", 412, 440),
        ev("engine.dispatch_window", 440, 465, k=4, active=5),
    ]
    request = [ev("serve.lock_wait", 95, 125, who="submit"),
               ev("serve.lock_wait", 300, 310, who="submit")]
    return tr, {"engine#3": engine, "request#9": request}


def test_three_gaps_under_three_spans(by_hand):
    tr, threads = by_hand
    assert spans.idle_intervals(tr) == [
        (100 * MS, 150 * MS), (300 * MS, 340 * MS), (400 * MS, 460 * MS)]
    assert spans.engine_thread(threads) is threads["engine#3"]
    # gap 1: lock wait 100-120, the step's own time 120-125, admit 125-150
    # gap 2: fetch 300-320, emit 320-335, retire 335-338, the step's own -340
    # gap 3: lock wait 400-410, admit 410-412, prepare 412-440, launches -460
    by_group = spans.idle_ns_by_group(tr, threads)
    assert {k: v / MS for k, v in by_group.items()} == {
        "between_steps": 20 + 10, "host_prepare": 25 + 2 + 28 + 20,
        "sync": 20, "emit": 15 + 3, "unattributed": 5 + 2}
    ctx = ctx_of(tr, threads)
    assert ctx["trace_window_s"] == 0.5
    got = {name: read(name, ctx) for name in SHARES}
    assert got == pytest.approx({
        "idle_host_prepare_pct": 15.0, "idle_sync_pct": 4.0,
        "idle_emit_pct": 3.6, "idle_between_steps_pct": 6.0,
        "idle_unattributed_pct": 1.4})
    assert sum(got.values()) == pytest.approx(
        trace.idle_pct(tr, ctx["trace_window_s"])) == pytest.approx(30.0)
    # launches begin at 160 and 440; the fetch ends at 320
    assert read("window_period_ms", ctx) == pytest.approx(280.0)
    assert read("window_host_ms", ctx) == pytest.approx(120.0)
    assert read("decode_batch_size", ctx) == pytest.approx(4.0)
    assert read("submit_lock_wait_ms_mean", ctx) == pytest.approx(20.0)
    assert read("engine_queue_wait_ms_mean", ctx) == pytest.approx(40.0)


def test_readers_on_the_recorded_miniature():
    """The first 2.2 s of a real v5e trace of ``serve-chat-steady`` (my
    chip run, PR 24): every device operation, the engine thread's 25 spans
    and two request threads' waits.  The values below were worked out from
    the listing ``cells/tools/dump_spans.py`` prints of it: two steps that
    each admit (51 ms and 47 + 31 ms of ``engine.admit``), three windows
    launched at 91.108, 1227.294 and 2027.181 ms, fetches ending at 821.477
    and 1958.877 ms, 60.4 ms of ``serve.publish_stats`` after the first
    step and 42 ms at the end of either step under no phase."""
    path = os.path.join(HERE, "mini_serve_spans.json.gz")
    tr, threads = trace.load(path), spans.load(path)
    assert list(tr["device"]) == [0] and len(trace.ops(tr, 0)) > 40000
    assert sorted(len(v) for v in threads.values()) == [1, 1, 25]
    events = spans.engine_thread(threads)
    assert [e[0] for e in events].count("engine.step") == 3
    assert len(trace.decode_program_s(tr)) >= 32  # names were kept
    ctx = ctx_of(tr, threads)
    assert ctx["trace_window_s"] == pytest.approx(2.199930, abs=1e-6)
    got = {name: read(name, ctx) for name in SHARES}
    window_ms = ctx["trace_window_s"] * 1e3
    assert {k: v * window_ms / 100 for k, v in got.items()} == \
        pytest.approx({"idle_host_prepare_pct": 99.270,
                       "idle_sync_pct": 14.297, "idle_emit_pct": 18.924,
                       "idle_between_steps_pct": 60.432,
                       "idle_unattributed_pct": 84.424}, abs=1e-3)
    assert sum(got.values()) == pytest.approx(
        trace.idle_pct(tr, ctx["trace_window_s"]), abs=1e-9)
    assert sum(got.values()) == pytest.approx(12.607, abs=1e-3)
    # (1227.294 - 91.108 + 2027.181 - 1227.294) / 2
    assert read("window_period_ms", ctx) == pytest.approx(968.036, abs=1e-3)
    # (1227.294 - 821.477 + 2027.181 - 1958.877) / 2
    assert read("window_host_ms", ctx) == pytest.approx(237.060, abs=1e-3)
    assert read("decode_batch_size", ctx) == pytest.approx((11 + 13 + 10) / 3)
    assert read("submit_lock_wait_ms_mean", ctx) == pytest.approx(
        (295.318 + 294.674) / 2, abs=1e-3)
    assert read("engine_queue_wait_ms_mean", ctx) == pytest.approx(
        (4.205 + 58.686 + 104.691) / 3)
    # the engine thread is tiled: what no span covers is under 0.3 ms of
    # the 2.2 s, and the step's own time is the 42 ms after each retire
    # (the third step ends past the cut, which took its emit and retire)
    cut = spans.pieces(events)
    assert all(a[1] <= b[0] for a, b in zip(cut, cut[1:]))
    covered = trace.merge((s, e) for s, e, _ in cut)
    first, last = trace.span(tr)
    assert trace.length(trace.subtract([(first, last)], covered)) < 300_000
    own = sorted(e - s for s, e, n in cut if n == spans.STEP)
    assert [round(d / 1e6) for d in own[-3:]] == [41, 43, 54]
    assert sum(own[:-3]) < 500_000


def test_innermost_span_owns_the_time():
    events = [("a", 0, 100, {}), ("b", 10, 30, {}), ("c", 15, 5, {}),
              ("d", 60, 40, {}), ("e", 120, 10, {})]
    assert spans.pieces(events) == [
        (0, 10, "a"), (10, 15, "b"), (15, 20, "c"), (20, 40, "b"),
        (40, 60, "a"), (60, 100, "d"), (120, 130, "e")]
    assert spans.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10


def test_a_program_without_the_spans_reports_nothing(by_hand):
    """The parent commit writes no ``engine.*`` span, a rehearsal has no
    device plane: every reader returns ``None`` and raises nothing."""
    tr, threads = by_hand
    names = SHARES + ("window_period_ms", "window_host_ms",
                      "decode_batch_size", "submit_lock_wait_ms_mean",
                      "engine_queue_wait_ms_mean")
    python_frames_only = ctx_of(tr, {})
    no_device_plane = {"trace": None, "trace_window_s": None}
    other_threads_only = ctx_of(tr, {"request#9": threads["request#9"]})
    for name in names:
        assert read(name, python_frames_only) is None
        assert read(name, no_device_plane) is None
    for name in SHARES + ("window_period_ms", "decode_batch_size"):
        assert read(name, other_threads_only) is None
    assert "spans" not in no_device_plane  # and no file was looked for


def test_every_new_metric_is_an_entry_of_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["source"] == "program_span"]
    assert len(mine) == 10
    for m in mine:
        assert m["layer"] == "serve loop" and m["moves"] == "tpot_ms_p50"
        assert m["workloads"] == ["serve-chat-steady"]
        assert os.path.isfile(os.path.join(CELLS, "layer_metrics",
                                           m["name"] + ".py"))
    assert {m["name"].rsplit(".", 1)[0] for m in mine} >= set(SHARES)
    assert bench["per_layer"][-10:] == mine  # appended, nothing between
