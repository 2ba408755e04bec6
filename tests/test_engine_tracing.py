"""The serve loop's spans in a profiler trace (``tracing.annotate``).

Each test takes a real ``jax.profiler`` session on the CPU backend into
``tmp_path`` and reads the annotations back with ``ProfileData``: names,
nesting and stats are what a chip run's trace carries on the device's
clock (``cells/spans.py`` reads the same events there).
"""

import glob
import os
import statistics
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu._private import tracing
from ray_tpu.models.generation import SamplingParams
from ray_tpu.models.llama import LlamaConfig, llama_init

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("engine.admit", "engine.first_tokens", "engine.verify",
          "engine.prepare_window", "engine.dispatch_window",
          "engine.fetch_window", "engine.emit", "engine.retire")
TILE_NS = 50_000


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    return cfg, llama_init(jax.random.PRNGKey(0), cfg)


class _Profile:
    """``with _Profile(dir): ...`` then ``.threads``: per host thread the
    ``engine.*``/``serve.*`` annotation events as (name, start_ns, end_ns,
    stats), sorted by start."""

    def __init__(self, trace_dir):
        self.dir = str(trace_dir)

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # annotations only: a small trace
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        from jax.profiler import ProfileData

        path = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        self.path = path
        self.threads = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:CPU"):
                continue
            for line in plane.lines:
                events = sorted((
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                     dict(e.stats))
                    for e in line.events
                    if e.name.startswith(("engine.", "serve.", "probe."))),
                    key=lambda e: (e[1], -e[2]))
                if events:
                    self.threads.append(events)
        return False

    def named(self, name):
        return [e for t in self.threads for e in t if e[0] == name]

    def thread_of(self, name):
        return next(t for t in self.threads if any(e[0] == name for e in t))


def _largest_hole(outer, children):
    """ns of the longest stretch of ``outer`` that no child covers."""
    cur, worst = outer[1], 0
    for _, s, e, _ in sorted(children, key=lambda c: c[1]):
        worst = max(worst, s - cur)
        cur = max(cur, e)
    return max(worst, outer[2] - cur)


@pytest.mark.parametrize("case,kwargs", [
    ("window", {}),
    ("speculative", {"spec_tokens": 3}),
    ("chunked", {"prefill_chunk": 16}),
])
def test_engine_step_is_tiled_by_its_phases(tiny, tmp_path, case, kwargs):
    from ray_tpu.llm import LLMEngine

    cfg, params = tiny
    eng = LLMEngine(cfg, params, batch_slots=2, max_len=128,
                    decode_window=4, **kwargs)
    sp = SamplingParams(temperature=0.0, max_tokens=22)
    # repetitive prompts so the speculative arm drafts; a 40-token one so
    # the chunked case prefills in chunks
    def prompts(base):
        return [[base, 4, 5, 4, 5, 4, 5, 4], [base + 1] + [7, 8] * 20,
                [base + 2, 9, 9, 9, 9]]
    # compile every program first; the profiled pass sends other prompts
    # of the same lengths (these would hit the prefix cache)
    eng.generate(prompts(10), sp)
    # what each window's slots hold as it is dispatched, from the host
    # mirror: _window_arity is the last thing prepare_window asks (the
    # verify arm asks it too, for the window it would displace)
    held, verifying = [], []
    arity, speculate = eng._window_arity, eng._try_speculate

    def recording_arity(active):
        if not verifying:
            held.append(int(sum(eng._cur_len[i] for i in active)))
        return arity(active)

    def flagged_speculate(active):
        verifying.append(True)
        try:
            return speculate(active)
        finally:
            verifying.pop()
    eng._window_arity = recording_arity
    eng._try_speculate = flagged_speculate
    with _Profile(tmp_path) as prof:
        outs = eng.generate(prompts(20), sp)
    assert all(len(o.token_ids) == 22 for o in outs)

    (thread,) = [t for t in prof.threads
                 if any(e[0] == "engine.step" for e in t)]
    steps = [e for e in thread if e[0] == "engine.step"]
    assert len(steps) >= 8
    holes, seen = [], set()
    for step in steps:
        inside = [e for e in thread if e[0] != "engine.step"
                  and step[1] <= e[1] and e[2] <= step[2]]
        assert {e[0] for e in inside} <= set(PHASES)
        # phases of one step do not overlap one another
        ordered = sorted(inside, key=lambda e: e[1])
        assert all(a[2] <= b[1] for a, b in zip(ordered, ordered[1:]))
        assert ordered[-1][0] == "engine.retire"
        seen |= {e[0] for e in inside}
        holes.append(_largest_hole(step, inside))
        assert set(step[3]) == {"queued", "slots_used"}
    # every annotation on the thread lies inside some engine.step
    assert all(any(s[1] <= e[1] and e[2] <= s[2] for s in steps)
               for e in thread)
    # tiled to 50 us: the median step's largest hole (one preempted
    # thread on a loaded box may not fail the test; a missing phase would
    # leave its hole in every step)
    assert statistics.median(holes) < TILE_NS, holes
    want = {"engine.admit", "engine.first_tokens", "engine.retire"}
    if case == "speculative":
        want |= {"engine.verify"}
        assert eng.spec_stats["verify_steps"] > 0
    else:
        want |= {"engine.prepare_window", "engine.dispatch_window",
                 "engine.fetch_window", "engine.emit"}
    assert want <= seen, seen
    admits = prof.named("engine.admit")
    kinds = {e[3]["kind"] for e in admits}
    assert "full" in kinds
    if case == "chunked":
        assert "partial" in kinds and eng.prefill_stats["chunks"] > 0
    full = [e[3] for e in admits if e[3]["kind"] == "full"]
    assert len(full) == 3
    assert {s["prompt_tokens"] for s in full} == {8, 41, 5}
    assert all(s["queue_wait_ms"] >= 0 and s["bucket"] >= 1
               and s["cached_tokens"] % eng.bs == 0 for s in full)
    windows = prof.named("engine.dispatch_window")
    for e in windows:
        assert 1 <= e[3]["k"] <= 4 and 1 <= e[3]["active"] <= 2
        assert e[3]["attn"] == eng.attn == "gather"  # CPU backend
    assert [e[3]["live_tokens"] for e in windows] == held
    emitted = sum(e[3]["tokens"] for e in prof.named("engine.emit"))
    assert sum(e[3]["n"] for e in prof.named("engine.first_tokens")) == 3
    if case != "speculative":
        assert emitted == 3 * 22 - 3  # all but the three first tokens
    assert sum(e[3]["n"] for e in prof.named("engine.retire")) == 3


@pytest.fixture
def server(tiny):
    from ray_tpu.llm.serving import LLMServer

    cfg, params = tiny
    srv = LLMServer._target(
        {"cfg": cfg, "params": params, "batch_slots": 4, "max_len": 128,
         "decode_window": 4}, 1)
    srv({"prompt": "warm the programs up", "max_tokens": 9,
         "temperature": 0.0})
    yield srv
    srv._stop = True
    srv._loop.join(timeout=10)
    assert not srv._loop.is_alive()


def test_engine_thread_is_covered_and_submit_waits_are_named(server,
                                                             tmp_path):
    body = {"prompt": "spans on one clock", "max_tokens": 9,
            "temperature": 0.0}
    with _Profile(tmp_path) as prof:
        threads = [threading.Thread(target=server, args=(body,))
                   for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        time.sleep(0.05)  # a few idle turns of the loop
    engine_thread = prof.thread_of("engine.step")
    top = [e for e in engine_thread
           if not any(o is not e and o[1] <= e[1] and e[2] <= o[2]
                      for o in engine_thread)]
    assert {e[0] for e in top} <= {
        "serve.lock_wait", "engine.step", "serve.deliver",
        "serve.publish_stats", "serve.settle", "serve.idle"}
    assert {"engine.step", "serve.deliver", "serve.idle"} <= {
        e[0] for e in top}
    # between the first and the last top-level span the thread is covered
    # (what is left is the loop's own bytecode between two phases: tens
    # of microseconds a turn, most of it waking from the idle sleep)
    holes = [b[1] - a[2] for a, b in zip(top, top[1:])]
    assert min(holes) >= 0
    assert sum(holes) < 0.1 * (top[-1][2] - top[0][1])
    assert all(e[3]["who"] == "engine" for e in engine_thread
               if e[0] == "serve.lock_wait")
    submit = [e for t in prof.threads if t is not engine_thread
              for e in t if e[0] == "serve.lock_wait"]
    assert len(submit) >= 3
    assert all(e[3]["who"] == "submit" for e in submit)
    assert not any(e[0].startswith("engine.") for t in prof.threads
                   if t is not engine_thread for e in t)


def test_queue_wait_stat_equals_the_request_span(tiny, tmp_path):
    from ray_tpu.llm import LLMEngine

    cfg, params = tiny
    eng = LLMEngine(cfg, params, batch_slots=1, max_len=64, decode_window=4)
    sp = SamplingParams(temperature=0.0, max_tokens=6)
    eng.generate([[3, 4, 5]], sp)  # compile first
    tracing.clear_local()
    with _Profile(tmp_path) as prof:
        with tracing.trace("a-request") as root:
            rid = eng.submit([6, 4, 5], sp)
        other = eng.submit([7, 4, 5], sp)  # outside any trace; waits
        time.sleep(0.02)
        while eng.has_unfinished():
            eng.step()
    spans = [s for s in tracing.local_spans(include_open=False)
             if s["name"].startswith("engine.")]
    mine = {s["name"]: s for s in spans
            if s["attrs"]["request_id"] == rid}
    assert set(mine) == {"engine.queue_wait", "engine.prefill",
                         "engine.decode"}
    assert all(s["trace_id"] == root.trace_id
               and s["parent_span_id"] == root.span_id
               for s in mine.values())
    assert mine["engine.prefill"]["attrs"]["prompt_tokens"] == 3
    assert mine["engine.decode"]["attrs"]["tokens"] == 6
    assert (mine["engine.queue_wait"]["end"]
            == mine["engine.prefill"]["start"])
    assert (mine["engine.prefill"]["end"]
            == mine["engine.decode"]["start"])
    theirs = [s for s in spans if s["attrs"]["request_id"] == other]
    assert len(theirs) == 3
    assert all(s["trace_id"] != root.trace_id for s in theirs)
    by_rid = {e[3]["rid"]: e[3] for e in prof.named("engine.admit")
              if e[3]["kind"] == "full"}
    for r in (rid, other):
        wait = next(s for s in spans if s["name"] == "engine.queue_wait"
                    and s["attrs"]["request_id"] == r)
        assert by_rid[r]["queue_wait_ms"] == pytest.approx(
            (wait["end"] - wait["start"]) * 1e3, abs=1.0)
    # the one slot was taken: the second request waited a whole request
    assert by_rid[other]["queue_wait_ms"] > by_rid[rid]["queue_wait_ms"]
    assert by_rid[rid]["queue_wait_ms"] >= 20.0


_NO_JAX = """
import sys
from ray_tpu._private import tracing
from ray_tpu.train.session import StepLedger
with tracing.span("probe.span", attrs={"k": 1}):
    with tracing.annotate("probe.note", k=2) as ann:
        ann.set_metadata(more=3)
ledger = StepLedger(group_name="probe", publish=False)
with ledger.step():
    with ledger.bucket("compute"):
        pass
names = [s["name"] for s in tracing.local_spans(include_open=False)]
assert names == ["probe.span", "train.step"], names
assert not [m for m in sys.modules if m == "jax" or m.startswith("jax.")]
print("ok")
"""


def test_without_jax_a_span_is_a_host_span_and_nothing_is_imported():
    env = dict(os.environ, PYTHONPATH=ROOT)
    proc = subprocess.run([sys.executable, "-c", _NO_JAX], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"


def test_tracing_off_writes_neither_sink(tmp_path, monkeypatch):
    from ray_tpu.train.session import StepLedger

    tracing.clear_local()
    ledger = StepLedger(group_name="probe", publish=False)

    def work():
        with tracing.span("probe.span", attrs={"k": 1}):
            with tracing.annotate("probe.note", k=2):
                pass
        with ledger.step():
            with ledger.bucket("compute"):
                pass

    monkeypatch.setenv(tracing.ENV_ENABLED, "0")
    with _Profile(tmp_path / "off") as off:
        work()
    assert off.threads == []
    assert tracing.local_spans(include_open=False) == []
    assert isinstance(tracing.annotate("probe.note"),
                      tracing._NoAnnotation)
    monkeypatch.delenv(tracing.ENV_ENABLED)
    with _Profile(tmp_path / "on") as on:
        work()
    (thread,) = on.threads
    assert [(e[0], e[3]) for e in thread] == [
        ("probe.span", {"k": 1}), ("probe.note", {"k": 2})]
    assert [s["name"] for s in tracing.local_spans(include_open=False)
            ] == ["probe.span", "train.step"]


def test_step_ledger_is_in_the_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    from ray_tpu.train.session import StepLedger

    ledger = StepLedger(group_name="probe", publish=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            with ledger.step():
                with ledger.bucket("h2d"):
                    pass
                with ledger.bucket("compute"):
                    jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    events = sorted(
        (int(e.start_ns), e.name, dict(e.stats))
        for p in ProfileData.from_file(path).planes
        for line in p.lines for e in line.events
        if e.name.startswith("train."))
    assert [(n, s) for _, n, s in events] == [
        ("train.step", {"step": 1}), ("train.h2d", {}),
        ("train.compute", {}),
        ("train.step", {"step": 2}), ("train.h2d", {}),
        ("train.compute", {})]


def test_start_and_stop_profile_write_a_trace(server, tmp_path):
    assert server.start_profile(str(tmp_path)) is True
    try:
        out = server({"prompt": "profile me", "max_tokens": 5,
                      "temperature": 0.0})
    finally:
        assert server.stop_profile() is True
    assert out["num_generated_tokens"] == 5
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    names = {e.name for p in ProfileData.from_file(path).planes
             for line in p.lines for e in line.events}
    assert {"engine.step", "engine.dispatch_window", "serve.lock_wait",
            "serve.deliver"} <= names
