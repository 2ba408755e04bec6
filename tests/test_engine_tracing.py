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
from ray_tpu.llm import SamplingParams
from ray_tpu.models.llama import LlamaConfig, llama_init

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ("engine.admit", "engine.first_tokens", "engine.prepare_window",
          "engine.dispatch_window", "engine.fetch_window", "engine.emit",
          "engine.retire")
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
    ("chunked", {"prefill_chunk": 16}),
    ("uncarried", {}),
])
def test_engine_step_is_tiled_by_its_phases(tiny, tmp_path, case, kwargs):
    from ray_tpu.llm import LLMEngine

    cfg, params = tiny
    eng = LLMEngine(cfg, params, batch_slots=2, max_len=128,
                    decode_window=4, **kwargs)
    if case == "uncarried":
        eng._carries = lambda: False  # every launch left to the next step
    sp = SamplingParams(temperature=0.0, max_tokens=22)
    # a 41-token one so that the chunked case prefills in chunks
    def prompts(base):
        return [[base, 4, 5, 4, 5, 4, 5, 4], [base + 1] + [7, 8] * 20,
                [base + 2, 9, 9, 9, 9]]
    # compile every program first; the profiled pass sends other prompts
    # of the same lengths (these would hit the prefix cache)
    eng.generate(prompts(10), sp)
    # what each window's slots hold as it is dispatched, from the host
    # mirror: _window_arity is the last thing prepare_window asks
    held = []
    arity = eng._window_arity

    def recording_arity(active):
        held.append(int(sum(eng._cur_len[i] for i in active)))
        return arity(active)
    eng._window_arity = recording_arity
    before = dict(eng.stats()["counters"])
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
    assert seen == set(PHASES), seen
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
    # the positions its slots hold as each window is dispatched, carried
    # or not: the commit has brought the host mirror up to date
    assert [e[3]["live_tokens"] for e in windows] == held
    emits = prof.named("engine.emit")
    commits = [e for e in emits if e[3]["part"] == "commit"]
    notifies = [e for e in emits if e[3]["part"] == "notify"]
    assert len(commits) == len(notifies) == len(emits) // 2
    emitted = sum(e[3]["tokens"] for e in commits)
    assert sum(e[3]["n"] for e in prof.named("engine.first_tokens")) == 3
    assert emitted == 3 * 22 - 3  # all but the three first tokens
    assert sum(e[3]["n"] for e in prof.named("engine.retire")) == 3
    # every window is fetched and committed before it is emitted, in the
    # step that hands its tokens out; a carried window (n+1) is dispatched
    # between the commit and the notify of the window before it (n)
    carried = [e for e in windows if e[3]["carried"]]
    counters = eng.stats()["counters"]
    assert counters["decode_windows"] - before["decode_windows"] \
        == len(windows)
    assert counters["windows_carried"] - before["windows_carried"] \
        == len(carried)
    if case == "uncarried":
        assert not carried and counters["windows_carried"] == 0
    else:
        assert len(carried) >= 0.6 * len(windows) >= 6
    for e in carried:
        step = next(s for s in steps if s[1] <= e[1] and e[2] <= s[2])
        (commit,) = [c for c in commits
                     if step[1] <= c[1] and c[2] <= step[2]]
        (notify,) = [n for n in notifies
                     if step[1] <= n[1] and n[2] <= step[2]]
        assert commit[2] <= e[1] and e[2] <= notify[1]
    for step in steps:
        names = [e[0] + (f"[{e[3]['part']}]" if e[0] == "engine.emit"
                         else f"[{e[3]['carried']}]"
                         if e[0] == "engine.dispatch_window" else "")
                 for e in thread if e[0] != "engine.step"
                 and step[1] <= e[1] and e[2] <= step[2]
                 and e[0] not in ("engine.admit", "engine.first_tokens")]
        assert names in (
            # nothing in flight, none launched (a drain)
            ["engine.retire"],
            # nothing in flight: launch, fetch, commit, [launch], notify
            ["engine.prepare_window", "engine.dispatch_window[0]",
             "engine.fetch_window", "engine.emit[commit]",
             "engine.emit[notify]", "engine.retire"],
            ["engine.prepare_window", "engine.dispatch_window[0]",
             "engine.fetch_window", "engine.emit[commit]",
             "engine.prepare_window", "engine.dispatch_window[1]",
             "engine.emit[notify]", "engine.retire"],
            # one in flight: fetch, commit, [launch], notify
            ["engine.fetch_window", "engine.emit[commit]",
             "engine.prepare_window", "engine.dispatch_window[1]",
             "engine.emit[notify]", "engine.retire"],
            ["engine.fetch_window", "engine.emit[commit]",
             "engine.emit[notify]", "engine.retire"]), names


# ------------------------------------------------- the carried window

def _engine_pair(model, **kwargs):
    """Two engines on the same weights: one as it is (a window is launched
    ahead of the emit of the one before), one whose every launch is left to
    the next step, which is the order the engine had before windows were
    carried."""
    from ray_tpu.llm import LLMEngine

    cfg, params, extra = model
    kw = dict(batch_slots=2, max_len=96, block_size=4, decode_window=4,
              **extra)
    carried = LLMEngine(cfg, params, **{**kw, **kwargs})
    plain = LLMEngine(cfg, params, **{**kw, **kwargs})
    plain._carries = lambda: False
    return carried, plain


def _run_schedule(eng, arrivals, aborts=None):
    """Drive ``eng`` a step at a time: ``arrivals[step]`` are submitted
    and ``aborts[step]`` (indices into the submitted) aborted before that
    step.  The block accounting is audited after every step.  Returns the
    requests' tokens, in the order submitted."""
    ids, outs, step = [], {}, 0
    while step <= max(arrivals) or eng.has_unfinished():
        for prompt, sp in arrivals.get(step, ()):
            ids.append(eng.submit(prompt, sp))
        for j in (aborts or {}).get(step, ()):
            assert eng.abort(ids[j])
        for out in eng.step():
            assert out.error is None
            outs[out.request_id] = out.token_ids
        for pool in eng._pools:
            pool.blocks.assert_integrity()
        assert (eng._inflight is not None) <= eng.has_unfinished()
        step += 1
        assert step < 200
    assert eng._inflight is None
    return [outs[i] for i in ids]


def _greedy(max_tokens, stop=None):
    return SamplingParams(temperature=0.0, max_tokens=max_tokens,
                          stop_token_id=stop)


P = [[3, 4, 5, 6, 7], [9, 8, 7, 6], [11, 12, 13, 14, 15, 16], [20, 21, 22]]
LONG = [30] + [7, 8] * 20  # 41 tokens: three chunks of 16


def _schedules():
    """name -> (engine kwargs, arrivals, aborts, what the engine's
    counters must show)."""
    return {
        # requests join and leave while others decode; the fourth waits
        # for a slot
        "staggered": ({}, {0: [(P[0], _greedy(14))], 2: [(P[1], _greedy(9))],
                           3: [(P[2], _greedy(11)), (P[3], _greedy(6))]},
                      None, {}),
        # 1 first token + 4 + 2: the budget ends inside the second window
        # while the other slot goes on
        "max_tokens_mid_window": ({}, {0: [(P[0], _greedy(7)),
                                           (P[1], _greedy(18))]}, None, {}),
        # the pool holds 6 blocks of 4: the younger request is preempted
        # and recomputed
        "preemption": ({"num_blocks": 7},
                       {0: [(P[0][:4], _greedy(10)), (P[1], _greedy(10))]},
                       None, {"preemptions": 1}),
        # the first request is aborted while its third window is in
        # flight; a queued one takes its slot
        "abort_in_flight": ({}, {0: [(P[0], _greedy(30)),
                                     (P[1], _greedy(13))],
                                 1: [(P[2], _greedy(9))]}, {3: [0]}, {}),
        # a 41-token prompt prefilled 16 tokens a step behind the windows
        # of a request that is decoding
        "chunked_prefill": ({"prefill_chunk": 16},
                            {0: [(P[0], _greedy(21))],
                             1: [(LONG, _greedy(10))]}, None, {"chunks": 2}),
    }


class _Ids:
    """Token ids in, token ids out: the tokenizer the typed-pool models'
    own test files hand the engine."""
    eos_id = None
    vocab_size = 256

    def encode(self, text):
        return [1]

    def decode(self, ids):
        return ""


@pytest.fixture(scope="module")
def models(tiny):
    from ray_tpu.models.longcat import LongcatConfig
    from ray_tpu.models.served import preset

    # the typed-pool models' tiny presets: a window of 8 = two blocks of 4,
    # so a 14-token answer crosses it and ``_release_behind_window`` gives
    # blocks back at a commit with a window in flight; phi4flash's state
    # records beside them
    typed = {"seed": 5, "tokenizer": _Ids()}
    return {"llama": (*tiny, {}),
            "longcat": (LongcatConfig.tiny(), None, {"seed": 5}),
            "smallthinker": (preset("smallthinker_tiny"), None, typed),
            "phi4flash": (preset("phi4flash_tiny"), None, typed)}


@pytest.mark.parametrize("model,case", [
    *[("llama", c) for c in _schedules()], ("longcat", "staggered"),
    ("llama", "stop_mid_window"),
    ("smallthinker", "staggered"), ("smallthinker", "abort_in_flight"),
    ("phi4flash", "staggered"), ("phi4flash", "max_tokens_mid_window")])
def test_carried_windows_give_the_uncarried_orders_tokens(models, model,
                                                          case):
    """Greedy outputs are token for token those of the engine that
    launches every window only after it has emitted the one before, over
    one pool, a latent pool, typed pools and state records alike."""
    if case == "stop_mid_window":
        # the token a request produces sixth (second of its second
        # window) becomes its stop token, if it is the first of its kind
        probe, _ = _engine_pair(models[model])
        (free,) = _run_schedule(probe, {0: [(P[0], _greedy(14))]})
        at = next(i for i in (5, 6, 9, 10) if free[i] not in free[:i])
        kwargs, aborts, want = {}, None, {"tokens": [free[:at], 18]}
        arrivals = {0: [(P[0], _greedy(14, stop=free[at])),
                        (P[1], _greedy(18))]}
    else:
        kwargs, arrivals, aborts, want = _schedules()[case]
    carried, plain = _engine_pair(models[model], **kwargs)
    got = _run_schedule(carried, arrivals, aborts)
    ref = _run_schedule(plain, arrivals, aborts)
    assert got == ref
    asked = [sp.max_tokens for step in sorted(arrivals)
             for _, sp in arrivals[step]]
    for j, (toks, n) in enumerate(zip(got, want.get("tokens", asked))):
        if aborts and any(j in js for js in aborts.values()):
            assert 0 < len(toks) < n  # what it had when it was aborted
        else:
            assert toks == n if isinstance(n, list) else len(toks) == n
    c, p = carried.stats()["counters"], plain.stats()["counters"]
    assert p["windows_carried"] == 0 and p["decode_windows"] > 0
    assert 0 < c["windows_carried"] <= c["decode_windows"]
    # only the first window after a drain is not carried
    assert c["windows_carried"] >= c["decode_windows"] - 3
    if "window_blocks_released" in c:
        # a window type: the answers crossed the window in both orders
        assert c["window_blocks_released"] > 0
        assert p["window_blocks_released"] > 0
    for eng in (carried, plain):
        assert eng.blocks.stats["preemptions"] >= want.get("preemptions", 0)
        assert eng.prefill_stats["chunks"] >= want.get("chunks", 0)
        for pool in eng._pools:
            assert pool.blocks.available() == pool.blocks.num_blocks - 1


@pytest.mark.parametrize("order", ["carried", "uncarried"])
def test_first_token_is_handed_out_with_its_first_window(tiny, order):
    """``on_token`` gets a request's first token together with the tokens
    of its first window, in either order of launch and emit: one step
    after the admission when windows are carried, in the admission's step
    when they are not.  A request that ends on its first token is told at
    once."""
    # a third slot, for a request that is already decoding
    pair = _engine_pair((*tiny, {}), batch_slots=3)
    eng = pair[order == "uncarried"]
    told = {}
    eng.on_token = lambda rid, tok: told.setdefault(rid, []).append(tok)
    eng.submit(P[2], _greedy(30))
    eng.step()  # its second window is in flight when the others come
    assert (eng._inflight is not None) == (order == "carried")
    long_, short = eng.submit(P[0], _greedy(10)), eng.submit(P[1], _greedy(1))
    per_step, outs = [], {}
    while long_ not in outs:
        seen = len(told.get(long_, []))
        for out in eng.step():
            outs[out.request_id] = out.token_ids
        per_step.append(len(told.get(long_, [])) - seen)
        if len(per_step) == 1:
            assert told.get(short) == outs[short] and len(outs[short]) == 1
    # 10 tokens = the first + windows of 4, 4 and 1
    assert per_step == ([0, 5, 4, 1] if order == "carried" else [5, 4, 1])
    assert told[long_] == outs[long_] and len(outs[long_]) == 10
    assert not any(r.held for r in eng._slots if r is not None)


def test_a_carried_window_writes_only_its_own_slots_blocks(tiny):
    """A window launched before ``retire`` writes into the blocks of the
    slots it decodes for and into the scratch block, nowhere else: not
    into the blocks of a request that is done (released right after the
    launch), not into a registered prefix block the cache holds."""
    import numpy as np

    eng, _ = _engine_pair((*tiny, {}))
    launch, checked = eng._launch_window, []

    def watched(carried):
        before = ({k: np.asarray(v) for k, v in eng.pool.items()}
                  if carried else None)
        done = [i for i, r in enumerate(eng._slots)
                if r is not None and r.done]
        w = launch(carried)
        if carried and w is not None:
            own = {0} | {b for i in w.active
                         for b in eng._slots[i].blocks}
            rest = [b for b in range(eng.num_blocks) if b not in own]
            for k, v in eng.pool.items():  # waits for the window
                assert np.array_equal(before[k][:, rest],
                                      np.asarray(v)[:, rest])
            checked.append((len(done), len(eng.blocks.lru)))
        return w
    eng._launch_window = watched
    shared = list(range(40, 48))  # two full blocks: registered
    _run_schedule(eng, {0: [(shared + [1], _greedy(7)),
                            (P[1], _greedy(20))],
                        4: [(shared + [2], _greedy(6))]})
    assert eng.blocks.stats["prefix_blocks_reused"] >= 2
    # some carried window ran beside a slot that was done and not yet
    # retired, and some while the cache held registered blocks
    assert any(done for done, _ in checked)
    assert any(cached for _, cached in checked)


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


# ---------------------------------------------------------------------------
# set-up: the engine's parts, the programs built, and what a trace carries
# ---------------------------------------------------------------------------

STARTUP_PARTS = ("backend", "weights", "pool")


@pytest.mark.parametrize("how", ["direct", "built"])
def test_startup_parts_account_for_the_total(tiny, how):
    """``stats()["startup"]``: the three parts sum to ``total_s`` (the
    constructor's wall, whoever calls it) within 5%, and each is an
    ``engine.startup.<part>`` span; built the way a replica builds it, they
    are the children of one ``engine.startup``."""
    from ray_tpu.llm import LLMEngine
    from ray_tpu.llm.serving import _build_engine

    cfg, _ = tiny
    tracing.clear_local()
    # a pool of its own shape each: its zeros are a program never built
    kwargs = dict(batch_slots=2 if how == "direct" else 3, max_len=80,
                  decode_window=4, seed=3)
    t0 = time.time()
    eng = (LLMEngine(cfg, **kwargs) if how == "direct"
           else _build_engine(dict(kwargs, cfg=cfg), 1))
    wall = time.time() - t0
    up = eng.stats()["startup"]
    assert set(up) == {f"{p}_s" for p in STARTUP_PARTS} | {"total_s"}
    assert up["weights_s"] > 0  # llama_init, until it is dispatched
    assert sum(up[f"{p}_s"] for p in STARTUP_PARTS) == pytest.approx(
        up["total_s"], rel=0.05, abs=0.02)
    assert up["total_s"] <= wall + 0.001
    spans = tracing.local_spans(include_open=False)
    parts = [s for s in spans if s["name"].startswith("engine.startup.")]
    assert [s["name"].rsplit(".", 1)[1] for s in parts] == list(
        STARTUP_PARTS)
    for s in parts:
        assert s["end"] - s["start"] == pytest.approx(
            up[s["name"].rsplit(".", 1)[1] + "_s"], abs=0.05)
    whole = [s for s in spans if s["name"] == "engine.startup"]
    if how == "direct":
        assert whole == []
    else:
        (whole,) = whole
        assert {s["parent_span_id"] for s in parts} == {whole["span_id"]}
        # the pool's programs were built under it
        built = [s for s in spans if s["name"] == "xla.build"
                 and whole["start"] <= s["start"] <= whole["end"]]
        assert built and {s["trace_id"] for s in built} == {
            whole["trace_id"]}


def test_a_window_length_never_run_is_a_build_and_a_repeated_one_is_not(
        tiny):
    """ROADMAP A6d as a number: the engine stacks a window's tokens with a
    program of the window's length, so a length the warm-up did not meet is
    built (or loaded: the cache's directory would not show it) while
    requests wait.  ``stats()["builds"]`` rising between two calls is how
    an operator, and the benchmark, sees it."""
    from ray_tpu.llm import LLMEngine

    cfg, params = tiny
    tracing.watch_builds()
    eng = LLMEngine(cfg, params, batch_slots=2, max_len=64, decode_window=8)

    def programs():
        b = eng.stats()["builds"]
        return b["built"] + b["loaded"]

    def run(max_tokens):
        before = programs()
        eng.generate([[5, 6, 7, 8]], SamplingParams(
            temperature=0.0, max_tokens=max_tokens))
        return programs() - before

    assert run(9) >= 1  # a prefill, the step, a window of 8
    assert run(9) == 0
    assert run(12) >= 1  # 8 then 3: a length never run
    assert run(12) == 0
    assert run(9) == 0
    assert set(eng.stats()["builds"]) == {
        "built", "loaded", "build_s", "load_s", "lower_s"}


def test_publish_stats_carries_the_ledger_and_the_startup(server, tmp_path):
    want = {"built", "loaded", "build_ms", "load_ms", "lower_ms",
            "startup_backend_s", "startup_weights_s", "startup_pool_s",
            "startup_total_s"}
    with _Profile(tmp_path) as prof:
        server._last_publish = 0.0  # due at the loop's next turn
        deadline = time.time() + 10
        while server._last_publish == 0.0 and time.time() < deadline:
            time.sleep(0.01)
        time.sleep(0.05)
    stats = prof.named("serve.publish_stats")[-1][3]
    assert set(stats) == want
    assert stats["built"] + stats["loaded"] >= 1  # the fixture's warm-up
    assert stats["startup_total_s"] == server.engine.stats()[
        "startup"]["total_s"] > 0
    assert stats["build_ms"] + stats["load_ms"] + stats["lower_ms"] > 0
