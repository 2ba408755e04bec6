"""The readers of the device programs' name scopes (``cells/parts.py`` and
the eleven readers that use it) on traces built by hand and on miniatures
cut from real v5e traces of ``serve-smallthinker-long-context`` and
``train-1chip-s4096`` (PR 37; ``parts.save_mini`` keeps each device event's
``op_name``).  CPU only:

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

from cells import parts, spans, trace  # noqa: E402
from cells.run import reader  # noqa: E402

US = 1_000  # the hand-built traces are written in microseconds, kept in ns
DECODE = "jit__unknown(11)"
PREFILL = {128: "jit__unknown(22)", 512: "jit__unknown(33)"}
SMALL = "jit__lambda_(44)"
SERVE_METRICS = (
    "prefill_device_pct", "prefill_us_per_token", "prefill_padding_pct",
    "decode_attention_ms", "decode_ffn_ms", "decode_experts_ms",
    "decode_head_ms", "decode_unscoped_pct")
TRAIN_METRICS = ("step_replay_ms", "step_head_loss_ms", "step_optimizer_ms")


def read(name, ctx):
    return reader("layer_metrics", name)(ctx)


def op(name, start, end, scope=None):
    op_name = None if scope is None else f"jit(<unknown>)/{scope}/mul"
    return (f"%{name} = f32[2] fusion()", start * US, (end - start) * US,
            op_name)


def ev(name, start, end, **stats):
    return (name, start * US, (end - start) * US, stats)


def ctx_of(loaded, threads):
    tr = {"device": {0: {trace.OPS_LINE: [e[:3] for e in loaded["ops"]],
                         trace.MODULES_LINE: loaded["modules"]}},
          "host": {}}
    first, last = trace.span(tr)
    return {"trace": tr, "spans": threads, "parts": loaded,
            "trace_window_s": (last - first) / 1e9}


def decode_window(t, steps=2):
    """``steps`` executions of the decode program from ``t`` us on, 100 us
    each: attention 40 (a ``while`` of 30 that holds 20 of a kernel), a
    dense MLP 25, experts 10 + 5 of combine, head 12, sample 3, a copy of
    XLA's own 5."""
    ops, modules = [], []
    for i in range(steps):
        s = t + 100 * i
        modules.append((DECODE, s * US, 100 * US))
        ops += [
            op("fusion.1", s, s + 10, "engine.decode/attn.proj"),
            op("while.2", s + 10, s + 40, "engine.decode/attn.core"),
            op("custom-call.3", s + 15, s + 35, "engine.decode/attn.core"),
            op("fusion.4", s + 40, s + 65, "engine.decode/ffn"),
            op("fusion.5", s + 65, s + 75, "engine.decode/experts"),
            op("fusion.6", s + 75, s + 80,
               "engine.decode/experts/experts.combine"),
            op("fusion.7", s + 80, s + 92, "engine.decode/head"),
            op("fusion.8", s + 92, s + 95, "engine.decode/sample"),
            op("copy.9", s + 95, s + 100)]
    return ops, modules


@pytest.fixture
def by_hand():
    """Two decode windows (100-300 us, 1300-1500 us) with one step's two
    prefills between them: buckets 128 (300 us of device time for 90
    tokens) and 512 (600 us for 300), a small program after them, and 90
    us of idle chip.  Window: 100-1500 us."""
    w1, m1 = decode_window(100)
    w2, m2 = decode_window(1300)
    prefills = [
        op("fusion.20", 300, 550, "engine.prefill/attn.core"),
        op("fusion.21", 550, 600, "engine.prefill/head"),
        op("fusion.30", 600, 1100, "engine.prefill/ffn"),
        op("copy.31", 1100, 1200),  # XLA's own, inside the second prefill
        op("fusion.40", 1200, 1210)]  # the sampler: no scope anywhere
    modules = m1 + [(PREFILL[128], 300 * US, 300 * US),
                    (PREFILL[512], 600 * US, 600 * US),
                    (SMALL, 1200 * US, 10 * US)] + m2
    engine = [
        ev("engine.step", 150, 1400, queued=2, slots_used=3),
        ev("engine.admit", 160, 180, kind="full", rid=1, prompt_tokens=90,
           prefilled_tokens=90, bucket=128, cached_tokens=0),
        ev("engine.admit", 180, 200, kind="full", rid=2, prompt_tokens=812,
           prefilled_tokens=300, bucket=512, cached_tokens=512),
        ev("engine.admit", 200, 201, kind="none"),
        ev("engine.first_tokens", 201, 1220, n=2),
        ev("engine.dispatch_window", 1220, 1300, k=2, active=3)]
    return {"ops": w1 + prefills + w2, "modules": modules}, \
        {"engine#1": engine}


def test_an_op_names_scopes():
    assert parts.scopes_of(
        "jit(<unknown>)/engine.decode/attn.proj/dot_general") == (
            "engine.decode", "attn.proj")
    assert parts.scopes_of(
        "jit(_train_step)/train.step/transpose(jvp(head))/bsh,hv->bsv/"
        "dot_general") == ("train.step", "head")
    assert parts.scopes_of(
        "jit(<unknown>)/engine.prefill/experts/experts.combine/"
        "scatter-add") == ("engine.prefill", "experts.combine")
    assert parts.scopes_of("jit(<unknown>)/engine.decode/add") == (
        "engine.decode", None)
    assert parts.scopes_of(None) == (None, None)
    replayed = ("jit(_train_step)/train.step/transpose(jvp())/while/body/"
                "closed_call/checkpoint/rematted_computation/ffn/mul")
    assert parts.scopes_of(replayed) == ("train.step", "ffn")
    assert parts.is_replay("%fusion.1 = f32[2] fusion()", replayed)
    assert parts.is_replay("%fusion.284.remat = f32[2] fusion()", None)
    assert not parts.is_replay("%fusion.284 = f32[2] fusion()",
                               "jit(f)/train.step/jvp(head)/dot_general")


def test_self_time_by_program_and_part(by_hand):
    ctx = ctx_of(*by_hand)
    table = parts.by_program_and_part(ctx)
    us = {k: round(v * 1e6, 6) for k, v in table.items()}
    assert us[("engine.decode", "attn.proj")] == 40      # 4 steps x 10
    assert us[("engine.decode", "attn.core")] == 120     # the while's 10
    # of its own and the kernel's 20 inside it: 30 a step, never 50
    assert us[("engine.decode", "experts.combine")] == 20
    assert us[("engine.decode", parts.UNSCOPED)] == 20   # XLA's copy
    assert us[("engine.prefill", "attn.core")] == 250
    assert us[("engine.prefill", parts.UNSCOPED)] == 100  # the copy takes
    # the program of the execution around it
    assert us[(None, parts.UNSCOPED)] == 10              # the small program
    assert len(parts.executions(ctx, "engine.decode")) == 4
    assert parts.executions(ctx, "engine.prefill") == [
        (300 * US, 300 * US), (600 * US, 600 * US)]
    # everything the chip ran is under some key
    assert sum(table.values()) == pytest.approx(
        trace.busy_ns(ctx["trace"], 0) / 1e9)


def test_the_eight_serve_metrics(by_hand):
    ctx = ctx_of(*by_hand)
    got = {m: read(m + ".steady", ctx) for m in SERVE_METRICS}
    assert got["prefill_device_pct"] == pytest.approx(100 * 900 / 1400)
    assert got["prefill_us_per_token"] == pytest.approx(900 / 390)
    assert got["prefill_padding_pct"] == pytest.approx(
        100 * (1 - 390 / 640))
    assert got["decode_attention_ms"] == pytest.approx(0.040)
    assert got["decode_ffn_ms"] == pytest.approx(0.025)
    assert got["decode_experts_ms"] == pytest.approx(0.015)
    assert got["decode_head_ms"] == pytest.approx(0.015)
    assert got["decode_unscoped_pct"] == pytest.approx(5.0)
    # with the small program and the idle chip they tile the window
    idle = trace.idle_pct(ctx["trace"], ctx["trace_window_s"])
    assert (got["prefill_device_pct"]
            + parts.program_pct(ctx, "engine.decode")
            + parts.program_pct(ctx, None) + idle) == pytest.approx(100)


def test_a_step_the_window_cuts_is_dropped(by_hand):
    loaded, threads = by_hand
    # a second step whose fetch ends after the last device operation, its
    # prefill's execution cut off by the end of the trace
    threads = {"engine#1": threads["engine#1"] + [
        ev("engine.step", 1400, 1600, queued=1, slots_used=3),
        ev("engine.admit", 1410, 1430, kind="full", rid=3,
           prompt_tokens=70, prefilled_tokens=70, bucket=128,
           cached_tokens=0),
        ev("engine.first_tokens", 1430, 1590, n=1)]}
    ctx = ctx_of(loaded, threads)
    assert [r[1:] for r in parts.matched_prefills(ctx)] == [
        (90, 128), (300, 512)]
    # ... and one whose admission began before the first device operation
    early = {"engine#1": [
        ev("engine.step", 40, 1400),
        *(e for e in by_hand[1]["engine#1"] if e[0] != "engine.step")]}
    early["engine#1"][1] = ev(
        "engine.admit", 50, 180, kind="full", rid=1, prompt_tokens=90,
        prefilled_tokens=90, bucket=128, cached_tokens=0)
    assert parts.matched_prefills(ctx_of(loaded, early)) == []
    assert read("prefill_us_per_token.steady", ctx_of(loaded, early)) is None


def test_a_count_that_disagrees_fails_the_reader(by_hand):
    loaded, threads = by_hand
    one_less = {"engine#1": [e for e in threads["engine#1"]
                             if e[3].get("rid") != 2]}
    ctx = ctx_of(loaded, one_less)
    assert parts.matched_prefills(ctx) is None
    assert read("prefill_us_per_token.steady", ctx) is None
    assert read("prefill_padding_pct.steady", ctx) is None
    # the share of the chip needs no admission and still reads
    assert read("prefill_device_pct.steady", ctx) == pytest.approx(
        100 * 900 / 1400)


def test_parts_that_do_not_sum_fail_the_reader(by_hand):
    loaded, threads = by_hand
    # a decode execution whose instructions cover 90 of its 100 us
    holed = dict(loaded, ops=[e for e in loaded["ops"]
                              if "fusion.1 " not in e[0]])
    ctx = ctx_of(holed, threads)
    assert parts.checked(ctx, "engine.decode") is None
    assert read("decode_attention_ms.steady", ctx) is None
    assert read("decode_unscoped_pct.steady", ctx) is None
    assert read("prefill_device_pct.steady", ctx) is not None


def test_a_program_without_scopes_reads_nothing(by_hand):
    """The parent commit, whose programs carry no scope and whose
    admissions no ``prefilled_tokens``: every reader returns ``None``."""
    loaded, threads = by_hand
    bare = {"ops": [(*e[:3], None) for e in loaded["ops"]],
            "modules": loaded["modules"]}
    old = {k: [(n, s, d, {x: y for x, y in st.items()
                          if x != "prefilled_tokens"})
               for n, s, d, st in v] for k, v in threads.items()}
    ctx = ctx_of(bare, old)
    del ctx["parts"]  # of_run is asked: no file either
    ctx["parts"] = None
    for m in SERVE_METRICS:
        assert read(m + ".steady", ctx) is None, m
    for m in TRAIN_METRICS:
        assert read(m + ".train", ctx) is None, m
    # scopes but an engine before the stat: the per-token readers alone
    ctx = ctx_of(loaded, old)
    assert read("prefill_us_per_token.steady", ctx) is None
    assert read("prefill_device_pct.steady", ctx) is not None
    # no trace at all (an untraced run, a rehearsal)
    assert read("decode_head_ms.steady", {"trace": None}) is None


def test_the_three_train_metrics():
    """Two steps of 1000 us: the head forward 100, its ``.remat`` twin 60,
    its backward 140, the loss 50; a replayed product 200; the optimizer
    150; the rest forward and backward under ``ffn``."""
    ops, modules = [], []
    step = "jit(_train_step)/train.step"
    for s in (0, 1000):
        modules.append(("jit__train_step(7)", s * US, 1000 * US))
        rows = [
            ("fusion.1", 0, 100, "jvp(head)/dot_general"),
            ("fusion.2", 100, 150, "jvp(loss)/reduce_sum"),
            ("fusion.1.remat", 150, 210, None),
            ("fusion.3", 210, 350, "transpose(jvp(head))/dot_general"),
            ("fusion.4", 350, 550, "transpose(jvp())/while/body/closed_call"
             "/checkpoint/rematted_computation/ffn/dot_general"),
            ("fusion.5", 550, 850, "transpose(jvp())/while/body/closed_call"
             "/checkpoint/ffn/dot_general"),
            ("fusion.6", 850, 1000, "optimizer/mul")]
        ops += [(f"%{n} = f32[2] fusion()", (s + a) * US, (b - a) * US,
                 f"{step}/{tail}" if tail else None)
                for n, a, b, tail in rows]
    ctx = ctx_of({"ops": ops, "modules": modules}, {})
    assert read("step_replay_ms.train", ctx) == pytest.approx(0.260)
    assert read("step_head_loss_ms.train", ctx) == pytest.approx(0.290)
    assert read("step_optimizer_ms.train", ctx) == pytest.approx(0.150)
    table = parts.by_program_and_part(ctx)
    assert table[("train.step", parts.UNSCOPED)] == pytest.approx(120e-6)


def recorded(name):
    path = os.path.join(HERE, name)
    return ctx_of(parts.load(path), spans.load(path))


def test_the_recorded_serve_miniature():
    """0.5 s of ``serve-smallthinker-long-context`` on the v5e (PR 37's
    first traced run, seed 3737000103, 2.25 s into its 4 s trace; cut by
    ``cells/tools/dump_parts.py``): 102 decode executions and two steps
    with one admission each, 2601 tokens in the 4096 bucket and 1224 in
    the 2048 one."""
    ctx = recorded("mini_serve_parts.json.gz")
    runs = parts.executions(ctx, "engine.decode")
    assert len(runs) == 102 == len(trace.decode_program_s(ctx["trace"]))
    # the reader's check on itself: the parts sum to the device time
    got = parts.checked(ctx, "engine.decode")
    device = sum(d for _, d in runs) / 1e9
    assert sum(got.values()) == pytest.approx(device, rel=0.02)
    assert {"attn.proj", "attn.cache", "attn.core", "attn.out", "router",
            "experts", "head", "sample", "embed", parts.UNSCOPED} == set(got)
    assert [r[1:] for r in parts.matched_prefills(ctx)] == [
        (2601, 4096), (1224, 2048)]
    m = {name: read(name + ".steady", ctx) for name in SERVE_METRICS}
    assert m["prefill_us_per_token"] == pytest.approx(25.005, abs=0.01)
    assert m["prefill_padding_pct"] == pytest.approx(
        100 * (1 - 3825 / 6144))
    assert m["prefill_device_pct"] == pytest.approx(19.13, abs=0.01)
    assert m["decode_experts_ms"] == pytest.approx(1.849, abs=0.001)
    assert m["decode_head_ms"] == pytest.approx(1.172, abs=0.001)
    assert m["decode_attention_ms"] == pytest.approx(0.559, abs=0.001)
    assert m["decode_unscoped_pct"] == pytest.approx(2.58, abs=0.01)
    assert m["decode_ffn_ms"] is None  # the model has no dense MLP
    # the four parts and the rest are the step the accepted reader times
    assert sum(got.values()) / len(runs) * 1e3 == pytest.approx(
        read("decode_step_ms.steady", ctx), rel=0.02)
    # programs and the idle chip tile the window
    assert (m["prefill_device_pct"] + parts.program_pct(ctx, "engine.decode")
            + parts.program_pct(ctx, None)
            + trace.idle_pct(ctx["trace"], ctx["trace_window_s"])
            ) == pytest.approx(100, abs=0.01)
    # the scatter-add of the grouped path is the largest part of a prefill
    # (47% of these two, 67% of the whole trace's seven: ROADMAP A3a)
    prefill = {part: v for (p, part), v in
               parts.by_program_and_part(ctx).items()
               if p == "engine.prefill"}
    assert max(prefill, key=prefill.get) == "experts.combine"
    assert prefill["experts.combine"] == pytest.approx(
        0.47 * sum(prefill.values()), rel=0.02)


def test_the_recorded_train_miniature():
    """Two steps of ``train-1chip-s4096`` on the v5e (PR 37's first traced
    run, seed 3737000102)."""
    ctx = recorded("mini_train_parts.json.gz")
    runs = parts.executions(ctx, "train.step")
    assert len(runs) == 2
    got = parts.checked(ctx, "train.step")
    assert sum(got.values()) == pytest.approx(
        sum(d for _, d in runs) / 1e9, rel=0.02)
    assert read("step_replay_ms.train", ctx) == pytest.approx(98.69, abs=0.01)
    assert read("step_head_loss_ms.train", ctx) == pytest.approx(128.71,
                                                                 abs=0.01)
    assert read("step_optimizer_ms.train", ctx) == pytest.approx(31.44,
                                                                 abs=0.01)
    # the replay is JAX's checkpoint and XLA's twin of the head's product
    rows, _ = parts.attributed(ctx["parts"])
    twins = [r for r in rows if r[2] and parts.REPLAY not in (r[5] or "")]
    assert twins and all(".remat" in trace.short(r[4]) for r in twins)
    assert sum(r[3] for r in twins) / 2e6 == pytest.approx(27.0, abs=0.5)
    for m in SERVE_METRICS:  # nothing of the serve readers is there
        assert read(m + ".steady", ctx) is None


def test_every_new_metric_is_an_entry_with_a_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    serve = [w["name"] for w in bench["workloads"]
             if w["name"].startswith("serve-")]
    for name in (*(m + ".steady" for m in SERVE_METRICS),
                 *(m + ".train" for m in TRAIN_METRICS)):
        m = entries[name]
        assert (m["source"], m["better"]) == ("program_span", "lower")
        assert m["moves"] == ("tpot_ms_p50" if name.endswith(".steady")
                              else "train_tokens_per_s")
        assert set(m["workloads"]) <= set(
            serve if name.endswith(".steady") else ["train-1chip-s4096"])
        assert os.path.exists(
            os.path.join(CELLS, "layer_metrics", name + ".py"))
    assert entries["decode_ffn_ms.steady"]["workloads"] == [
        "serve-chat-steady", "serve-longcat-long-answers"]
    assert entries["decode_experts_ms.steady"]["workloads"] == [
        "serve-longcat-long-answers", "serve-smallthinker-long-context"]
