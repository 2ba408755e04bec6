"""The benchmark's own tests of family ``smallthinker`` and its cell.  CPU
only:

    JAX_PLATFORMS=cpu python -m pytest cells/tests/test_smallthinker.py -q

A file of its own because the family came by files alone (``cells/README.md``,
"A model family"): ``test_cells.py`` is a file the benchmark had.  The
engine's two pools, the shares that add up and the kernels are in the repo's
``tests/test_smallthinker.py`` (the same reference file).
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.dirname(HERE)
ROOT = os.path.dirname(CELLS)
sys.path.insert(0, ROOT)

from cells import families, flops  # noqa: E402
from cells import run as cells_run  # noqa: E402

CONFIG = "smallthinker-21ba3b-L4-serve"
CELL = "serve-smallthinker-long-context"

# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# SmallThinker-21BA3B-Instruct): what the source publishes, under its keys
LAYOUT = [0, 1, 1, 1] * 13
PUBLISHED = dict(
    head_dim=128, hidden_size=2560, max_position_embeddings=16384,
    model_name="smallthinker_21b_instruct", moe_ffn_hidden_size=768,
    moe_num_active_primary_experts=6, moe_num_primary_experts=64,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    num_attention_heads=28, num_hidden_layers=52, num_key_value_heads=4,
    rms_norm_eps=1e-06, rope_layout=LAYOUT, rope_scaling=None,
    rope_theta=1500000, sliding_window_layout=LAYOUT,
    sliding_window_size=4096, tie_word_embeddings=False, vocab_size=151936)
REDUCED = {"num_hidden_layers", "max_position_embeddings"}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load(CELLS, "configs", CONFIG + ".json")


def test_every_published_value_is_held_or_listed_as_reduced(config):
    bench = _load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    fam = families.load(config["family"])
    assert entry["source"] == config["source"]
    assert entry["file"] == f"cells/configs/{CONFIG}.json"
    assert set(entry["reduced"]) == set(config["reduced"]) == REDUCED
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert config[key] < value, key
        else:
            assert config[key] == value, key
    # a cut is of a key the family maps, and never of one of its widths
    assert REDUCED <= set(fam.SOURCE_KEYS) - fam.WIDTHS
    assert fam.WIDTHS <= set(fam.SOURCE_KEYS)
    assert not any(k.endswith(("_dim", "_rank", "_size")) or "hidden" in k
                   or "head" in k or "window" in k or "expert" in k
                   for k in REDUCED - {"num_hidden_layers"})
    # the source's own keys at the top level say the same as ``model``
    for theirs, ours in fam.SOURCE_KEYS.items():
        assert config[theirs] == config["model"][ours], theirs
    m = config["model"]
    # the layouts are kept whole; the layers held are their first entries,
    # in whole periods, and ``layer_period`` says one period in words
    assert m["num_layers"] % 4 == 0 and m["num_layers"] >= 4
    held = [fam.LAYOUT[v] for v in config["sliding_window_layout"]
            [:m["num_layers"]]]
    assert held == fam.layer_kinds(m) and held.count("full") == 1
    assert config["rope_layout"] == config["sliding_window_layout"]
    # every expert held, the router at its published width; the floors
    assert m["num_experts"] == m["held_experts"] == 64
    assert m["first_expert"] == 0
    assert m["vocab_size"] == PUBLISHED["vocab_size"]  # whole
    assert "with EVERY expert of its layers" in config["stands_for"]
    assert {"router_input", "router_scores", "expert_activation",
            "rotary_pairs", "secondary_experts"} <= set(config["assumed"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, "longctx-mixed")
    assert config["engine"]["max_len"] == m["max_seq_len"]
    # the cell reports the bounded metric, the shared .steady readers, the
    # expert readers and its own three
    mine = {x["name"] for x in bench["per_layer"]
            if CELL in x.get("workloads", [])}
    both = {x["name"] for x in bench["per_layer"]
            if {"serve-chat-steady", "serve-longcat-long-answers"}
            <= set(x.get("workloads", []))}
    assert both <= mine
    assert {"decode_step_roofline.steady", "expert_hit_pct.steady",
            "expert_tokens_per_step.steady",
            "paged_attention_roofline.steady",
            "flash_prefill_roofline.steady",
            "window_cache_saved_pct.steady"} <= mine
    assert "zero_expert_pick_pct.steady" not in mine
    e2e = next(x for x in bench["end_to_end"] if x["name"] == "tpot_ms_p50")
    assert CELL in e2e["workloads"]


def test_parameters_and_bytes_by_hand(config):
    fam = families.load("smallthinker")
    m = config["model"]
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert fam.attention_params(m) == attn == 20_971_520
    assert fam.expert_params(m) == 3 * 2560 * 768 == 5_898_240
    outside = attn + 2560 * 64 + 2 * 2560
    assert fam.layer_params_outside_experts(m) == outside
    layer = outside + 64 * 5_898_240
    assert round(layer / 1e6, 1) == 398.6
    total = 2 * 151936 * 2560 + 4 * layer + 2560
    assert fam.num_params(m) == total
    assert round(total * 2 / 1e9, 3) == 4.745 == round(
        fam.weight_bytes(m) / 1e9, 3)
    # at the issue's depth, three periods: 11.12 GB
    assert round(fam.weight_bytes(dict(m, num_layers=12)) / 1e9, 2) == 11.12
    # a cached position: 2 KiB a layer, 8 KiB over the 4
    assert fam.kv_row_bytes(m) == 2048 and fam.kv_bytes_per_token(m) == 8192
    experts = 4 * 64 * 5_898_240 * 2
    embed = 151936 * 2560 * 2
    assert fam.decode_step_bytes(m, 1000, 0.5) == (
        fam.weight_bytes(m) - embed - experts + 0.5 * experts
        + 1000 * 8192)
    # the floor the predictions start from: 3.97 GB at no live cache, of
    # which the head is 0.78
    assert round(fam.decode_step_bytes(m, 0) / 1e9, 2) == 3.97
    assert round(fam.decode_step_bytes(m, 0) / 819e9 * 1e3, 2) == 4.84
    # the kernel's bytes: the full layer the whole context, 3 the window's
    assert fam.paged_attention_bytes(m, {"full": 1000, "window": 400}) == \
        (1000 + 3 * 400) * 2048
    # the pools as the engine makes them, and what a slot can hold
    e = config["engine"]
    nb = e["num_blocks"]
    assert round(((nb["full"] - 1) + (nb["window"] - 1) * 3) * 16 * 2048
                 / 1e9, 2) == 1.34
    assert nb["window"] <= e["batch_slots"] * 258 + 4


def test_the_flash_prefill_counts_only_the_pairs_a_prompt_asks_for():
    fam = families.load("smallthinker")
    m = _load(CELLS, "configs", CONFIG + ".json")["model"]
    per_pair = 4 * 128 * 28  # QK^T and PV, all query heads
    # one by one: query q sees keys max(0, q - window + 1) .. q
    for n in (1, 100, 4096, 4097, 8193):
        full = sum(q + 1 for q in range(n))
        window = sum(min(q + 1, 4096) for q in range(n))
        assert fam._pairs_admitted(n, None) == full
        assert fam._pairs_admitted(n, 4096) == window
        assert fam.flash_prefill_flops(m, n) == (full + 3 * window) * per_pair
    # the true prompt, not its bucket: 8193 tokens ask for what 8193 do,
    # whatever they are padded to, and a window layer asks for less
    assert fam.flash_prefill_flops(m, 8193) < 1.001 * \
        fam.flash_prefill_flops(m, 8192)
    assert fam._pairs_admitted(8192, 4096) < 0.76 * \
        fam._pairs_admitted(8192, None)
    assert fam.flash_prefill_bytes(m, 1000) == 4 * 2 * (
        1000 * 28 * 128 * 2 + 1000 * 4 * 128 * 2)
    # compute bounds a long prompt, by far
    ops, byt = fam.flash_prefill_flops(m, 8192), fam.flash_prefill_bytes(
        m, 8192)
    assert ops / 197e12 > 5 * byt / 819e9


def test_the_family_supplies_what_a_served_family_must():
    fam = families.load("smallthinker")
    for name in ("config", "init", "apply", "reference", "serve_programs",
                 "num_params", "weight_bytes", "kv_bytes_per_token",
                 "decode_step_bytes", "TOY_MODEL", "SOURCE_KEYS", "WIDTHS"):
        assert hasattr(fam, name), name
    ref = fam.reference()
    assert callable(ref.logits) and callable(ref.loss)
    # the reference imports nothing of the program
    with open(ref.__file__) as f:
        text = f.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text
    cfg = fam.config(fam.TOY_MODEL)
    assert type(cfg).__name__ == "SmallThinkerConfig" and cfg.num_held == 8
    assert cfg.layer_types == ("full", "window", "window", "window")


def test_reference_agrees_with_the_program_at_tiny_size():
    import jax
    import jax.numpy as jnp

    fam = families.load("smallthinker")
    model = dict(fam.TOY_MODEL, sliding_window=16, first_expert=2,
                 held_experts=4)
    cfg = fam.config(model)
    params = fam.init(jax.random.PRNGKey(1), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 48), 0, 256)
    got = fam.apply(params, tokens, cfg, None)[0]
    want = fam.reference().logits(params, tokens[0], model)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    # blocks of queries: a sequence of several blocks and a ragged last one
    ref = fam.reference()
    whole = ref.QUERY_BLOCK
    try:
        ref.QUERY_BLOCK = 20
        blocked = ref.logits(params, tokens[0], model)
    finally:
        ref.QUERY_BLOCK = whole
    assert float(jnp.max(jnp.abs(blocked - want))) < 2e-5
    assert float(ref.loss(params, tokens[0], model)) > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_reads_not_correct(seed):
    """The control of the cell's ``correct``: the reference with every
    weight product's operands rounded to float8_e4m3fn, in the program's
    place, against the program in bfloat16 (as the configuration states).
    At this size the median over positions of the logit error separates the
    two (one pick that rounding flips moves one position's logits, sound or
    not: ``tests/test_longcat.py`` says why the median); the cell judges
    every returned token at the published widths (PERF.md section 6)."""
    import jax
    import jax.numpy as jnp

    fam = families.load("smallthinker")
    model = dict(fam.TOY_MODEL)
    cfg = fam.config(dict(model, dtype="bfloat16"))
    params = fam.init(jax.random.PRNGKey(seed), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 10), (1, 64), 0,
                                256)
    ref = fam.reference()
    want = ref.logits(params, tokens[0], model)
    sound = fam.apply(params, tokens, cfg, None)[0]
    wrong = ref.logits(params, tokens[0],
                       dict(model, control_dtype="float8_e4m3fn"))
    e_sound = float(jnp.median(jnp.max(jnp.abs(sound - want), axis=-1)))
    e_wrong = float(jnp.median(jnp.max(jnp.abs(wrong - want), axis=-1)))
    assert e_wrong > 3 * e_sound, (e_sound, e_wrong)


def _spans_context(rows, prefills=()):
    """A reader's context over made-up spans: ``rows`` of (k, active, live,
    live_full, live_window, held_full, held_window, pairs, hit), one decode
    window each; ``prefills``: (prompt tokens, bucket) of an admission in
    the trace, a kernel call a layer each, at the bucket padded to whole
    blocks of 1024."""
    cfg = _load(CELLS, "configs", CONFIG + ".json")
    events, t = [], 0
    for k, active, live, lf, lw, hf, hw, pairs, hit in rows:
        events.append(("engine.dispatch_window", t, 10, {
            "k": k, "active": active, "live_tokens": live,
            "live_tokens_full": lf, "live_tokens_window": lw,
            "blocks_held_full": hf, "blocks_held_window": hw}))
        events.append(("engine.fetch_window", t + 10, 10,
                       {"k": k, "active": active, "moe_pairs_held": pairs,
                        "moe_experts_hit": hit, "moe_zero_picks": 0}))
        t += 100
    paged = ('%closed_call.7 = bf16[32,28,128]{2,1,0:T(8,128)(2,1)} '
             'custom-call(%a, %b), custom_call_target="tpu_custom_call"')
    ragged = ('%ragged.3 = bf16[256,768]{1,0} custom-call(%a), '
              'custom_call_target="tpu_custom_call"')
    flash = ('%closed_call.9 = (bf16[28,{s},128]{{2,1,0}}, f32[28,1,{s}]'
             '{{2,1,0}}) custom-call(%q, %k, %v), '
             'custom_call_target="tpu_custom_call"')
    L = cfg["model"]["num_layers"]
    ops = [(paged, i * 1000, 300_000) for i in range(2 * L)]  # two steps
    ops.append((ragged, 0, 999_000))
    for rid, (n, bucket) in enumerate(prefills):
        events.append(("engine.admit", t, 10, {
            "kind": "full", "rid": rid, "prompt_tokens": n,
            "bucket": bucket, "window_skips": int(n > 4096),
            "cached_tokens": 0, "queue_wait_ms": 1.0}))
        t += 100
        padded = -(-bucket // min(1024, bucket)) * min(1024, bucket)
        ops += [(flash.format(s=padded), 0, 2_000_000)] * L
    trace = {"device": {0: {
        "XLA Ops": ops,
        "XLA Modules": [("jit__unknown(1)", 0, 25_000_000)] * 3
        + [("jit__unknown(2)", 0, 90_000_000)]}}, "host": {}}
    return {"trace": trace, "spans": {"engine#1": events},
            "model": cfg["model"], "engine": cfg["engine"],
            "family": families.load("smallthinker"),
            "peaks": flops.peaks("TPU v5 lite"), "run": {}}


def test_readers_on_made_up_spans():
    # two windows of 16 steps, 20 slots then 24
    ctx = _spans_context(
        [(16, 20, 70_000, 100_000, 60_000, 6400, 4000, 16 * 4 * 118,
          16 * 4 * 56),
         (16, 24, 90_000, 140_000, 73_333, 8800, 4800, 16 * 4 * 142,
          16 * 4 * 60)],
        prefills=((12_000, 14_352), (9_000, 14_352), (700, 1024)))
    read = lambda name: cells_run.reader("layer_metrics", name)(ctx)  # noqa
    fam, m = ctx["family"], ctx["model"]
    assert read("expert_tokens_per_step.steady") == pytest.approx(130.0)
    assert read("expert_hit_pct.steady") == pytest.approx(100 * 58 / 64)
    live = (70_000 + 20 * 8.5 + 90_000 + 24 * 8.5) / 2
    want = 100 * fam.decode_step_bytes(m, live, 58 / 64) / 819e9 / 0.025
    assert read("decode_step_roofline.steady") == pytest.approx(want)
    assert 15 < want < 30
    # the paged kernel: 8 calls = 2 steps of 4 layers, 1.2 ms a step
    by_kind = {"full": (100_000 + 20 * 8.5 + 140_000 + 24 * 8.5) / 2,
               "window": (60_000 + 73_333) / 2}
    want = 100 * (fam.paged_attention_bytes(m, by_kind) / 819e9) / 1.2e-3
    assert read("paged_attention_roofline.steady") == pytest.approx(want)
    assert 0 < want < 100
    # the flash kernel: three prefills, 4 calls of 2 ms each, against what
    # the prompts ask for at their true lengths (the two of one bucket
    # are told apart by nothing in the trace: their mean, twice)
    least = sum(max(fam.flash_prefill_flops(m, n) / 197e12,
                    fam.flash_prefill_bytes(m, n) / 819e9)
                for n in (12_000, 9_000, 700))
    assert read("flash_prefill_roofline.steady") == pytest.approx(
        100 * least / 0.024)
    # a bucket admitted before the trace began: its calls are left out
    gone = _spans_context([], prefills=((700, 1024),))
    gone["spans"]["engine#1"] = []
    assert cells_run.reader("layer_metrics",
                            "flash_prefill_roofline.steady")(gone) is None
    assert read("window_cache_saved_pct.steady") == pytest.approx(
        100 * (1 - 8800 / 15200))


def test_readers_find_nothing_where_the_program_writes_nothing():
    """The parent commit's engine writes none of the new stats, another
    model's programs hold no such kernel, a run may have no trace: every
    new reader returns None and raises nothing."""
    new = ("paged_attention_roofline.steady", "flash_prefill_roofline.steady",
           "window_cache_saved_pct.steady")
    ctx = _spans_context([(16, 20, 70_000, 1, 1, 1, 1, 1, 1)])
    for e in ctx["spans"]["engine#1"]:
        for key in [k for k in e[3] if "_full" in k or "_window" in k]:
            del e[3][key]
    for name in new:  # no prefill in the trace either
        assert cells_run.reader("layer_metrics", name)(ctx) is None, name
    ctx = dict(_spans_context([(16, 20, 70_000, 1, 1, 1, 1, 1, 1)],
                              prefills=((700, 1024),)),
               family=families.load("dense"))
    for name in new[:2]:
        assert cells_run.reader("layer_metrics", name)(ctx) is None, name
    ctx = dict(_spans_context([]), trace=None)
    for name in new:
        assert cells_run.reader("layer_metrics", name)(ctx) is None, name


def test_the_traffic_is_the_issues():
    from cells import loadgen

    t = _load(CELLS, "traffic", "longctx-mixed.json")
    assert (t["loop"], t["stream"], t["runner"]) == ("open", True, "serve")
    assert t["prompt_tokens"] | {"why_max": 0} == {
        "dist": "lognormal", "median": 3072, "sigma": 1.0, "min": 128,
        "max": 12288, "why_max": 0}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 512,
                                  "sigma": 0.7, "min": 64, "max": 2048}
    assert t["arrivals"]["process"] == "poisson"
    assert t["order"]["block"] == 8 and t["warmup"]["ramp_s"] == 60
    assert t["warmup"]["prompt_lengths"] == [
        128, 256, 512, 1024, 2048, 4096, 8192, 12288]
    assert t["warmup"]["window_lengths"] == list(range(1, 16))
    assert t["trace"] == {"start_s": 4.0, "seconds": 4.0}
    assert t["reference"]["requests"] == 3
    cfg = _load(CELLS, "configs", CONFIG + ".json")
    reqs = loadgen.make_requests(t, 2 ** 31 + 9, cfg["model"]["vocab_size"],
                                 400.0)
    lens = [len(r["prompt"]) for r in reqs]
    assert all(128 <= n <= 12288 for n in lens)
    assert all(64 <= r["max_tokens"] <= 2048 for r in reqs)
    assert all(0 <= tok < 151936 for r in reqs[:3] for tok in r["prompt"])
    assert 12288 + 2048 < cfg["engine"]["max_len"] - 1
    # short and long in one queue
    share = lambda f: sum(map(f, lens)) / len(lens)  # noqa: E731
    assert 0.05 < share(lambda n: n < 1024) < 0.25
    assert 0.25 < share(lambda n: n > 4096) < 0.5


@pytest.mark.parametrize("trace_flag", [0, 1])
def test_rehearsal_of_the_cell_ends_in_a_well_formed_line(trace_flag):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(CELLS, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 5), "--seconds", "3", "--trace",
         str(trace_flag), "--rehearse"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu" and last["metrics"]
    assert all(k.startswith("rehearsal.") for k in last["metrics"])
    if not trace_flag:
        assert set(last["metrics"]) == {"rehearsal.tpot_ms_p50",
                                        "rehearsal.setup_s"}
