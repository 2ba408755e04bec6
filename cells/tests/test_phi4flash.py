"""The benchmark's own tests of family ``phi4flash`` and its cell.  CPU only:

    JAX_PLATFORMS=cpu python -m pytest cells/tests/test_phi4flash.py -q

A file of its own because the family came by files alone (``cells/README.md``,
"A model family"): ``test_cells.py`` is a file the benchmark had.  The
engine's three pools, a record's life and the kernels' scale are in the
repo's ``tests/test_phi4flash.py`` (the same reference file).
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

CONFIG = "phi-4-mini-flash-reasoning-L32-serve"
CELL = "serve-phi4flash-reasoning"
TRAFFIC = "reasoning-long-answers"

# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Phi-4-mini-flash-reasoning): what the source publishes, under its keys
PUBLISHED = dict(
    embd_pdrop=0, hidden_act="silu", hidden_size=2560,
    intermediate_size=10240, layer_norm_eps=1e-05,
    max_position_embeddings=262144, mb_per_layer=2, model_type="phi4flash",
    num_attention_heads=40, num_hidden_layers=32, num_key_value_heads=20,
    resid_pdrop=0, sliding_window=512, tie_word_embeddings=True,
    mlp_bias=False, lm_head_bias=False, vocab_size=200064)
REDUCED = {"max_position_embeddings"}
NEW = ("decode_step_hbm_roofline.steady", "hybrid_attention_roofline.steady",
       "state_update_roofline.steady", "decode_state_ms.steady",
       "cross_decoder_prefill_pct.steady")


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
    assert len(entry["why"]) <= 200
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
                   or "head" in k or "window" in k or "state" in k
                   for k in REDUCED)
    # the source's own keys at the top level say the same as ``model``
    for theirs, ours in fam.SOURCE_KEYS.items():
        assert config[theirs] == config["model"][ours], theirs
    m = config["model"]
    # nothing is cut but the context: every layer, the whole vocabulary
    assert m["num_layers"] == 32 and m["vocab_size"] == 200064
    assert m["head_dim"] * m["num_heads"] == m["hidden_size"]
    kinds = fam.layer_kinds(m)
    assert kinds[:16] == ["ssm", "window"] * 8
    assert kinds[16:18] == ["ssm", "full"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert config["mb_per_layer"] == 2  # a state-space layer every second
    # the assumed values are listed, and are what the model runs
    assert {"mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
            "mamba_biases", "ssm_state_dtype", "attention_biases",
            "rotary_embedding", "differential_attention"} \
        <= set(config["assumed"])
    assert (m["mamba_d_state"], m["mamba_d_conv"], m["mamba_expand"],
            m["mamba_dt_rank"]) == (16, 4, 2, -(-2560 // 16))
    assert "WHOLE" in config["stands_for"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (CONFIG, TRAFFIC)
    e = config["engine"]
    assert e["max_len"] == m["max_seq_len"] == 4112
    assert (e["batch_slots"], e["block_size"], e["decode_window"]) == (
        64, 16, 16)
    assert e["num_blocks"]["state"] == e["batch_slots"] + 1
    # the cell reports the bounded metric, the shared .steady readers and
    # its own five; not the readers that know experts or two types only
    mine = {x["name"] for x in bench["per_layer"]
            if CELL in x.get("workloads", [])}
    shared = {x["name"] for x in bench["per_layer"]
              if {"serve-chat-steady", "serve-longcat-long-answers",
                  "serve-smallthinker-long-context"}
              <= set(x.get("workloads", []))}
    assert shared <= mine and set(NEW) <= mine
    assert "decode_ffn_ms.steady" in mine  # a dense MLP in every layer
    assert not mine & {
        "decode_step_roofline.steady", "paged_attention_roofline.steady",
        "flash_prefill_roofline.steady", "latent_attention_roofline.steady",
        "expert_hit_pct.steady", "expert_tokens_per_step.steady",
        "zero_expert_pick_pct.steady", "decode_experts_ms.steady",
        "window_cache_saved_pct.steady"}
    for x in bench["per_layer"]:
        if x["name"] in NEW:
            assert x["workloads"] == [CELL] and x["moves"] == "tpot_ms_p50"
    e2e = next(x for x in bench["end_to_end"] if x["name"] == "tpot_ms_p50")
    assert e2e["workloads"][-1] == CELL


def test_parameters_and_bytes_by_hand(config):
    fam = families.load("phi4flash")
    m = config["model"]
    assert fam.mlp_params(m) == 3 * 2560 * 10240 == 78_643_200
    ssm = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * (160 + 32) + 160 * 5120
           + 5120 + 5120 * 16 + 5120 + 5120 * 2560)
    assert fam.ssm_params(m) == ssm == 41_241_600
    diff = 2560 * 2560 + 2560 + 4 * 64 + 128
    assert fam.attention_params(m) == 2560 * 5120 + 5120 + diff == 19_668_864
    assert fam.cross_attention_params(m) == 2560 * 2560 + 2560 + diff \
        == 13_112_704
    assert fam.gmu_params(m) == 2 * 2560 * 5120 == 26_214_400
    layers = (32 * (78_643_200 + 4 * 2560) + 9 * ssm + 9 * 19_668_864
              + 7 * 13_112_704 + 7 * 26_214_400)
    # ISSUE 39's count, 3 852 557 824, left out the final LayerNorm
    assert layers + 200064 * 2560 == 3_852_557_824
    assert fam.num_params(m) == 3_852_557_824 + 2 * 2560
    f32 = 9 * 5120 * 16 + 16 * 4 * 64  # A_log and the lambda vectors
    assert fam.float32_params(m) == f32
    assert fam.weight_bytes(m) == 2 * fam.num_params(m) + 2 * f32
    assert round(fam.weight_bytes(m) / 1e9, 3) == 7.707
    # a cached position: 5120 B a storing layer, 9 of them
    assert fam.kv_row_bytes(m) == 2 * 20 * 64 * 2 == 5120
    assert fam.storing_layers(m) == {"full": 1, "window": 8}
    assert fam.reading_layers(m) == {"full": 8, "window": 8}
    assert fam.kv_bytes_per_token(m) == 9 * 5120
    # a request's record: 9 x (5120 x 16 x 4 + 3 x 5120 x 2) bytes
    assert fam.state_record_bytes(m) == 9 * (327_680 + 30_720) == 3_225_600
    live = {"full": 70_400, "window": 32_768, "state": 64}
    attn = 8 * 70_400 * 5120 + 8 * 32_768 * 5120
    assert fam.hybrid_attention_bytes(m, live) == attn
    assert fam.state_update_bytes(m, 64) == 2 * 64 * 3_225_600
    assert fam.decode_step_bytes(m, live) == (
        fam.weight_bytes(m) + attn + 2 * 64 * 3_225_600)
    # the issue's 12.3 GB a step at 64 slots of 1100 positions: 15.1 ms
    assert round(fam.decode_step_bytes(m, live) / 1e9, 1) == 12.3
    assert round(fam.decode_step_bytes(m, live) / 819e9 * 1e3, 1) == 15.1
    # the pools as the engine makes them, and what is resident
    e = config["engine"]
    nb = e["num_blocks"]
    assert nb["window"] <= e["batch_slots"] * 34 + 4  # 34 blocks a slot
    pools = ((nb["full"] - 1) + (nb["window"] - 1) * 8) * 16 * 5120 \
        + nb["state"] * 3_225_600
    assert round(pools / 1e9, 2) == 2.32
    assert 0.6 < (fam.weight_bytes(m) + pools) / 15.75e9 < 0.7


def test_the_family_supplies_what_a_served_family_must():
    import jax

    fam = families.load("phi4flash")
    for name in ("config", "init", "apply", "reference", "serve_programs",
                 "num_params", "weight_bytes", "kv_bytes_per_token",
                 "decode_step_bytes", "hybrid_attention_bytes",
                 "state_update_bytes", "TOY_MODEL", "SOURCE_KEYS", "WIDTHS"):
        assert hasattr(fam, name), name
    ref = fam.reference()
    assert callable(ref.logits) and callable(ref.loss)
    # the reference imports nothing of the program
    with open(ref.__file__) as f:
        text = f.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text
    cfg = fam.config(fam.TOY_MODEL)
    assert type(cfg).__name__ == "Phi4FlashConfig"
    assert list(cfg.layer_kinds) == fam.layer_kinds(fam.TOY_MODEL)
    assert ref.kinds(fam.TOY_MODEL) == fam.layer_kinds(fam.TOY_MODEL)
    # num_params is the leaves of init's tree
    params = fam.init(jax.random.PRNGKey(0), cfg)
    assert sum(a.size for a in jax.tree.leaves(params)) \
        == fam.num_params(fam.TOY_MODEL)
    f32 = sum(a.size for a in jax.tree.leaves(params)
              if a.dtype == "float32")
    assert fam.float32_params(fam.TOY_MODEL) <= f32  # a float32 toy: all
    low = fam.init(jax.random.PRNGKey(0),
                   fam.config(dict(fam.TOY_MODEL, param_dtype="bfloat16")))
    assert sum(a.size for a in jax.tree.leaves(low)
               if a.dtype == "float32") == fam.float32_params(fam.TOY_MODEL)


def test_reference_agrees_with_the_program_at_tiny_size():
    import jax
    import jax.numpy as jnp

    fam = families.load("phi4flash")
    model = dict(fam.TOY_MODEL, sliding_window=16)
    cfg = fam.config(model)
    params = fam.init(jax.random.PRNGKey(1), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 48), 0, 256)
    got = fam.apply(params, tokens, cfg, None)[0]
    ref = fam.reference()
    want = ref.logits(params, tokens[0], model)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    # blocks of queries (several and a ragged last one) and of vocabulary
    whole = ref.QUERY_BLOCK, ref.VOCAB_BLOCKS
    try:
        ref.QUERY_BLOCK, ref.VOCAB_BLOCKS = 20, 1
        blocked = ref.logits(params, tokens[0], model)
    finally:
        ref.QUERY_BLOCK, ref.VOCAB_BLOCKS = whole
    assert float(jnp.max(jnp.abs(blocked - want))) < 2e-5
    wide = dict(model, vocab_size=8192)  # the head in 16 pieces
    cfg = fam.config(wide)
    params = fam.init(jax.random.PRNGKey(3), cfg)
    got = fam.apply(params, tokens, cfg, None)[0]
    assert float(jnp.max(jnp.abs(
        got - ref.logits(params, tokens[0], wide)))) < 2e-5
    assert float(ref.loss(params, tokens[0], wide)) > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_reads_not_correct(seed):
    """The control of the cell's ``correct``: the reference with every
    weight product's operands rounded to float8_e4m3fn, in the program's
    place, against the program in bfloat16 (as the configuration states).
    At this size the median over positions of the logit error separates the
    two; the cell judges every returned token at the published widths
    (PERF.md section 6)."""
    import jax
    import jax.numpy as jnp

    fam = families.load("phi4flash")
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


def _spans_context(rows, admissions=()):
    """A reader's context over made-up spans and a made-up joined trace:
    ``rows`` of (k, active, live_full, live_window), one decode window
    each; two executions of the decode program of 20 ms, each 16 paged
    kernel calls of 0.3 ms, 9 updates of 0.1 ms, 9 convolutions of 0.02
    ms and 7 gates of 0.01 ms; ``admissions``: (positions, cross
    positions) of an ``engine.first_tokens``."""
    cfg = _load(CELLS, "configs", CONFIG + ".json")
    events, t = [], 0
    for k, active, lf, lw in rows:
        events.append(("engine.dispatch_window", t, 10, {
            "k": k, "active": active, "live_tokens": 1,
            "live_tokens_full": lf, "live_tokens_window": lw,
            "live_tokens_state": active, "state_records_held": active,
            "blocks_held_full": 1, "blocks_held_window": 1}))
        t += 100
    for positions, cross in admissions:
        events.append(("engine.first_tokens", t, 10, {
            "n": cross, "prefill_positions": positions,
            "prefill_cross_positions": cross}))
        t += 100
    paged = ('%closed_call.7 = bf16[64,40,128]{2,1,0:T(8,128)(2,1)} '
             'custom-call(%a, %b), custom_call_target="tpu_custom_call"')
    pre = "jit(<unknown>)/engine.decode/"
    step = ([(paged, 300_000, pre + "attn.core/pallas_call")] * 16
            + [("%fusion.1 = f32[]", 100_000,
                pre + "attn.core/ssm.update/mul")] * 9
            + [("%fusion.2 = f32[]", 20_000,
                pre + "attn.core/ssm.conv/add")] * 9
            + [("%fusion.3 = f32[]", 10_000, pre + "attn.core/gmu/mul")] * 7
            + [("%fusion.4 = f32[]", 50_000, pre + "attn.core/diff/sub")] * 16)
    rest = 20_000_000 - sum(d for _, d, _ in step)
    step.append(("%fusion.5 = f32[]", rest, pre + "ffn/dot_general"))
    ops, modules, t = [], [], 0
    for _ in range(2):
        modules.append(("jit__unknown(1)", t, 20_000_000))
        for name, dur, op_name in step:
            ops.append((name, t, dur, op_name))
            t += dur
        t += 1000
    trace = {"device": {0: {
        "XLA Ops": [e[:3] for e in ops],
        "XLA Modules": modules}}, "host": {}}
    return {"trace": trace, "spans": {"engine#1": events},
            "parts": {"ops": ops, "modules": modules},
            "model": cfg["model"], "engine": cfg["engine"],
            "family": families.load("phi4flash"),
            "peaks": flops.peaks("TPU v5 lite"), "run": {}}


def test_readers_on_made_up_spans():
    # two windows of 16 steps, 48 slots then 52
    ctx = _spans_context([(16, 48, 50_000, 24_000), (16, 52, 58_000, 26_000)],
                         admissions=((700, 3), (300, 1)))
    read = lambda name: cells_run.reader("layer_metrics", name)(ctx)  # noqa
    fam, m = ctx["family"], ctx["model"]
    live = {"full": (50_000 + 48 * 8.5 + 58_000 + 52 * 8.5) / 2,
            "window": 25_000, "state": 50}
    want = 100 * fam.decode_step_bytes(m, live) / 819e9 / 0.020
    assert read("decode_step_hbm_roofline.steady") == pytest.approx(want)
    assert 50 < want < 100
    # the paged kernel: 32 calls = 2 steps of 16 reading layers, 4.8 ms a step
    want = 100 * (8 * live["full"] + 8 * 25_000) * 5120 / 819e9 / 4.8e-3
    assert read("hybrid_attention_roofline.steady") == pytest.approx(want)
    assert 0 < want < 100
    # the update and the convolution: 9 x (0.1 + 0.02) ms a step
    want = 100 * (2 * 50 * 3_225_600 / 819e9) / 1.08e-3
    assert read("state_update_roofline.steady") == pytest.approx(want)
    assert 0 < want < 100
    assert read("decode_state_ms.steady") == pytest.approx(1.08 + 0.07)
    assert read("decode_state_ms.steady") < read("decode_attention_ms.steady")
    assert read("decode_attention_ms.steady") == pytest.approx(
        4.8 + 1.08 + 0.07 + 0.8)
    assert read("cross_decoder_prefill_pct.steady") == pytest.approx(0.4)


def test_readers_find_nothing_where_the_program_writes_nothing():
    """Another model's engine writes none of the new stats and its programs
    hold no such scope or kernel, a run may have no trace: every new reader
    returns None and raises nothing."""
    ctx = _spans_context([(16, 48, 50_000, 24_000)], admissions=((700, 3),))
    for e in ctx["spans"]["engine#1"]:
        for key in [k for k in e[3] if "state" in k or "cross" in k]:
            del e[3][key]
    ctx["parts"]["ops"] = [(n, s, d, (o or "").replace("ssm.", "x.").replace(
        "gmu", "x")) for n, s, d, o in ctx["parts"]["ops"]]
    for name in NEW:
        assert cells_run.reader("layer_metrics", name)(ctx) is None, name
    ctx = dict(_spans_context([(16, 48, 50_000, 24_000)]),
               family=families.load("smallthinker"))
    for name in NEW[:3]:
        assert cells_run.reader("layer_metrics", name)(ctx) is None, name
    ctx = dict(_spans_context([]), trace=None)
    for name in NEW:
        assert cells_run.reader("layer_metrics", name)(ctx) is None, name


def test_the_traffic_is_the_issues():
    from cells import loadgen

    t = _load(CELLS, "traffic", TRAFFIC + ".json")
    assert (t["loop"], t["stream"], t["runner"]) == ("open", True, "serve")
    assert t["pool_seed"] == 3900
    assert t["prompt_tokens"] | {"why_max": 0} == {
        "dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32,
        "max": 1024, "why_max": 0}
    assert t["output_tokens"] == {"dist": "lognormal", "median": 1536,
                                  "sigma": 0.6, "min": 256, "max": 3072}
    assert t["arrivals"]["process"] == "poisson"
    assert t["order"]["block"] == 8 and t["warmup"]["ramp_s"] == 60
    assert t["warmup"]["prompt_lengths"] == [32, 64, 128, 256, 512, 1024]
    assert t["warmup"]["window_lengths"] == list(range(1, 16))
    assert t["trace"] == {"start_s": 4.0, "seconds": 4.0}
    assert t["reference"]["requests"] == 3
    bench = _load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert f"{t['arrivals']['rate_rps']}/s" in cell["why"]
    cfg = _load(CELLS, "configs", CONFIG + ".json")
    reqs = loadgen.make_requests(t, 2 ** 31 + 9, cfg["model"]["vocab_size"],
                                 400.0)
    assert all(32 <= len(r["prompt"]) <= 1024 for r in reqs)
    assert all(256 <= r["max_tokens"] <= 3072 for r in reqs)
    assert all(0 <= tok < 200064 for r in reqs[:3] for tok in r["prompt"])
    assert 1024 + 3072 <= cfg["engine"]["max_len"] - 1
    # a short problem in, a long chain of thought out
    assert sum(r["max_tokens"] for r in reqs) > 4 * sum(
        len(r["prompt"]) for r in reqs)


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
