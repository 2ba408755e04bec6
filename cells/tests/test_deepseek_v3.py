"""The benchmark's own tests of family ``deepseek_v3`` and its cell.  CPU
only:

    JAX_PLATFORMS=cpu python -m pytest cells/tests/test_deepseek_v3.py -q

A file of its own because the family came by files alone (``cells/README.md``,
"A model family").  The reference against the program at tiny size, the
shares that add up, the router, the YaRN table and the control are in the
repo's ``tests/test_deepseek_v3.py`` (the same reference file).
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

from cells import families, flops, trace  # noqa: E402
from cells import run as cells_run  # noqa: E402

CONFIG = "gigachat3.1-702b-a36b-ep16-serve"
CELL = "serve-gigachat-long-answers"
US = 1000

# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# GigaChat3.1-702B-A36B): what the source publishes, under the source's keys
PUBLISHED = dict(
    vocab_size=128256, max_position_embeddings=262144, hidden_size=7168,
    intermediate_size=18432, moe_intermediate_size=2048,
    num_hidden_layers=64, num_nextn_predict_layers=1,
    num_attention_heads=64, n_shared_experts=1, n_routed_experts=256,
    ep_size=1, routed_scaling_factor=2.5, kv_lora_rank=512,
    q_lora_rank=1536, qk_rope_head_dim=64, v_head_dim=192,
    qk_nope_head_dim=128, topk_method="noaux_tc", n_group=8, topk_group=4,
    num_experts_per_tok=8, moe_layer_freq=1, first_k_dense_replace=3,
    norm_topk_prob=True, scoring_func="sigmoid", num_key_value_heads=64,
    hidden_act="silu", rms_norm_eps=1e-06, rope_theta=100000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64,
                  "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096,
                  "rope_type": "yarn"},
    attention_bias=False, tie_word_embeddings=False,
    model_type="deepseek_v3")
REDUCED = {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "max_position_embeddings"}


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
    assert not any(k.endswith(("_dim", "_rank", "intermediate_size"))
                   or k in ("hidden_size", "num_experts_per_tok")
                   for k in REDUCED)
    # the source's own keys at the top level say the same as ``model``
    for theirs, ours in fam.SOURCE_KEYS.items():
        assert config[theirs] == config["model"][ours], theirs
    # the floors: four expert layers behind the leading dense ones (which
    # count once), 8 routed experts, an eighth of the vocabulary
    m = config["model"]
    assert m["num_layers"] >= 4 and m["dense_layers"] == 1
    assert m["hidden_layers"] == m["num_layers"] + m["dense_layers"] == 6
    assert m["held_experts"] >= 8
    assert m["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # the router keeps its published width; the range held lies inside it,
    # and inside ONE of the router's groups
    assert m["num_experts"] == PUBLISHED["n_routed_experts"]
    assert 0 <= m["first_expert"] <= m["num_experts"] - m["held_experts"]
    size = m["num_experts"] // m["n_group"]
    assert m["first_expert"] // size == (
        m["first_expert"] + m["held_experts"] - 1) // size == 2
    assert "16 v5e chips that share each layer" in config["stands_for"]
    assert "vocabulary 8 ways" in config["stands_for"]
    assert "NOT served" in config["stands_for"]  # the MTP module
    assert {"dropped_groups", "router_bias", "rotary_pairs", "yarn"} <= set(
        config["assumed"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert config["engine"]["max_len"] == m["max_seq_len"] == 5120


def test_parameters_and_bytes_by_hand(config):
    fam = families.load("deepseek_v3")
    m = config["model"]
    attn = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 320
            + 64 * 192 * 7168)
    assert fam.attention_params(m) == attn == 132_579_328
    assert fam.expert_params(m) == 3 * 7168 * 2048 == 44_040_192
    assert fam.derived_pair_params(m) == 512 * 64 * 320 == 10_485_760
    dense, router = 3 * 7168 * 18432, 7168 * 256
    norms = 2 * 7168 + 1536 + 512
    assert fam.dense_layer_params(m) == attn + dense + norms
    assert round((attn + dense) / 1e6, 2) == 528.94
    outside = attn + 44_040_192 + router + 256 + norms
    assert fam.layer_params_outside_experts(m) == outside
    assert round(outside / 1e6, 2) == 178.47
    total = (2 * 16032 * 7168 + (attn + dense + norms)
             + 5 * (outside + 16 * 44_040_192) + 7168)
    assert fam.num_params(m) == total
    assert round(total / 1e6, 1) == 5174.4 and round(total * 2 / 1e9, 2) \
        == 10.35
    assert fam.weight_bytes(m) == 2 * total + 5 * 256 * 2
    assert round((fam.weight_bytes(m)
                  + 6 * 2 * fam.derived_pair_params(m)) / 1e9, 2) == 10.47
    # a cached position: 6 layers x (512 + 64), stored 640 wide
    assert fam.latent_bytes_per_token(m) == 6 * 576 * 2 == 6912
    assert fam.kv_bytes_per_token(m) == 6 * 640 * 2 == 7680
    assert fam.latent_attention_bytes(m, 1000) == 1000 * 576 * 2
    experts = 5 * 16 * 44_040_192 * 2
    embed = 16032 * 7168 * 2
    assert fam.decode_step_bytes(m, 1000, 0.5) == (
        fam.weight_bytes(m) - embed - experts + 0.5 * experts
        + 1000 * 6912)
    # what the predictions start from: 10.1 GB with every expert hit, 8.4
    # with three quarters of them (12 of 16), before any live cache
    assert round(fam.decode_step_bytes(m, 0) / 1e9, 1) == 10.1
    assert round(fam.decode_step_bytes(m, 0, 0.75) / 1e9, 1) == 8.4
    # the pool as the engine makes it
    e = config["engine"]
    assert round(e["num_blocks"] * e["block_size"] * 7680 / 1e9, 2) == 2.02


def test_the_family_supplies_what_a_served_family_must():
    fam = families.load("deepseek_v3")
    for name in ("config", "init", "apply", "reference", "serve_programs",
                 "num_params", "weight_bytes", "kv_bytes_per_token",
                 "latent_attention_bytes", "decode_step_bytes", "TOY_MODEL",
                 "SOURCE_KEYS", "WIDTHS"):
        assert hasattr(fam, name), name
    ref = fam.reference()
    assert callable(ref.logits) and callable(ref.loss)
    # the reference imports nothing of the program
    with open(ref.__file__) as f:
        text = f.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text
    cfg = fam.config(dict(fam.TOY_MODEL))
    assert type(cfg).__name__ == "DeepseekV3Config" and cfg.num_held == 8
    assert (cfg.num_layers, cfg.dense_layers, cfg.expert_layers) == (3, 1, 2)
    assert fam.config(fam.model_of(cfg)) == cfg
    # the cell's configuration as the program takes it
    big = fam.config(_load(CELLS, "configs", CONFIG + ".json")["model"])
    assert (big.num_layers, big.expert_layers, big.rope_factor,
            big.rope_original_max_len) == (6, 5, 64, 4096)
    assert round(big.softmax_scale, 6) == 0.144680
    assert big.held_groups == (False, False, True) + (False,) * 5


# ------------------------------------------------------------ the readers

def _op(name, start, end, scope=None):
    op_name = None if scope is None else f"jit(<unknown>)/{scope}/mul"
    return (f"%{name} = f32[2] fusion()", start * US, (end - start) * US,
            op_name)


def _context(windows):
    """A reader's context over made-up spans and a made-up joined trace:
    ``windows`` of (k, active, group_tokens), one decode window each, and
    two executions of the decode program of 100 us: the router 6, the
    routed experts 20, the shared expert 9 inside them, XLA's own copy 5,
    attention the rest."""
    cfg = _load(CELLS, "configs", CONFIG + ".json")
    events, t = [], 0
    for k, active, group_tokens in windows:
        events.append(("engine.fetch_window", t, 10,
                       {"k": k, "active": active, "moe_pairs_held": 1,
                        "moe_experts_hit": 1, "moe_zero_picks": 0,
                        "moe_group_tokens": group_tokens}))
        t += 100
    ops, modules = [], []
    for s in (0, 100):
        modules.append(("jit__unknown(11)", s * US, 100 * US))
        ops += [_op("fusion.1", s, s + 60, "engine.decode/attn.core"),
                _op("fusion.2", s + 60, s + 66, "engine.decode/router"),
                _op("fusion.3", s + 66, s + 86, "engine.decode/experts"),
                _op("fusion.4", s + 86, s + 95,
                    "engine.decode/experts/experts.shared"),
                _op("copy.5", s + 95, s + 100)]
    loaded = {"ops": ops, "modules": modules}
    tr = {"device": {0: {trace.OPS_LINE: [e[:3] for e in ops],
                         trace.MODULES_LINE: modules}}, "host": {}}
    return {"trace": tr, "spans": {"engine#1": events}, "parts": loaded,
            "model": cfg["model"], "engine": cfg["engine"],
            "family": families.load("deepseek_v3"),
            "peaks": flops.peaks("TPU v5 lite"), "run": {}}


def _read(name, ctx):
    return cells_run.reader("layer_metrics", name)(ctx)


def test_the_three_new_readers_on_made_up_spans():
    # two windows of 16 steps: 40 slots then 50, 5 expert layers
    ctx = _context([(16, 40, 16 * 40 * 5 // 2), (16, 50, 16 * 50 * 3)])
    assert _read("expert_group_hit_pct.steady", ctx) == pytest.approx(
        100 * (1600 + 2400) / ((40 + 50) * 16 * 5))
    assert _read("decode_router_ms.steady", ctx) == pytest.approx(0.006)
    assert _read("decode_shared_expert_ms.steady", ctx) == pytest.approx(
        0.009)
    # the shared expert is INSIDE the expert layer's time, not beside it
    assert _read("decode_experts_ms.steady", ctx) == pytest.approx(0.035)
    assert _read("decode_unscoped_pct.steady", ctx) == pytest.approx(5.0)


def test_the_new_readers_find_nothing_where_the_program_writes_nothing():
    """A program without the counter or the scopes (another model, the
    parent commit) or a run without a trace: every new reader returns
    None and does not raise."""
    ctx = _context([(16, 40, 1)])
    for e in ctx["spans"]["engine#1"]:
        del e[3]["moe_group_tokens"]
    assert _read("expert_group_hit_pct.steady", ctx) is None
    ctx["parts"]["ops"] = [
        (n, s, d, (o or "").replace("router", "ffn").replace(
            "/experts.shared", "") or None)
        for n, s, d, o in ctx["parts"]["ops"]]
    assert _read("decode_router_ms.steady", ctx) is None
    assert _read("decode_shared_expert_ms.steady", ctx) is None
    for name in ("expert_group_hit_pct.steady", "decode_router_ms.steady",
                 "decode_shared_expert_ms.steady"):
        assert _read(name, {"trace": None, "model": ctx["model"]}) is None


def test_the_new_metrics_are_entries_of_the_cell_alone():
    bench = _load(ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    new = {"decode_router_ms.steady": ("program_span", "model step", "ms"),
           "decode_shared_expert_ms.steady": ("program_span", "model step",
                                              "ms"),
           "expert_group_hit_pct.steady": ("program_counter", "model", "%")}
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(new)
    for name, (source, layer, unit) in new.items():
        m = entries[name]
        assert (m["source"], m["layer"], m["unit"]) == (source, layer, unit)
        assert m["moves"] == "tpot_ms_p50" and m["workloads"] == [CELL]
    # and the cell reports what LongCat's cell reports, but the
    # zero-compute picks
    longcat = "serve-longcat-long-answers"
    for m in bench["end_to_end"] + bench["per_layer"]:
        if longcat in m.get("workloads", []):
            assert (CELL in m["workloads"]) == (
                m["name"] != "zero_expert_pick_pct.steady"), m["name"]
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG


# ------------------------------------------------------------ the traffic

def test_the_traffic_is_chat_long_answers_with_longer_answers():
    """``chat-long-answers.json`` but for the rate, the answers, the seed
    of the pool, the ramp, the tolerance and (the one change ISSUE 47
    allowed when six seeds spread over 2.5%) prompts to 1020, the 1024
    bucket the largest."""
    from cells import loadgen

    mine = _load(CELLS, "traffic", "assistant-long-answers.json")
    theirs = _load(CELLS, "traffic", "chat-long-answers.json")
    same = lambda a, b, keys: {k: a[k] for k in keys} == {  # noqa: E731
        k: b[k] for k in keys}
    assert same(mine["prompt_tokens"], theirs["prompt_tokens"],
                ("dist", "median", "sigma", "min"))
    assert mine["prompt_tokens"]["max"] == 1020
    assert "2.93%" in mine["prompt_tokens"]["why_max"]
    assert mine["warmup"]["prompt_lengths"] == \
        theirs["warmup"]["prompt_lengths"][:-1] == [64, 128, 256, 512, 1024]
    assert same(mine["warmup"], theirs["warmup"],
                ("window_lengths", "tokens"))
    assert same(mine, theirs, ("runner", "loop", "stream", "trace"))
    assert same(mine["reference"], theirs["reference"],
                ("requests", "model_programs"))
    assert mine["order"]["block"] == theirs["order"]["block"] == 8
    assert mine["output_tokens"] == {"dist": "lognormal", "median": 1024,
                                     "sigma": 0.7, "min": 128, "max": 3072}
    assert (mine["pool_seed"], mine["warmup"]["ramp_s"]) == (4700, 60.0)
    assert (mine["loop"], mine["stream"]) == ("open", True)
    assert mine["arrivals"]["process"] == "poisson"
    assert 1.0 <= mine["arrivals"]["rate_rps"] <= 3.0
    assert "sweep" in mine["arrivals"]["why"]
    reqs = loadgen.make_requests(mine, 2 ** 31 + 9, 16032, 80.0)
    assert all(64 <= len(r["prompt"]) <= 1020
               and 128 <= r["max_tokens"] <= 3072 for r in reqs)
    assert all(0 <= t < 16032 for r in reqs[:5] for t in r["prompt"])
    cfg = _load(CELLS, "configs", CONFIG + ".json")
    assert 2040 + 3072 < cfg["engine"]["max_len"] - 1  # as ISSUE 47 set it
    assert cfg["engine"]["num_blocks"] == 16400


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
