"""The benchmark's own tests of family ``longcat_flash`` and its cell.  CPU
only:

    JAX_PLATFORMS=cpu python -m pytest cells/tests/test_longcat_flash.py -q

A file of its own because the family came by files alone (``cells/README.md``,
"A model family"): ``test_cells.py`` is a file the benchmark had.  The
reference against the program at tiny size, the shares that add up and the
control are in the repo's ``tests/test_longcat.py`` (the same reference file).
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

CONFIG = "longcat-flash-omni-L4-ep32-serve"
CELL = "serve-longcat-long-answers"

# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# LongCat-Flash-Omni): what the source publishes, under the source's keys
PUBLISHED = dict(
    attention_bias=False, vocab_size=131072, hidden_size=6144,
    ffn_hidden_size=12288, expert_ffn_hidden_size=2048, num_layers=28,
    num_attention_heads=64, kv_lora_rank=512, q_lora_rank=1536,
    qk_rope_head_dim=64, v_head_dim=128, qk_nope_head_dim=128,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, routed_scaling_factor=6,
    n_routed_experts=512, max_position_embeddings=131072, rms_norm_eps=1e-05,
    rope_theta=10000000, attention_method="MLA", zero_expert_num=256,
    zero_expert_type="identity", moe_topk=12)
REDUCED = {"num_layers", "n_routed_experts", "vocab_size",
           "max_position_embeddings"}


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
    assert set(entry["reduced"]) == set(config["reduced"]) == REDUCED
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert config[key] < value, key
        else:
            assert config[key] == value, key
    # a cut is of a key the family maps, and never of one of its widths
    assert REDUCED <= set(fam.SOURCE_KEYS) - fam.WIDTHS
    assert fam.WIDTHS <= set(fam.SOURCE_KEYS)
    assert not any(k.endswith(("_dim", "_rank")) or "hidden" in k
                   or k == "moe_topk" for k in REDUCED)
    # the source's own keys at the top level say the same as ``model``
    for theirs, ours in fam.SOURCE_KEYS.items():
        assert config[theirs] == config["model"][ours], theirs
    # the floors: four layers, 8 routed experts, an eighth of the vocabulary
    m = config["model"]
    assert m["num_layers"] >= 4 and m["held_experts"] >= 8
    assert m["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # the router keeps its published width; the range held lies inside it
    assert m["num_experts"] == PUBLISHED["n_routed_experts"]
    assert 0 <= m["first_expert"] <= m["num_experts"] - m["held_experts"]
    assert "32 v5e chips that share each layer" in config["stands_for"]
    assert {"norm_topk_prob", "router_bias", "rotary_pairs"} <= set(
        config["assumed"])
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert config["engine"]["max_len"] == m["max_seq_len"]


def test_parameters_and_bytes_by_hand(config):
    fam = families.load("longcat_flash")
    m = config["model"]
    attn = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
            + 8192 * 6144)
    assert fam.attention_params(m) == attn == 90_570_752
    assert fam.expert_params(m) == 3 * 6144 * 2048 == 37_748_736
    dense, router = 3 * 6144 * 12288, 6144 * 768
    norms = 4 * 6144 + 2 * 1536 + 2 * 512
    outside = 2 * attn + 2 * dense + router + 768 + norms
    assert fam.layer_params_outside_experts(m) == outside
    assert round(outside / 1e6, 1) == 638.9
    total = 2 * 16384 * 6144 + 4 * (outside + 16 * 37_748_736) + 6144
    assert fam.num_params(m) == total
    assert round(total * 2 / 1e9, 2) == 10.35
    assert fam.weight_bytes(m) == 2 * total + 4 * 768 * 2
    # a cached position: 8 attention blocks x (512 + 64), stored 640 wide
    assert fam.latent_bytes_per_token(m) == 8 * 576 * 2 == 9216
    assert fam.kv_bytes_per_token(m) == 8 * 640 * 2 == 10240
    assert fam.latent_attention_bytes(m, 1000) == 1000 * 576 * 2
    experts = 4 * 16 * 37_748_736 * 2
    embed = 16384 * 6144 * 2
    assert fam.decode_step_bytes(m, 1000, 0.5) == (
        fam.weight_bytes(m) - embed - experts + 0.5 * experts
        + 1000 * 9216)
    # the floor the predictions start from: ~9.5 GB at no live cache
    assert round(fam.decode_step_bytes(m, 0) / 1e9, 1) == 10.1
    assert round(fam.decode_step_bytes(m, 0) / 819e9 * 1e3, 1) == 12.4
    # the pool as the engine makes it
    e = config["engine"]
    assert round((e["num_blocks"] - 1) * e["block_size"] * 10240 / 1e9,
                 2) == 1.97


def test_the_family_supplies_what_a_served_family_must():
    fam = families.load("longcat_flash")
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
    cfg = fam.config(dict(fam.TOY_MODEL, routed_scaling_factor=6))
    assert type(cfg).__name__ == "LongcatConfig" and cfg.num_held == 8


def _spans_context(rows):
    """A reader's context over made-up spans: ``rows`` of (k, active,
    live_tokens, pairs, hit, zero), one decode window each."""
    cfg = _load(CELLS, "configs", CONFIG + ".json")
    events, t = [], 0
    for k, active, live, pairs, hit, zero in rows:
        events.append(("engine.dispatch_window", t, 10,
                       {"k": k, "active": active, "live_tokens": live}))
        events.append(("engine.fetch_window", t + 10, 10,
                       {"k": k, "active": active, "moe_pairs_held": pairs,
                        "moe_experts_hit": hit, "moe_zero_picks": zero}))
        t += 100
    kernel = ('%closed_call.7 = bf16[128,64,512]{2,1,0:T(8,128)(2,1)} '
              'custom-call(%a, %b), custom_call_target="tpu_custom_call"')
    other = ('%ragged.3 = bf16[256,2048]{1,0} custom-call(%a), '
             'custom_call_target="tpu_custom_call"')
    trace = {"device": {0: {
        "XLA Ops": [(kernel, 0, 200_000), (kernel, 300_000, 200_000),
                    (other, 600_000, 999_000)],
        "XLA Modules": [("jit__unknown(1)", 0, 25_000_000)] * 3
        + [("jit__unknown(2)", 0, 90_000_000)]}}, "host": {}}
    return {"trace": trace, "spans": {"engine#1": events},
            "model": cfg["model"], "engine": cfg["engine"],
            "family": families.load("longcat_flash"),
            "peaks": flops.peaks("TPU v5 lite"), "run": {}}


def test_expert_readers_on_made_up_spans():
    # two windows of 16 steps: 80 slots then 100, 4 layers x 16 experts
    ctx = _spans_context([(16, 80, 80_000, 16 * 4 * 20, 16 * 4 * 12, 20_000),
                          (16, 100, 100_000, 16 * 4 * 26, 16 * 4 * 14,
                           26_000)])
    read = lambda name: cells_run.reader("layer_metrics", name)(ctx)  # noqa
    assert read("expert_tokens_per_step.steady") == pytest.approx(23.0)
    assert read("expert_hit_pct.steady") == pytest.approx(100 * 13 / 16)
    assert read("zero_expert_pick_pct.steady") == pytest.approx(
        100 * 46_000 / ((80 + 100) * 16 * 12 * 4))
    live = (80_000 + 80 * 8.5 + 100_000 + 100 * 8.5) / 2
    fam, m = ctx["family"], ctx["model"]
    want = 100 * fam.decode_step_bytes(m, live, 13 / 16) / 819e9 / 0.025
    assert read("decode_step_roofline.steady") == pytest.approx(want)
    assert 40 < want < 60
    # the latent arm: the Mosaic call whose result is [slots, heads, kr]
    want = 100 * (live * 576 * 2 / 819e9) / 200e-6
    assert read("latent_attention_roofline.steady") == pytest.approx(want)
    assert 50 < want < 70


def test_readers_find_nothing_where_the_program_writes_nothing():
    """A program without the counters (another model, a commit before
    them) or a run without a trace: every new reader returns None."""
    ctx = _spans_context([(16, 80, 80_000, 1, 1, 1)])
    for e in ctx["spans"]["engine#1"]:
        for key in [k for k in e[3] if k.startswith("moe_")]:
            del e[3][key]
    for name in ("expert_tokens_per_step.steady", "expert_hit_pct.steady",
                 "zero_expert_pick_pct.steady",
                 "decode_step_roofline.steady"):
        assert cells_run.reader("layer_metrics", name)(ctx) is None, name
    ctx = dict(_spans_context([(16, 80, 80_000, 1, 1, 1)]),
               family=families.load("dense"))
    assert cells_run.reader(
        "layer_metrics", "latent_attention_roofline.steady")(ctx) is None
    ctx = dict(_spans_context([]), trace=None)
    for name in ("expert_hit_pct.steady", "decode_step_roofline.steady",
                 "latent_attention_roofline.steady"):
        assert cells_run.reader("layer_metrics", name)(ctx) is None, name


def test_the_traffic_is_chat_steadys_prompts_with_long_answers():
    from cells import loadgen

    mine = _load(CELLS, "traffic", "chat-long-answers.json")
    theirs = _load(CELLS, "traffic", "chat-steady.json")
    assert {k: mine["prompt_tokens"][k] for k in (
        "dist", "median", "sigma", "min", "max")} == {
            k: theirs["prompt_tokens"][k] for k in (
                "dist", "median", "sigma", "min", "max")}
    assert mine["warmup"]["prompt_lengths"] == \
        theirs["warmup"]["prompt_lengths"]
    assert mine["output_tokens"] == {"dist": "lognormal", "median": 512,
                                     "sigma": 0.7, "min": 64, "max": 1536}
    assert (mine["loop"], mine["stream"]) == ("open", True)
    reqs = loadgen.make_requests(mine, 2 ** 31 + 9, 16384, 80.0)
    assert all(64 <= len(r["prompt"]) <= 2040
               and 64 <= r["max_tokens"] <= 1536 for r in reqs)
    assert all(0 <= t < 16384 for r in reqs[:5] for t in r["prompt"])
    cfg = _load(CELLS, "configs", CONFIG + ".json")
    assert 2040 + 1536 < cfg["engine"]["max_len"] - 1


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
