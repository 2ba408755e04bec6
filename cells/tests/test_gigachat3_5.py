"""The benchmark's own tests of family ``gigachat3_5`` and its cell.  CPU
only:

    JAX_PLATFORMS=cpu python -m pytest cells/tests/test_gigachat3_5.py -q

A file of its own because the family came by files alone (``cells/README.md``,
"A model family").  The reference against the program at tiny size, the
chunked scan against the recurrence, the shares that add up and the control
are in the repo's ``tests/test_gigachat3_5.py`` (the same reference file; the
two share a basename, so run the two directories apart).
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

CONFIG = "gigachat3.5-432b-a28b-ep16-serve"
CELL = "serve-gigachat35-long-answers"
FAMILY = "gigachat3_5"
US = 1000

# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# GigaChat3.5-432B-A28B): what the source publishes, under the source's keys
PUBLISHED = dict(
    vocab_size=128256, max_position_embeddings=262144, hidden_size=7168,
    intermediate_size=18432, moe_intermediate_size=2048,
    num_hidden_layers=40, nextn_is_sparse=False, num_attention_heads=64,
    n_shared_experts=1, n_routed_experts=256, routed_scaling_factor=2.5,
    kv_lora_rank=512, q_lora_rank=1536, qk_rope_head_dim=64, v_head_dim=128,
    qk_nope_head_dim=128, qk_head_dim=192, n_group=1, topk_group=1,
    num_experts_per_tok=8, first_k_dense_replace=3, norm_topk_prob=True,
    rope_interleave=True, num_key_value_heads=64, hidden_act="silu",
    rms_norm_eps=1e-06, rope_theta=100000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 32768, "type": "yarn"},
    attention_bias=False, norm_type="ZeroCenteredGatedNorm",
    layernorm_type="pre_post", layernorm_gating_weight=2,
    gated_attention=True, use_shared_expert_sigmoid=False,
    use_mla_scaling_factor=True,
    linear_attention_type="GigaChat35GatedDeltaNet",
    full_attention_layers=[3, 7, 11, 15, 19, 23, 27, 31, 35, 39],
    linear_key_head_dim=128, linear_value_head_dim=128,
    linear_conv_kernel_dim=4, linear_num_key_heads=32,
    linear_num_value_heads=64,
    linear_gating_type="gated_rmsnorm_sigmoid_zero_centered",
    linear_sigmoid_gate_scale=2, linear_attn_o_norm_eps=1e-06,
    swiglu_limit=10, tie_word_embeddings=False, num_nextn_predict_layers=2,
    model_type="gigachat3_5", tf_legacy_loss=False)
REDUCED = {"num_hidden_layers", "first_k_dense_replace",
           "full_attention_layers", "n_routed_experts", "vocab_size",
           "max_position_embeddings"}
# the six keys whose text is in the modelling file, each with its reading
ASSUMED = {"layernorm_type", "norm_type", "gated_attention",
           "linear_gating_type", "swiglu_limit", "use_mla_scaling_factor"}


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _load(CELLS, "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def fam():
    return families.load(FAMILY)


def test_every_published_value_is_held_or_listed_as_reduced_or_assumed(
        config, fam):
    bench = _load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"]
    assert entry["file"] == f"cells/configs/{CONFIG}.json"
    assert set(entry["reduced"]) == set(config["reduced"]) == REDUCED
    for key, value in PUBLISHED.items():
        if key == "full_attention_layers":
            assert config[key] == [4]
        elif key in REDUCED:
            assert config[key] < value, key
        else:
            assert config[key] == value, key
    assert ASSUMED < set(config["assumed"])
    # the reference's docstring lists the same six readings
    doc = fam.reference().__doc__
    for key in ASSUMED:
        assert key in doc, key
    # a cut is of a key the family maps, and never of one of its widths
    assert REDUCED <= set(fam.SOURCE_KEYS) - fam.WIDTHS
    assert fam.WIDTHS <= set(fam.SOURCE_KEYS)
    assert not any(k.endswith(("_dim", "_rank", "intermediate_size"))
                   or k in ("hidden_size", "num_experts_per_tok")
                   for k in REDUCED)
    # the source's own keys at the top level say the same as ``model``
    for theirs, ours in fam.SOURCE_KEYS.items():
        assert config[theirs] == config["model"][ours], theirs
    # the floors: a whole period and four layers behind the leading dense
    # one, 8 routed experts, an eighth of the vocabulary
    m = config["model"]
    assert m["num_layers"] >= 4 and m["dense_layers"] == 1
    assert m["hidden_layers"] == m["num_layers"] + m["dense_layers"] == 5
    # the period as published: DeltaNet, DeltaNet, DeltaNet, latent
    kinds = ["latent" if l in m["full_attention_layers"] else "delta"
             for l in range(1, 5)]
    assert kinds == ["delta", "delta", "delta", "latent"]
    assert m["held_experts"] >= 8
    assert m["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    assert m["num_experts"] == PUBLISHED["n_routed_experts"]
    assert 0 <= m["first_expert"] <= m["num_experts"] - m["held_experts"]
    assert "16 v5e chips that share each layer" in config["stands_for"]
    assert "vocabulary 8 ways" in config["stands_for"]
    assert "NOT served" in config["stands_for"]  # the MTP modules
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (
        CONFIG, "assistant-reasoning-answers")
    assert config["engine"]["max_len"] == m["max_seq_len"] == 4112
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_parameters_and_bytes_by_hand(config, fam):
    m = config["model"]
    gdn = (7168 * 4096 * 2 + 7168 * 8192 * 2 + 7168 * 128 + 16384 * 4
           + 8192 * 7168)
    assert round(gdn / 1e6, 1) == 235.9
    assert fam.delta_net_params(m) == gdn + 64 + 64 + 128
    latent = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 256
              + 64 * 128 * 7168)
    assert fam.attention_params(m) == latent and round(latent / 1e6, 1) \
        == 101.1
    assert fam.latent_block_params(m) == latent + 7168 * 8192 + 1536 + 512
    assert round((latent + 7168 * 8192) / 1e6, 1) == 159.8
    assert fam.expert_params(m) == 3 * 7168 * 2048 == 44_040_192
    assert fam.derived_pair_params(m) == 512 * 64 * 256
    per_layer = [(fam.mixer_params(m, l) + fam.ffn_params(m, l) + 4 * 7168)
                 / 1e6 for l in range(5)]
    assert [round(x, 1) for x in per_layer] == [632.3, 986.4, 986.4, 986.4,
                                               910.4]
    total = fam.num_params(m)
    assert round(total / 1e6, 1) == 4731.7
    assert round(fam.weight_bytes(m) / 1e9, 2) == 9.46
    assert fam.weight_bytes(m) == 2 * total + 2 * fam.float32_params(m)
    # a record: four DeltaNet layers' float32 state and convolution tail
    assert fam.delta_layers(m) == 4 and fam.latent_blocks(m) == 1
    assert fam.state_record_bytes(m) == 4 * (4_194_304 + 98_304)
    assert fam.state_update_bytes(m, 50) == 2 * 50 * 17_170_432
    assert round(fam.state_update_bytes(m, 50) / 1e9, 2) == 1.72
    e = config["engine"]
    records = e["num_blocks"]["state"] - 1
    assert records == e["batch_slots"] == 128
    assert round(records * fam.state_record_bytes(m) / 1e9, 1) == 2.2
    # a cached position: one latent block x (512 + 64), stored 640 wide
    assert fam.latent_bytes_per_token(m) == 576 * 2
    assert fam.kv_bytes_per_token(m) == 640 * 2
    assert fam.latent_attention_bytes(m, 1000) == 1000 * 576 * 2
    assert round(e["num_blocks"]["latent"] * e["block_size"] * 1280 / 1e9,
                 2) == 0.34
    # the scan's own work a position: 7 x 128 x 128 a value head and layer
    ops, moved = fam.delta_scan_work(m, 1)
    assert ops == 4 * 64 * 7 * 128 * 128
    assert moved == 4 * ((16384 + 8192) * 2 + 2 * 64 * 4)
    # a step: weights outside embedding and experts, held experts by the
    # share hit, latent rows, and (where the reader hands them) records
    experts = 4 * 16 * 44_040_192 * 2
    embed = 16032 * 7168 * 2
    assert fam.decode_step_bytes(m, 1000, 0.5) == (
        fam.weight_bytes(m) - embed - experts + 0.5 * experts + 1000 * 1152)
    assert fam.decode_step_bytes(m, 1000, 0.5, 50) == (
        fam.decode_step_bytes(m, 1000, 0.5) + fam.state_update_bytes(m, 50))
    assert round(fam.decode_step_bytes(m, 50 * 1500, 0.8, 50) / 1e9, 1) \
        == 9.9


def test_the_family_supplies_what_a_served_family_must(fam):
    for name in ("config", "init", "apply", "reference", "serve_programs",
                 "num_params", "weight_bytes", "kv_bytes_per_token",
                 "latent_attention_bytes", "decode_step_bytes",
                 "state_update_bytes", "delta_scan_work", "TOY_MODEL",
                 "SOURCE_KEYS", "WIDTHS"):
        assert hasattr(fam, name), name
    ref = fam.reference()
    assert callable(ref.logits) and callable(ref.loss)
    # the reference imports nothing of the program, and says how it
    # multiplies
    with open(ref.__file__) as f:
        text = f.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text
    assert 'default_matmul_precision("highest")' in text
    cfg = fam.config(dict(fam.TOY_MODEL))
    assert type(cfg).__name__ == "GigaChat35Config" and cfg.num_held == 8
    assert (cfg.num_layers, cfg.dense_layers, cfg.expert_layers,
            cfg.attention_blocks, cfg.delta_layers) == (4, 1, 3, 1, 3)
    assert fam.config(fam.model_of(cfg)) == cfg
    big = fam.config(_load(CELLS, "configs", CONFIG + ".json")["model"])
    assert (big.num_layers, big.expert_layers, big.rope_factor,
            big.rope_original_max_len, big.full_attention_layers) == (
                5, 4, 8, 32768, (4,))
    assert round(big.softmax_scale, 6) == 0.105304


# ------------------------------------------------------------ the readers

def _op(name, start, end, scope=None):
    op_name = None if scope is None else f"jit(<unknown>)/{scope}/mul"
    return (f"%{name} = f32[2] fusion()", start * US, (end - start) * US,
            op_name)


def _context():
    """A reader's context over made-up spans and a made-up joined trace:
    two decode windows (40 then 60 live records) and two executions of the
    decode program of 100 us (the convolution 4, the update 36, the gate 2,
    the rest attention and experts), then one prefill of 700 true tokens in
    a 1024 bucket whose scan takes 200 of its 1000 us."""
    cfg = _load(CELLS, "configs", CONFIG + ".json")
    events = [("engine.dispatch_window", 0, 5,
               {"k": 16, "active": 40, "live_tokens": 40_000,
                "live_tokens_latent": 40_000, "live_tokens_state": 40}),
              ("engine.dispatch_window", 100, 5,
               {"k": 16, "active": 60, "live_tokens": 60_000,
                "live_tokens_latent": 60_000, "live_tokens_state": 60})]
    ops, modules = [], []
    for s in (0, 100):
        modules.append(("jit__unknown(11)", s * US, 100 * US))
        ops += [_op("fusion.1", s, s + 4,
                    "engine.decode/attn.core/gdn.conv"),
                _op("custom-call.2", s + 4, s + 40,
                    "engine.decode/attn.core/gdn.update"),
                _op("fusion.3", s + 40, s + 42,
                    "engine.decode/attn.core/attn.gate"),
                _op("fusion.4", s + 42, s + 70, "engine.decode/attn.core"),
                _op("fusion.5", s + 70, s + 100, "engine.decode/experts")]
    t = 300
    step = ("engine.step", t * US, 1100 * US, {})
    admit = ("engine.admit", (t + 1) * US, 10 * US,
             {"kind": "full", "bucket": 1024, "prompt_tokens": 700,
              "prefilled_tokens": 700})
    first = ("engine.first_tokens", (t + 1050) * US, 20 * US, {})
    modules.append(("jit__unknown(12)", (t + 20) * US, 1000 * US))
    ops += [_op("fusion.6", t + 20, t + 220,
                "engine.prefill/attn.core/gdn.scan"),
            _op("fusion.7", t + 220, t + 1020, "engine.prefill/experts")]
    events = [(n, s * US if n == "engine.dispatch_window" else s,
               d * US if n == "engine.dispatch_window" else d, a)
              for n, s, d, a in events] + [step, admit, first]
    # one more device operation behind the fetch, so that the traced
    # window does not cut the step
    ops.append(_op("fusion.8", t + 1090, t + 1095, "engine.decode/head"))
    modules.append(("jit__unknown(11)", (t + 1090) * US, 5 * US))
    loaded = {"ops": ops, "modules": modules}
    tr = {"device": {0: {trace.OPS_LINE: [e[:3] for e in ops],
                         trace.MODULES_LINE: modules}}, "host": {}}
    return {"trace": tr, "spans": {"engine#1": events}, "parts": loaded,
            "model": cfg["model"], "engine": cfg["engine"],
            "family": families.load(FAMILY),
            "peaks": flops.peaks("TPU v5 lite"), "run": {}}


def _read(name, ctx):
    return cells_run.reader("layer_metrics", name)(ctx)


def test_the_three_new_readers_on_made_up_spans(fam):
    ctx = _context()
    m = ctx["model"]
    # 50 records on average; conv + update 40 us of each of 3 decode runs
    # but the third, 5 us long, has neither: 80 us over 3 executions
    ms = 0.080 / 3
    assert _read("decode_delta_ms.steady", ctx) == pytest.approx(ms)
    least = fam.state_update_bytes(m, 50) / 819e9
    assert _read("delta_state_roofline.steady", ctx) == pytest.approx(
        100 * least / (ms / 1e3))
    ops, moved = fam.delta_scan_work(m, 700)
    least = max(ops / 197e12, moved / 819e9)
    assert least == moved / 819e9  # the bytes bound it, by a little
    assert _read("delta_scan_roofline.steady", ctx) == pytest.approx(
        100 * least / 200e-6)


def test_the_new_readers_find_nothing_where_the_program_writes_nothing():
    """A program without the scopes (another model, the parent commit), a
    family without the arithmetic, or a run without a trace: every new
    reader returns None and does not raise."""
    names = ("delta_state_roofline.steady", "delta_scan_roofline.steady",
             "decode_delta_ms.steady")
    ctx = _context()
    ctx["parts"]["ops"] = [
        (n, s, d, (o or "").replace("gdn.", "ssm.") or None)
        for n, s, d, o in ctx["parts"]["ops"]]
    for name in names:
        assert _read(name, ctx) is None, name
    other = _context()
    other["family"] = families.load("deepseek_v3")
    assert _read("delta_state_roofline.steady", other) is None
    assert _read("delta_scan_roofline.steady", other) is None
    for name in names:
        assert _read(name, {"trace": None, "model": ctx["model"],
                            "family": ctx["family"], "peaks": None}) is None
    # the state type's stat missing: no share, whatever the scopes say
    bare = _context()
    for e in bare["spans"]["engine#1"]:
        e[3].pop("live_tokens_state", None)
    assert _read("delta_state_roofline.steady", bare) is None


def test_the_new_metrics_are_entries_of_the_cell_alone():
    bench = _load(ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    new = {"delta_state_roofline.steady": ("device_trace", "kernels", "%"),
           "delta_scan_roofline.steady": ("device_trace", "kernels", "%"),
           "decode_delta_ms.steady": ("program_span", "model step", "ms")}
    for name, (source, layer, unit) in new.items():
        m = entries[name]
        assert (m["source"], m["layer"], m["unit"]) == (source, layer, unit)
        assert m["moves"] == "tpot_ms_p50" and m["workloads"] == [CELL]
        assert os.path.exists(os.path.join(CELLS, "layer_metrics",
                                           name + ".py"))
    # the cell reports what GigaChat3.1's cell reports, but the groups' hit
    # share (one group here)
    old = "serve-gigachat-long-answers"
    for m in bench["end_to_end"] + bench["per_layer"]:
        if old in m.get("workloads", []):
            assert (CELL in m["workloads"]) == (
                m["name"] != "expert_group_hit_pct.steady"), m["name"]
    assert len(bench["workloads"]) == 7 and len(bench["configs"]) == 7


# ------------------------------------------------------------ the traffic

def test_the_traffic_is_reasoning_long_answers_with_longer_prompts():
    from cells import loadgen

    mine = _load(CELLS, "traffic", "assistant-reasoning-answers.json")
    theirs = _load(CELLS, "traffic", "reasoning-long-answers.json")
    same = lambda a, b, keys: {k: a[k] for k in keys} == {  # noqa: E731
        k: b[k] for k in keys}
    assert mine["output_tokens"] == theirs["output_tokens"] == {
        "dist": "lognormal", "median": 1536, "sigma": 0.6, "min": 256,
        "max": 3072}
    assert {k: mine["prompt_tokens"][k] for k in (
        "dist", "median", "sigma", "min", "max")} == {
            "dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64,
            "max": 1020}
    assert mine["warmup"]["prompt_lengths"] == [64, 128, 256, 512, 1024]
    assert same(mine["warmup"], theirs["warmup"],
                ("window_lengths", "tokens", "ramp_s"))
    assert same(mine, theirs, ("runner", "loop", "stream", "trace"))
    assert same(mine["reference"], theirs["reference"],
                ("requests", "model_programs"))
    assert mine["order"]["block"] == 8 and mine["pool_seed"] == 5200
    assert mine["arrivals"]["process"] == "poisson"
    assert 1.0 <= mine["arrivals"]["rate_rps"] <= 4.0
    assert "sweep" in mine["arrivals"]["why"]
    assert "float8" in mine["reference"]["why"]
    reqs = loadgen.make_requests(mine, 2 ** 31 + 9, 16032, 80.0)
    assert all(64 <= len(r["prompt"]) <= 1020
               and 256 <= r["max_tokens"] <= 3072 for r in reqs)
    assert all(0 <= t < 16032 for r in reqs[:5] for t in r["prompt"])
    cfg = _load(CELLS, "configs", CONFIG + ".json")
    assert 1020 + 3072 < cfg["engine"]["max_len"] - 1


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
