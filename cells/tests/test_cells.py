"""The benchmark's own tests.  CPU only:

    JAX_PLATFORMS=cpu python -m pytest cells/tests -q

They are not part of the repo's tier-1 suite (``tests/``): a PR that
defines the benchmark may not add files there.
"""

import gzip
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.dirname(HERE)
ROOT = os.path.dirname(CELLS)
sys.path.insert(0, ROOT)

from cells import families, flops, loadgen, trace  # noqa: E402
from cells import run as cells_run  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(name):
    with open(os.path.join(CELLS, "configs", name + ".json")) as f:
        return json.load(f)


# ------------------------------------------------------------ the files

def test_every_cell_resolves_to_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        assert os.path.isfile(os.path.join(ROOT, configs[cell["config"]]["file"]))
        with open(os.path.join(CELLS, "traffic", cell["traffic"] + ".json")) as f:
            runner = json.load(f)["runner"]
        assert os.path.isfile(os.path.join(CELLS, runner + "_runner.py"))
        family = _config(cell["config"])["family"]  # no default: one path
        assert os.path.isfile(os.path.join(CELLS, "families", family + ".py"))
        assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    for section, folder in (("end_to_end", "end_to_end"),
                            ("per_layer", "layer_metrics")):
        for m in bench[section]:
            stem = m["name"].rsplit(".", 1)[0]  # run.py's reader() rule
            assert any(os.path.isfile(os.path.join(CELLS, folder, n + ".py"))
                       for n in (m["name"], stem)), m
            for w in m.get("workloads", []):
                assert w in {c["name"] for c in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        # the metric it moves is reported wherever it is
        moved = next(x for x in bench["end_to_end"] if x["name"] == m["moves"])
        cells = m.get("workloads") or [w["name"] for w in bench["workloads"]]
        assert set(cells) <= set(moved.get("workloads") or cells)
    used = {w["config"] for w in bench["workloads"]}
    assert used == set(configs)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_names_units_and_keys(bench):
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[key]:
            assert NAME.match(entry["name"]), entry["name"]
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for root, _, files in os.walk(CELLS):
        if "__pycache__" in root:
            continue
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


# what each source publishes, under the source's own keys: a
# configuration of that source states these and cuts none of them
PUBLISHED = {
    "https://huggingface.co/mistralai/Mistral-7B-v0.3/blob/main/config.json":
    dict(vocab_size=32768, hidden_size=4096, num_attention_heads=32,
         num_key_value_heads=8, head_dim=128, intermediate_size=14336,
         rope_theta=1e6, sliding_window=None, tie_word_embeddings=False),
}


def test_no_width_is_cut(bench):
    for c in bench["configs"]:
        cfg = _config(c["name"])
        fam = families.load(cfg["family"])
        assert c["source"] == cfg["source"] and c["source"] in PUBLISHED
        for k, v in PUBLISHED[c["source"]].items():
            assert cfg[k] == v, (c["name"], k)
        assert set(c["reduced"]) == set(cfg["reduced"])
        # a cut is of a key the family maps, and never of one of its widths
        assert set(c["reduced"]) <= set(fam.SOURCE_KEYS) - fam.WIDTHS
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])
        assert fam.WIDTHS <= set(fam.SOURCE_KEYS)
        # the source's own keys at the top level say the same as ``model``
        for theirs, ours in fam.SOURCE_KEYS.items():
            assert cfg[theirs] == cfg["model"][ours], (c["name"], theirs)


# ------------------------------------------------------------ arithmetic

def test_parameters_and_operations_by_hand():
    # one layer: q 4096*4096, k and v 4096*1024 each, o 4096*4096,
    # three MLP matrices 4096*14336, two norms
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert layer == 218_112_000
    emb = 32768 * 4096
    l2 = _config("mistral-7b-v0.3-L2-train")["model"]
    l6 = _config("mistral-7b-v0.3-L6-train-fsdp4")["model"]
    l22 = _config("mistral-7b-v0.3-L22-serve")["model"]
    dense = families.load("dense")
    assert dense.layer_params(l2) == layer
    assert dense.num_params(l2) == 2 * emb + 2 * layer + 4096 == 704_663_552
    assert dense.num_params(l6) == 2 * emb + 6 * layer + 4096 == 1_577_111_552
    # 6 x matmul parameters x tokens + 12 * L * b * s^2 * h * hd / 2
    tok = 4 * 4096
    by_hand = 6 * (704_663_552 - emb) * tok + 6 * 2 * 4 * 4096 ** 2 * 32 * 128
    assert dense.train_flops_per_step(l2, 4, 4096) == by_hand
    assert round(by_hand / 1e12, 1) == 59.4
    assert round(dense.train_flops_per_step(l6, 16, 4096) / 1e12) == 607
    assert flops.flash_flops_per_step(l2, 4, 4096) == 6 * 2 * 4 * 4096 ** 2 * 32 * 128
    # bf16 weights of the serve configuration, and 88 KiB of cache a token
    assert dense.weight_bytes(l22) == 2 * (2 * emb + 22 * layer + 4096)
    assert dense.kv_bytes_per_token(l22) == 2 * 22 * 8 * 128 * 2 == 90112
    assert dense.decode_step_bytes(l22, 1000) == (
        dense.weight_bytes(l22) - 2 * emb + 1000 * 90112)
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")
    with pytest.raises(KeyError):
        flops.peaks("_source")


def test_percentile_and_due_time():
    assert loadgen.quantile(list(range(1, 201)), 0.95) == 190
    assert loadgen.quantile([5.0], 0.95) == 5.0
    assert loadgen.quantile([3, 1, 2], 0.5) == 2
    # due at 10.0, the sender stalled and sent at 10.4, first token 10.9:
    # the user waited 900 ms, not 500
    rec = {"due": 10.0, "sent": 10.4, "t_first": 10.9, "t_last": 12.9,
           "n_out": 21}
    assert loadgen.ttft_ms(rec) == pytest.approx(900.0)
    assert loadgen.tpot_ms(rec) == pytest.approx(100.0)
    traffic = {"loop": "open", "stream": True}
    ok = dict(rec, ok=True, n_prompt=7, t_done=13.0)
    failed = dict(rec, ok=False, due=11.0, sent=11.0, t_first=None)
    late = dict(ok, due=20.5)
    red = loadgen.reduce_window([ok, failed, late], traffic, 10.0, 20.0)
    assert (red["attempted"], red["failed"]) == (2, 1)
    assert red["ttft_ms"] == [pytest.approx(900.0)]
    # the whole wait, from the due time, a token: 2900 ms over 21 tokens
    assert red["norm_latency_ms"] == [pytest.approx(2900.0 / 21)]
    # closed loop: a request's tokens by the share of its time in the
    # system inside the window.  Sent 10.4, done 13.0: all 21.  Sent 10.4,
    # done 25.0: 9.6 of its 14.6 s.  Sent 5.0, done 15.0: half.  Failed: 0
    closed = {"loop": "closed", "stream": False}
    red = loadgen.reduce_window(
        [ok, dict(ok, t_done=25.0), dict(ok, sent=5.0, t_done=15.0),
         dict(ok, ok=False, t_done=12.0)], closed, 10, 20)
    assert (red["attempted"], red["failed"]) == (3, 1)
    assert red["output_tokens"] == pytest.approx(
        21 + 21 * 9.6 / 14.6 + 21 * 0.5)
    assert red["output_tokens_at_completion"] == 42


def test_every_seed_gets_the_same_work():
    with open(os.path.join(CELLS, "traffic", "chat-steady.json")) as f:
        traffic = json.load(f)
    a = loadgen.make_requests(traffic, 1, 32768, 53.0)
    b = loadgen.make_requests(traffic, 2 ** 31 + 12345, 32768, 53.0)
    again = loadgen.make_requests(traffic, 1, 32768, 53.0)
    assert a == again and a != b
    size = lambda reqs: sorted((len(r["prompt"]), r["max_tokens"])  # noqa
                               for r in reqs)
    assert size(a) == size(b)
    assert a[-1]["due"] == pytest.approx(b[-1]["due"])
    assert all(64 <= len(r["prompt"]) <= 2040 and 16 <= r["max_tokens"] <= 512
               for r in a)
    assert all(0 <= t < 32768 for r in a[:5] for t in r["prompt"])
    with pytest.raises(ValueError):  # only what a cell uses is there
        loadgen.make_requests(dict(traffic, arrivals={
            "process": "gamma", "rate_rps": 1.0}), 3, 32768, 10.0)


# ------------------------------------------------------------ the trace

def test_interval_arithmetic():
    m = trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert m == [(0, 3), (5, 8)] and trace.length(m) == 6
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    kernel = '%closed_call.4 = bf16[8] custom-call(), ' + trace.MOSAIC
    tr = {"device": {0: {trace.OPS_LINE: [
        ("%while.9 = (s32[]) while(...)", 0, 15),
        ("%fusion.1 = f32[2] fusion(...)", 0, 10),
        ("%all-gather.2 = f32[8] all-gather(...)", 10, 4),
        ("%fusion.3 = f32[2] fusion(...)", 30, 10), (kernel, 40, 5)],
        trace.MODULES_LINE: [("jit_step(1)", 0, 45)]}},
        "host": {"main": [("wait", 14, 17)]}}
    assert trace.busy_ns(tr, 0) == 30
    assert trace.exposed_collective_ns(tr, 0) == 4
    assert trace.op_time_s(tr, trace.MOSAIC) == (5e-9, 1)
    assert trace.module_durations_s(tr, "step") == [45e-9]
    own = {trace.short(n): s for n, _, _, s in
           trace.self_times(trace.ops(tr, 0))}
    assert own["while.9"] == 1 and own["fusion.1"] == 10
    assert trace.top_device_ops(tr)[0] == ["fusion", 20e-9]
    assert ["tpu_custom_call:closed_call.4", 5e-9] in trace.top_device_ops(tr)


def test_reducer_on_the_recorded_miniature():
    """A cut of a real v5e trace of ``train-1chip-s4096`` (PR 23)."""
    path = os.path.join(HERE, "mini_train.json.gz")
    tr = trace.load(path)
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    assert list(tr["device"]) == [0]
    n_ops = len(raw["device"]["0"][trace.OPS_LINE])
    assert n_ops > 100
    t0, t1 = trace.span(tr)
    busy = trace.busy_ns(tr, 0)
    assert 0.5 * (t1 - t0) < busy <= t1 - t0
    seconds, count = trace.op_time_s(tr, trace.MOSAIC)
    # 3 kernels (forward, dQ, dK/dV) x 2 layers a step, ~21 ms a layer
    assert count >= 6 and 0.015 < seconds / (count / 3) < 0.03
    assert trace.exposed_collective_ns(tr, 0) == 0  # one chip: none
    top = trace.top_device_ops(tr)
    assert len(top) <= 10 and top[0][1] >= top[-1][1] > 0
    assert trace.module_durations_s(tr, "train_step")


def test_decode_program_on_the_recorded_serve_miniature():
    """A cut of a real v5e trace of ``serve-batch-saturated`` (PR 23): the
    decode program is the ``jit__unknown`` module run most often."""
    tr = trace.load(os.path.join(HERE, "mini_serve.json.gz"))
    runs = trace.decode_program_s(tr)
    assert len(runs) >= 3 and all(0.03 < r < 0.08 for r in runs)
    names = {n for n, _, _ in trace.modules(tr, 0)}
    assert sum(n.startswith("jit__unknown") for n in names) >= 2
    top = dict(map(tuple, trace.top_device_ops(tr)))
    assert "slice_bitcast_fusion" in top  # the per-layer pool slices
    t0, t1 = trace.span(tr)
    assert trace.busy_ns(tr, 0) <= t1 - t0


# values the readers gave on the recorded miniatures before a family
# stood between them and the arithmetic (PR 25's tree): bit for bit
ON_THE_MINIATURES = {
    "step_ms.train": 565.75,
    "mfu_pct.train": 53.27427198146659,
    "flash_attention_roofline.train": 99.94744048394286,
    "device_idle_pct.train": 0.25138786908188715,
    "exposed_collective_pct.train": 0.0,
    "decode_step_ms.steady": 48.970622000000006,
    "paged_decode_roofline.saturated": 29.01935669211527,
    "device_idle_pct.steady": 3.724824552163075,
    "kv_pool_fill_pct.steady": 48.046875,
    "queue_depth.steady": 2.0,
    "slot_occupancy_pct.saturated": 77.5,
}


def _reader_context(kind):
    mini, config, traffic, run = {
        "train": ("mini_train.json.gz", "mistral-7b-v0.3-L2-train",
                  "train-b4-s4096",
                  {"kind": "train", "step_s": [0.5657, 0.5658, 0.56575]}),
        "serve": ("mini_serve.json.gz", "mistral-7b-v0.3-L22-serve",
                  "batch-saturated",
                  {"kind": "serve", "polls": [
                      {"blocks_used": 1200, "blocks_total": 2560,
                       "queued": 1, "slot_occupancy": 0.75},
                      {"blocks_used": 1260, "blocks_total": 2560,
                       "queued": 3, "slot_occupancy": 0.8}]}),
    }[kind]
    tr = trace.load(os.path.join(HERE, mini))
    first, last = trace.span(tr)
    cfg = _config(config)
    with open(os.path.join(CELLS, "traffic", traffic + ".json")) as f:
        traffic = json.load(f)
    return {"run": run, "family": families.load(cfg["family"]),
            "model": cfg["model"], "engine": cfg.get("engine", {}),
            "traffic": traffic, "config": cfg, "chips": 1, "trace": tr,
            "trace_window_s": (last - first) / 1e9,
            "peaks": flops.peaks("TPU v5 lite")}


@pytest.mark.parametrize("name", sorted(ON_THE_MINIATURES))
def test_readers_on_the_recorded_miniatures(name):
    kind = "train" if name.endswith(".train") else "serve"
    value = cells_run.reader("layer_metrics", name)(_reader_context(kind))
    assert value == ON_THE_MINIATURES[name]


# ------------------------------------------------------------ reference

def test_reference_agrees_with_the_program_at_tiny_size():
    import jax
    import jax.numpy as jnp

    dense = families.load("dense")
    reference = dense.reference()
    model = dict(vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
                 num_kv_heads=2, head_dim=16, mlp_dim=128, max_seq_len=64,
                 rope_theta=1e6)
    cfg = dense.config(dict(model, dtype="float32", param_dtype="float32",
                            attention_impl="ref"))
    params = dense.init(jax.random.PRNGKey(5), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 49), 0, 256)
    want = dense.apply(params, tokens[:, :-1], cfg, None)
    err = 0.0
    for i in range(2):
        got = reference.logits(params, tokens[i, :-1], model)
        err = max(err, float(jnp.max(jnp.abs(got - want[i]))))
    assert err < 1e-5
    # and it notices a wrong model: the rotary base changed
    other = reference.logits(params, tokens[0, :-1],
                             dict(model, rope_theta=1e4))
    assert float(jnp.max(jnp.abs(other - want[0]))) > 100 * err
    loss = reference.loss(params, tokens[0], model)
    logp = jax.nn.log_softmax(want[0], axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, tokens[0, 1:, None], axis=-1))
    assert float(abs(loss - nll)) < 1e-4
    # its gradient with respect to the embedded tokens is the program's
    # gradient of the rows of the embedding table those tokens select
    from ray_tpu.models.llama import llama_loss
    one = tokens[:1]
    g_table = jax.grad(lambda p: llama_loss(p, {"tokens": one}, cfg))(
        params)["embed"]
    g_ref = reference.embedding_gradient(params, one[0], model)
    ids = [int(t) for t in one[0, :-1]]
    once = [j for j, t in enumerate(ids) if ids.count(t) == 1]
    assert len(once) > 10
    once = jnp.array(once)
    got, want_g = g_ref[once], g_table[jnp.array(ids)[once]]

    def rel(a, b):
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    assert rel(got, want_g) < 1e-5
    # and it notices a wrong model here too (at random weights attention
    # is nearly uniform, so the rotary base moves the gradient by little)
    off = reference.embedding_gradient(params, one[0],
                                       dict(model, rope_theta=1e4))[once]
    assert rel(off, want_g) > 100 * rel(got, want_g)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_reads_not_correct(seed):
    """The control of ``correct`` (``cells/tools/control.py`` runs it on
    the chip at a cell's own size): the reference with every weight
    product's operands rounded to an 8-bit float, in the program's place.
    At toy size, with the program in bfloat16 as the configurations state
    (seeds 1-3): logit error 0.0033-0.0039 sound, 0.032-0.035 control;
    1 - cosine of the gradient 1.2e-5 to 1.4e-5 sound, 6.1e-4 to 7.5e-4
    control.  The limits here sit between: the control must fail them and
    the program pass."""
    import jax
    import jax.numpy as jnp

    from cells import train_worker
    from ray_tpu.models.llama import llama_loss

    dense = families.load("dense")
    ref = dense.reference()
    model = dict(dense.TOY_MODEL, rope_theta=1e6)
    control = dict(model, control_dtype="float8_e4m3fn")
    cfg = dense.config(dict(model, dtype="bfloat16", attention_impl="ref"))
    params = dense.init(jax.random.PRNGKey(seed), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 10), (1, 65), 0, 256)
    want = ref.logits(params, tokens[0, :-1], model)

    def logit_err(got):
        return float(jnp.max(jnp.abs(got - want))
                     / jnp.maximum(1.0, jnp.max(jnp.abs(want))))
    ids, once = train_worker.seen_once(tokens)
    g_ref = ref.embedding_gradient(params, tokens[0], model)[once]
    g_sys = jax.grad(lambda p: llama_loss(p, {"tokens": tokens}, cfg))(
        params)["embed"][ids[once]].astype(jnp.float32)
    g_control = ref.embedding_gradient(params, tokens[0], control)[once]
    sound = (logit_err(dense.apply(params, tokens[:, :-1], cfg, None)[0]),
             train_worker.one_minus_cos(g_sys, g_ref))
    wrong = (logit_err(ref.logits(params, tokens[0, :-1], control)),
             train_worker.one_minus_cos(g_control, g_ref))
    assert sound[0] < 0.012 < wrong[0] and sound[1] < 1e-4 < wrong[1]
    assert wrong[0] > 3 * sound[0] and wrong[1] > 3 * sound[1]


# ------------------------------------------------------------ rehearsals

def _a_copy_with(tmp_path, entries):
    """A copy of the benchmark under ``tmp_path`` (``ray_tpu`` linked
    beside it) whose ``BENCHMARK.json`` gained the entries of one cell:
    its configuration, the cell, its name in the ``workloads`` of the
    metrics named, and any new per-layer metrics.  Returns its root."""
    shutil.copytree(CELLS, tmp_path / "cells", ignore=shutil.ignore_patterns(
        "__pycache__"))
    os.symlink(os.path.join(ROOT, "ray_tpu"), tmp_path / "ray_tpu")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(entries["config"])
    bench["workloads"].append(entries["workload"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in entries["metrics"]:
            m["workloads"].append(entries["workload"]["name"])
    bench["per_layer"] += entries.get("per_layer", [])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def _leftovers():
    out = subprocess.run(["pgrep", "-f", "ray_tpu._private"],
                         capture_output=True, text=True).stdout.split()
    return [p for p in out if p != str(os.getpid())]


@pytest.mark.parametrize("workload,trace_flag", [
    ("train-1chip-s4096", 0), ("train-fsdp4-s4096", 1),
    ("serve-chat-steady", 0), ("serve-chat-steady", 1),
    ("serve-batch-saturated", 1)])
def test_rehearsal_ends_in_a_well_formed_line(bench, tmp_path, workload,
                                              trace_flag):
    """Every cell of ``BENCHMARK.json``; a parked cell whose entries are
    kept in ``cells/parked/<cell>.json`` runs in a copy of the benchmark
    that gained them and nothing else, which is all the PR that takes the
    cell in has to add."""
    root = ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    if workload not in {w["name"] for w in bench["workloads"]}:
        parked = os.path.join(CELLS, "parked", workload + ".json")
        if not os.path.exists(parked):
            pytest.skip(f"{workload} is not a cell of BENCHMARK.json")
        with open(parked) as f:
            root = _a_copy_with(tmp_path, json.load(f))
        env.pop("PYTHONPATH", None)  # the copy finds its own files
    before = set(_leftovers())
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "cells", "run.py"), "--workload",
         workload, "--seed", str(2 ** 31 + 5), "--seconds", "3", "--trace",
         str(trace_flag), "--rehearse"],
        capture_output=True, text=True, env=env, cwd=root, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["rehearsal"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert last["metrics"]
    assert all(k.startswith("rehearsal.") for k in last["metrics"])
    for m in last["metrics"].values():
        assert isinstance(m["value"], float) and UNIT.match(m["unit"])
    assert set(_leftovers()) <= before


def _sha(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _files(root):
    return {os.path.relpath(os.path.join(d, f), root): _sha(os.path.join(d, f))
            for d, _, fs in os.walk(root) if "__pycache__" not in d
            for f in fs}


@pytest.mark.parametrize("reference_is,correct", [
    ("sound", True), ("top-k off by one", False)])
def test_a_second_family_is_added_by_files_alone(tmp_path, reference_is,
                                                 correct):
    """What a ``model_config`` PR does: a copy of the benchmark gains a
    family file (over the program's ``ray_tpu.models.moe``), its plain
    reference with a router and experts in it, a configuration naming the
    family, a traffic mix and the entries of one train cell.  No file that
    was there is edited, and the added cell is held to the added
    reference: with the reference keeping one expert too many, the same
    run reads not correct.  Readings at toy shapes, float32 on both sides
    (seeds 1, 2, 3, 2**31 + 77): sound, logit error 1.8e-7 to 2.1e-7,
    loss difference at most 9.6e-7, 1 - cosine at most 0; off by one,
    0.052 to 0.060 of the logits, 1.8e-4 to 9.4e-4 of the loss, 4.8e-3
    to 5.9e-3 of the direction; the fixture's limits are 1e-5 each."""
    fixture = os.path.join(HERE, "files_only")
    with open(os.path.join(fixture, "entries.json")) as f:
        entries = json.load(f)
    _a_copy_with(tmp_path, entries)
    before = _files(tmp_path / "cells")
    for src, dst in entries["files"].items():
        shutil.copy(os.path.join(fixture, src), tmp_path / dst)
    if not correct:
        path = tmp_path / "cells" / "families" / "toy_moe_reference.py"
        sound = 'kept = model["experts_per_token"]\n'
        assert sound in path.read_text()
        path.write_text(path.read_text().replace(
            sound, 'kept = model["experts_per_token"] + 1\n'))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)  # the copy finds its own files
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "cells" / "run.py"), "--workload",
         entries["workload"]["name"], "--seed", "3", "--seconds", "2",
         "--trace", "0", "--rehearse"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"rehearsal.train_tokens_per_s",
                                    "rehearsal.setup_s"}
    assert last["correct"] is correct, proc.stdout[-3000:]
    if not correct:
        assert "FAIL logits agree with the reference" in proc.stderr
    after = _files(tmp_path / "cells")
    assert {k: after[k] for k in before} == before  # nothing edited
    assert set(after) - set(before) == {
        os.path.relpath(dst, "cells") for dst in entries["files"].values()}


def test_without_a_tpu_there_is_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(CELLS, "run.py"), "--workload",
         "train-1chip-s4096", "--seed", "1", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=300)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in
                   proc.stdout.splitlines())
