"""GigaChat3.5's family (GigaChat3.5-432B-A28B) as ``LLMEngine`` serves it,
against the plain reference the benchmark keeps for it
(``cells/families/gigachat3_5_reference.py``: float32 ``jax.numpy``, the
recurrence position by position, importing nothing of the program).

Seeded float32 at toy widths on the CPU:

(a) ``models/gigachat3_5.py``'s forward against the reference, for the whole
    range of experts and for a held range, with norms, gates and a selection
    bias that matter (all are neutral at initialisation);
(b) ``ops/delta.py``: the chunked scan against the sequential recurrence at
    lengths that are no multiple of a chunk and shorter than the bucket; the
    decode update as one operation (the convolution's position, the heads'
    vectors, the rule), gathered and as its Pallas kernel (interpreter),
    against the composition it replaced, on records and tails, idle slots
    and unheld records untouched;
(c) prefill then decode through ``LLMEngine`` (the latent pool and the
    records) against the reference's full forward, on logits; a record's
    life through preemption;
(d) the shares of all chips add up to the uncut expert layer, the shared
    expert counted once;
(e) ``models/mla.py`` with the gate off is bit for bit what it was: LongCat's
    and DeepSeek-V3's programs lower to the same text with and without the
    gate's code in the way;
(f) the control of the cell's ``correct`` reads not correct;
(g) what the model does not supply raises by name; presets resolve.

Tolerances: float32 on both sides, different orders of summation (a chunked
scan against a loop over positions, an absorbed product against an
up-projected one): logits of magnitude ~1 agree to 5e-5 (2e-5 in the
families whose mixers are attention alone; the delta rule's state adds a
sum over the whole prompt).  A returned token's gap under the reference's
largest logit is 0 unless two logits tie to that.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cells.families import gigachat3_5 as family
from cells.families import gigachat3_5_reference as reference
from ray_tpu.llm import SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import gigachat3_5 as gc
from ray_tpu.models import mla
from ray_tpu.models.gigachat3_5 import (GigaChat35Config, gigachat3_5_apply,
                                        gigachat3_5_init)
from ray_tpu.models.served import preset, served_model
from ray_tpu.ops import delta
from ray_tpu.ops.ssm import causal_conv1d_step

TOL = 5e-5
_model = family.model_of  # the configuration as the reference takes it
GREEDY = lambda **kw: SamplingParams(  # noqa: E731
    temperature=0.0, stop_token_id=None, **kw)


def _params(cfg, seed=3):
    """Seeded weights whose neutral leaves matter: the zero-centred norms'
    and the output norm's ``w`` (zeros at init), the selection bias."""
    params = gigachat3_5_init(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    out = []
    for (path, leaf), k in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if "norms" in name or "o_norm" in name or "final_norm" in name:
            leaf = 0.5 * jax.random.normal(k, leaf.shape, leaf.dtype)
        elif "bias" in name:
            leaf = 0.05 * jax.random.normal(k, leaf.shape, leaf.dtype)
        out.append(leaf)
    return jax.tree_util.tree_unflatten(tree, out)


class _Ids:
    """Token ids in, token ids out."""
    eos_id = None
    vocab_size = 256

    def encode(self, text):
        return [1]

    def decode(self, ids):
        return ""


def _engine(cfg=None, **kw):
    cfg = cfg or GigaChat35Config.tiny(first_expert=4, held_experts=8)
    kw = {**dict(tokenizer=_Ids(), batch_slots=4, max_len=96, block_size=8,
                 decode_window=4), **kw}
    return cfg, LLMEngine(cfg, _params(cfg), **kw)


_PADDED = 96  # one reference program for every length: causal, so a
# position's logits do not see the padding behind it


@functools.lru_cache(maxsize=None)
def _reference(cfg):
    model = _model(cfg)
    return jax.jit(lambda params, tokens: reference.logits(params, tokens,
                                                           model))


def _logits(params, cfg, seq):
    """The reference's logits at the ``len(seq)`` positions of ``seq``."""
    padded = jnp.zeros((_PADDED,), jnp.int32).at[:len(seq)].set(
        jnp.asarray(seq, jnp.int32))
    return _reference(cfg)(params, padded)[:len(seq)]


def _gap(eng, cfg, prompt, answer):
    """The largest gap of a returned token under the reference's largest
    logit, over the answer."""
    seq = list(prompt) + list(answer)
    rows = _logits(eng.params, cfg, seq[:-1])[len(prompt) - 1:]
    chosen = jnp.take_along_axis(
        rows, jnp.asarray(seq[len(prompt):])[:, None], -1)[:, 0]
    return float(jnp.max(jnp.max(rows, -1) - chosen))


# ------------------------------------------------------ (a) the forward

@pytest.mark.parametrize("held", [None, (4, 8)],
                         ids=["all-experts", "held-4..11"])
def test_forward_matches_the_plain_reference(held):
    cfg = GigaChat35Config.tiny() if held is None else GigaChat35Config.tiny(
        first_expert=held[0], held_experts=held[1])
    params = _params(cfg)
    # mixer kind and feed-forward kind vary independently
    assert [sorted(lp) for lp in params["layers"]] == [
        ["ffn", "gdn", "norms"], ["gdn", "moe", "norms"],
        ["gdn", "moe", "norms"], ["attn", "moe", "norms"]]
    assert "w_g" in params["layers"][3]["attn"]
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, 256)
    got, stats = jax.jit(functools.partial(
        gigachat3_5_apply, cfg=cfg, return_stats=True))(params, tokens)
    assert float(jnp.max(jnp.abs(got))) > 0.3
    model = _model(cfg)

    def ref(params, model):
        return jax.jit(lambda p: reference.logits(p, tokens[0], model))(
            params)

    want = ref(params, model)
    assert float(jnp.max(jnp.abs(got[0] - want))) < TOL
    assert float(jnp.max(jnp.abs(got[1] - jax.jit(
        lambda p: reference.logits(p, tokens[1], model))(params)))) < TOL
    assert int(stats[0]) > 0 and int(stats[2]) == 0
    # and the reference notices a wrong model: no clamp, no gate on the
    # latent block, the plain softmax scale, a norm without its gate
    for wrong in (dict(model, swiglu_limit=0.05),
                  dict(model, norm_gate_scale=1.0),
                  dict(model, linear_gate_scale=1.0),
                  dict(model, rope_scaling=dict(model["rope_scaling"],
                                                mscale_all_dim=0))):
        assert float(jnp.max(jnp.abs(ref(params, wrong) - want))) > 10 * TOL
    ungated = jax.tree.map(lambda a: a, params)
    ungated["layers"][3]["attn"]["w_g"] = jnp.zeros_like(
        params["layers"][3]["attn"]["w_g"])
    assert float(jnp.max(jnp.abs(ref(ungated, model) - want))) > 10 * TOL


# ------------------------------------------------ (b) the delta rule

def _delta_inputs(b, s, Hv, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = delta.l2_normalise(jax.random.normal(ks[0], (b, s, Hv, d))) / d ** .5
    k = delta.l2_normalise(jax.random.normal(ks[1], (b, s, Hv, d)))
    v = jax.random.normal(ks[2], (b, s, Hv, d))
    g = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (b, s, Hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, Hv)))
    state = jax.random.normal(ks[5], (b, Hv, d, d))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("s,length,chunk", [
    (150, None, 32), (150, 97, 32), (128, 64, 64), (64, 1, 64),
    (40, 33, 64), (96, 96, 16)])
def test_the_chunked_scan_is_the_sequential_recurrence(s, length, chunk):
    """Lengths that are no multiple of a chunk, shorter than the bucket,
    one position, and a sequence shorter than one chunk: the outputs at
    the true positions and the state that is left."""
    q, k, v, g, beta, state = _delta_inputs(2, s, 4, 16)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = delta.sequential_delta_scan(q, k, v, g, beta, state,
                                                     length)
        got_o, got_s = delta.chunked_delta_scan(q, k, v, g, beta, state,
                                                length, chunk=chunk)
    n = s if length is None else length
    assert float(jnp.max(jnp.abs(got_o[:, :n] - want_o[:, :n]))) < 2e-6
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 2e-6
    if length is not None:  # the bucket's padding does not move the state
        _, cut = delta.chunked_delta_scan(
            q[:, :n], k[:, :n], v[:, :n], g[:, :n], beta[:, :n], state,
            chunk=chunk)
        assert float(jnp.max(jnp.abs(got_s - cut))) < 2e-6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("Hk", [2, 4], ids=["half-the-heads", "every-head"])
@pytest.mark.parametrize("path", ["gather", "kernel"])
def test_the_decode_update_moves_the_live_records_alone(path, Hk, dtype):
    """One position of a layer as ONE operation on the slots' own records
    and tails, through the gathered path and through the Pallas kernel (the
    interpreter), against the composition it replaced on the gathered live
    records: the convolution's position (``causal_conv1d_step``), the
    heads' vectors (``delta_heads_of``: a key head serving ``Hv / Hk`` value
    heads) and the recurrence as it is written (``delta_step``).  The tails
    to the bit (the model's dtype, float32 or bfloat16); layer 0 and every
    record AND TAIL no slot holds untouched; an idle slot (record 0) moving
    nothing."""
    b, L, R, K, Hv, d = 5, 2, 6, 4, 4, 16
    C = (2 * Hk + Hv) * d
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    qkv = jax.random.normal(ks[0], (b, C)).astype(dtype)
    alpha = jnp.exp(-0.3 * jax.nn.softplus(jax.random.normal(ks[1], (b, Hv))))
    beta = jax.nn.sigmoid(jax.random.normal(ks[2], (b, Hv)))
    conv_w = (0.5 * jax.random.normal(ks[3], (K, C))).astype(dtype)
    state = {"s": jax.random.normal(ks[4], (L, R, Hv, d, d)),
             "conv": jax.random.normal(
                 ks[5], (L, R, (K - 1) * C // d, d)).astype(dtype)}
    rec = jnp.asarray([3, 0, 1, 0, 5], jnp.int32)
    live, free = np.asarray(rec) != 0, jnp.asarray([2, 4])
    y, want_tail = causal_conv1d_step(
        qkv, conv_w, state["conv"][1, rec].reshape(b, -1))
    want_o, want_s = delta.delta_step(
        *delta.delta_heads_of(y, Hk, Hv, d), alpha, beta, state["s"][1, rec])
    got_o, got = delta.delta_update_records(
        qkv, alpha, beta, conv_w, state, 1, rec, path=path,
        **({"interpret": True} if path == "kernel" else {}))
    assert float(jnp.max(jnp.abs(got_o - want_o)[live])) < 5e-6
    assert float(jnp.max(jnp.abs(got["s"][1, rec] - want_s)[live])) < 2e-6
    assert got["conv"].dtype == dtype and jnp.array_equal(
        got["conv"][1, rec].reshape(b, -1)[live], want_tail[live])
    for leaf in ("s", "conv"):
        assert jnp.array_equal(got[leaf][0], state[leaf][0]), leaf
        assert jnp.array_equal(got[leaf][1, free], state[leaf][1, free]), leaf
    if path == "kernel":  # an idle slot's output is defined
        assert not np.asarray(got_o)[~live].any()
    assert delta.delta_update_path(state) == "gather"  # the CPU


# ----------------------------------------------------- (c) the engine

def test_engine_decodes_through_the_latent_pool_and_the_records():
    cfg, eng = _engine()
    assert eng.model is served_model(cfg) and eng.attn == "gather"
    assert set(eng.pool) == {"latent", "state"}
    assert eng.pool["latent"]["kv"].shape == (
        1, eng.num_blocks["latent"], 8, 128)
    assert eng.pool["state"]["s"].shape == (3, 5, 4, 16, 16)
    assert eng.pool["state"]["s"].dtype == jnp.float32
    # a record's tail: 3 taps of the 128 channels, a row a head's width
    assert eng.pool["state"]["conv"].shape == (3, 5, 3 * 8, 16)
    assert [(p.name, p.state, p.blocks.run) for p in eng._pools] == [
        ("latent", False, eng._pools[0].blocks.run), ("state", True, 1)]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).tolist() for n in (5, 23, 40, 9, 17)]
    outs = eng.generate(prompts, GREEDY(max_tokens=12))
    st = eng.stats()
    assert st["model"] == "gigachat3_5" and st["experts"] == "grouped"
    assert st["prefill_attention"] == {"plain": len(prompts)}
    assert st["prefix_cache"]["prefix_blocks_reused"] == 0
    assert st["pools"]["state"] == {"total": 4, "available": 4, "held": 0}
    c = st["counters"]
    assert c["decode_steps"] > 0 and c["moe_pairs_held"] > 0
    assert 0 < c["moe_experts_hit"] and c["moe_zero_picks"] == 0
    assert c["prefill_calls"] == len(prompts) and c["prefill_moe_pairs_held"]
    eng.blocks.assert_integrity()
    for prompt, out in zip(prompts, outs):
        assert len(out.token_ids) == 12
        assert _gap(eng, cfg, prompt, out.token_ids) < TOL


def test_decode_step_logits_match_the_reference_and_skip_freed_slots():
    """Prefill, then decode token by token through both pools, on logits;
    a slot that holds no request moves no record and is not counted."""
    cfg = GigaChat35Config.tiny(first_expert=4, held_experts=8)
    params = _params(cfg)
    pool = gc.init_pools(cfg, {"latent": 16, "state": 4}, 8)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 256, 19).tolist()
    S = 32
    toks = jnp.asarray([prompt + [0] * (S - len(prompt))], jnp.int32)
    pos = np.arange(S)
    dst = {"latent": jnp.asarray(np.where(pos < 19, 1 + pos // 8, 0),
                                 jnp.int32),
           "state": jnp.asarray([2], jnp.int32)}
    empty = gc.gather_prefix(pool, jnp.zeros((0,), jnp.int32), cfg)
    prefill = jax.jit(gc.prefill_suffix, static_argnames=("cfg",))
    step = jax.jit(gc.decode_step, static_argnames=("cfg",))
    logits, pool, stats = prefill(
        params, toks, jnp.int32(19), jnp.int32(0), *empty, jnp.int32(0),
        dst, jnp.asarray(pos % 8, jnp.int32), pool, cfg=cfg)
    seq, got = list(prompt), [logits[0]]
    assert int(stats[0]) > 0
    # the same prompt in another bucket leaves the same record
    toks64 = jnp.asarray([prompt + [7] * (64 - len(prompt))], jnp.int32)
    pos64 = np.arange(64)
    dst64 = {"latent": jnp.asarray(np.where(pos64 < 19, 1 + pos64 // 8, 0),
                                   jnp.int32),
             "state": jnp.asarray([3], jnp.int32)}
    _, pool, _ = prefill(
        params, toks64, jnp.int32(19), jnp.int32(0), *empty, jnp.int32(0),
        dst64, jnp.asarray(pos64 % 8, jnp.int32), pool, cfg=cfg)
    for leaf in ("s", "conv"):
        a, b = pool["state"][leaf][:, 2], pool["state"][leaf][:, 3]
        assert float(jnp.max(jnp.abs(a - b))) < 2e-6, leaf
    tables = {"latent": jnp.zeros((3, 8), jnp.int32).at[1, :4].set(
        jnp.arange(1, 5)), "state": jnp.asarray([[0], [2], [0]], jnp.int32)}
    untouched = pool["state"]["s"][:, 3]
    for _ in range(6):
        seq.append(int(jnp.argmax(got[-1])))
        cur = jnp.asarray([0, len(seq) - 1, 0], jnp.int32)
        logits, pool, stats = step(
            params, jnp.asarray([5, seq[-1], 9], jnp.int32), cur, tables,
            pool, cfg=cfg)
        got.append(logits[1])
        # one live slot: at most 3 picks a layer over 3 expert layers
        assert 0 <= int(stats[0]) <= 9
    want = _logits(params, cfg, seq)[len(prompt) - 1:]
    assert float(jnp.max(jnp.abs(jnp.stack(got) - want))) < TOL
    assert jnp.array_equal(pool["state"]["s"][:, 3], untouched)


def test_a_preempted_request_gives_its_record_back_and_resumes():
    cfg, eng = _engine()
    prompt = np.random.default_rng(4).integers(0, 256, 10).tolist()
    sp = GREEDY(max_tokens=30)
    want = eng.generate([prompt], sp)[0].token_ids
    rid = eng.submit(prompt, sp)
    eng._carries = lambda: False
    for _ in range(3):
        eng.step()
    state = eng._pools[1]
    record = int(state.tables[0, 0])
    assert record and eng._slots[0].more_blocks[-1] == [record]
    assert eng.stats()["pools"]["state"]["held"] == 1
    assert eng._preempt_youngest() == 0
    assert state.held() == 0 and not state.tables.any()
    out = None
    while eng.has_unfinished():
        out = next((o for o in eng.step() if o.request_id == rid), out)
    assert out.token_ids == want
    assert eng.blocks.stats["preemptions"] == 1
    assert _gap(eng, cfg, prompt, out.token_ids) < TOL
    for p in eng._pools:  # every book back at zero
        assert p.blocks.available() == p.blocks.num_blocks - 1
        assert p.held() == 0 and not p.tables.any()


def test_more_requests_than_records_wait_for_one():
    """Three records for four slots: the fourth request waits for a record
    and every answer is the reference's."""
    cfg, eng = _engine(num_blocks={"latent": 64, "state": 4})
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 256, n).tolist() for n in (7, 12, 20, 9, 15)]
    most = 0
    ids = [eng.submit(p, GREEDY(max_tokens=10)) for p in prompts]
    outs = {}
    while eng.has_unfinished():
        for o in eng.step():
            outs[o.request_id] = o
        most = max(most, eng._pools[1].held())
        busy = [i for i, r in enumerate(eng._slots) if r is not None]
        held = [int(eng._pools[1].tables[i, 0]) for i in busy]
        assert all(held) and len(set(held)) == len(held)
    assert most == 3
    for rid, p in zip(ids, prompts):
        assert _gap(eng, cfg, p, outs[rid].token_ids) < TOL


def test_the_engine_says_what_it_runs():
    """``engine.dispatch_window``'s stats by layer type, and the name
    scopes the readers look for."""
    cfg, eng = _engine()
    eng.submit([3] * 11, GREEDY(max_tokens=6))
    eng.submit([4] * 20, GREEDY(max_tokens=6))
    eng.step()
    live = eng._live_tokens([0, 1])
    assert live["live_tokens_state"] == 2 and live["state_records_held"] == 2
    assert live["live_tokens_latent"] == live["live_tokens"] > 0
    while eng.has_unfinished():
        eng.step()
    params = eng.params
    pool = gc.init_pools(cfg, {"latent": 8, "state": 3}, 8)
    tables = {"latent": jnp.zeros((2, 4), jnp.int32),
              "state": jnp.zeros((2, 1), jnp.int32)}
    text = jax.jit(gc.decode_step, static_argnames=("cfg",)).lower(
        params, jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
        tables, pool, cfg=cfg).as_text(debug_info=True)
    for scope in ("attn.core/gdn.conv", "attn.core/gdn.update",
                  "attn.core/attn.gate", "experts/experts.shared", "router",
                  "ffn", "head"):
        assert scope in text, scope
    empty = gc.gather_prefix(pool, jnp.zeros((0,), jnp.int32), cfg)
    text = jax.jit(gc.prefill_suffix, static_argnames=("cfg",)).lower(
        params, jnp.zeros((1, 16), jnp.int32), jnp.int32(9), jnp.int32(0),
        *empty, jnp.int32(0),
        {"latent": jnp.zeros((16,), jnp.int32),
         "state": jnp.ones((1,), jnp.int32)},
        jnp.zeros((16,), jnp.int32), pool, cfg=cfg).as_text(debug_info=True)
    for scope in ("attn.core/gdn.conv", "attn.core/gdn.scan",
                  "attn.core/attn.gate"):
        assert scope in text, scope


# ------------------------------------------------------ (d) the shares

def test_the_shares_add_up_to_the_uncut_layer():
    """16 routed experts in 4 shares of 4: every share routes over all 16,
    computes its own four experts' part and the shared expert; the routed
    parts plus the shared expert ONCE are what the uncut reference gives
    for the layer."""
    whole = GigaChat35Config.tiny()
    mp = _params(whole, seed=11)["layers"][1]["moe"]
    # experts large enough that the clamp bites and their part is of the
    # stream's own size
    mp["experts"] = jax.tree.map(lambda a: 6.0 * a, mp["experts"])
    model = _model(whole)
    h = jax.random.normal(jax.random.PRNGKey(12), (1, 24, 64))
    want_routed, want_shared = reference.moe_parts(h[0], mp, model)
    assert float(jnp.max(jnp.abs(want_routed))) > 0.3
    chosen, _ = reference.route(h[0], mp["router"], model)
    total, picks = jnp.zeros_like(h[0]), 0
    for c in range(4):
        mp_c = dict(mp, experts=jax.tree.map(lambda a: a[4 * c:4 * c + 4],
                                             mp["experts"]))
        cfg_c = dataclasses.replace(whole, first_expert=4 * c,
                                    held_experts=4)
        s, stats = gc._moe(h, mp_c, cfg_c, jnp.ones((1, 24), bool))
        total += s[0] - want_shared  # this share's routed part
        picks += int(stats[0])
        routed_c, shared_c = reference.moe_parts(
            h[0], mp_c, dict(model, first_expert=4 * c, held_experts=4))
        assert float(jnp.max(jnp.abs(s[0] - routed_c - shared_c))) < TOL
        assert float(jnp.max(jnp.abs(shared_c - want_shared))) == 0.0
    assert float(jnp.max(jnp.abs(total - want_routed))) < 4 * TOL
    assert picks == chosen.size == 24 * 3  # every pick lands on one share


# ------------------------------------- (e) the gate off is what it was

@pytest.mark.parametrize("model", ["longcat", "deepseek_v3"])
def test_the_ungated_models_are_bit_for_bit_what_they_were(model, monkeypatch):
    """LongCat's and DeepSeek-V3's blocks draw no ``w_g``, their programs
    lower to the same text whether or not the gate's code is in the way,
    and their outputs are the same bits."""
    if model == "longcat":
        from ray_tpu.models import longcat as m
        cfg = m.LongcatConfig.tiny()
        params = m.longcat_init(jax.random.PRNGKey(1), cfg)
        blocks = [ap for lp in params["layers"] for ap in lp["attn"]]
        pool = m.init_latent_pool(cfg, 6, 8)
        step = lambda: jax.jit(  # noqa: E731
            m.latent_decode_step, static_argnames=("cfg", "attn"))
        prefill = lambda: jax.jit(  # noqa: E731
            m.latent_prefill_suffix, static_argnames=("cfg",))
    else:
        from ray_tpu.models import deepseek_v3 as m
        cfg = m.DeepseekV3Config.tiny()
        params = m.deepseek_v3_init(jax.random.PRNGKey(1), cfg)
        blocks = [lp["attn"] for lp in params["layers"]]
        pool = m.init_latent_pool(cfg, 6, 8)
        step = lambda: jax.jit(  # noqa: E731
            m.decode_step, static_argnames=("cfg", "attn"))
        prefill = lambda: jax.jit(  # noqa: E731
            m.prefill_suffix, static_argnames=("cfg",))
    assert not cfg.gated_attention
    assert all("w_g" not in ap for ap in blocks)
    assert mla.output_gate(jnp.zeros((1, 2, cfg.hidden_size)), blocks[0],
                           cfg) is None
    tables = jnp.zeros((2, 4), jnp.int32).at[0, :2].set(jnp.arange(1, 3))
    step_args = (params, jnp.asarray([3, 0], jnp.int32),
                 jnp.asarray([9, 0], jnp.int32), tables, pool)
    empty = m.gather_latent_prefix(pool, jnp.zeros((0,), jnp.int32), cfg)
    pre_args = (params, jnp.ones((1, 16), jnp.int32), jnp.int32(9),
                jnp.int32(0), *empty, jnp.int32(0),
                jnp.asarray([1] * 8 + [2] * 8, jnp.int32),
                jnp.asarray(list(range(8)) * 2, jnp.int32), pool)

    def texts_and_outputs():
        s = step().lower(*step_args, cfg=cfg, attn="gather")
        p = prefill().lower(*pre_args, cfg=cfg)
        return ((s.as_text(), p.as_text()),
                (s.compile()(*step_args)[0], p.compile()(*pre_args)[0]))

    now_text, now_out = texts_and_outputs()
    # mla.py as it was before the gate: the two call sites without the
    # gate's argument, nothing else
    monkeypatch.setattr(mla, "_gate_of", lambda xn, ap, cfg: {})
    was_text, was_out = texts_and_outputs()
    assert now_text == was_text
    for a, b in zip(now_out, was_out):
        assert jnp.array_equal(a, b)


def test_the_gate_multiplies_after_w_uv_in_the_absorbed_form():
    """The gated block's absorbed decode against its own plain form, and
    the gate is not a no-op."""
    cfg = GigaChat35Config.tiny()
    params = _params(cfg)
    ap = params["layers"][3]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 9, 64))
    cos, sin = gc._rope_table(cfg, 9)
    q_nope, q_pe, c_kv, k_pe = mla.project(x, ap, cfg, cos, sin, None)
    mask = jnp.tril(jnp.ones((9, 9), bool))[None]
    gate = mla.output_gate(x, ap, cfg)
    want = mla.plain(q_nope, q_pe, c_kv, k_pe, mask, ap, cfg, "plain",
                     gate=gate)
    rows = mla.pack_rows(c_kv[0], k_pe[0], cfg)  # [9, W]

    def attend_rows(q):  # the last query over every row
        scores = jnp.einsum("bhw,tw->bht", q, rows) * cfg.softmax_scale
        return jnp.einsum("bht,tk->bhk", jax.nn.softmax(scores, -1),
                          rows[:, :cfg.kv_lora_rank])

    got = mla.absorbed(q_nope[:, -1], q_pe[:, -1], ap, cfg, attend_rows,
                       gate=gate[:, -1])
    assert float(jnp.max(jnp.abs(got - want[:, -1]))) < TOL
    ungated = mla.absorbed(q_nope[:, -1], q_pe[:, -1], ap, cfg, attend_rows)
    assert float(jnp.max(jnp.abs(ungated - got))) > 100 * TOL


# --------------------------------------------------- (f) the control

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_reads_not_correct(seed):
    """The control of the cell's ``correct``: the reference with every
    weight product's operands rounded to float8_e4m3fn, in the program's
    place, against the program in bfloat16 with float32 records (as the
    configuration states).  The median over positions of the logit error
    (``tests/test_longcat.py`` says why the median at toy widths)."""
    cfg = GigaChat35Config.tiny(first_expert=4, held_experts=8,
                                dtype=jnp.bfloat16)
    params = gigachat3_5_init(jax.random.PRNGKey(seed), cfg)
    model = _model(GigaChat35Config.tiny(first_expert=4, held_experts=8))
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 10), (1, 64), 0,
                                256)
    want = jax.jit(lambda p: reference.logits(p, tokens[0], model))(params)

    def readings(got):
        err = jnp.max(jnp.abs(got - want), axis=-1)
        return float(jnp.median(err)), float(jnp.max(err))

    sound = readings(jax.jit(functools.partial(
        gigachat3_5_apply, cfg=cfg))(params, tokens)[0])
    control = dict(model, control_dtype="float8_e4m3fn")
    wrong = readings(jax.jit(
        lambda p: reference.logits(p, tokens[0], control))(params))
    assert wrong[0] > 3 * sound[0], (sound, wrong)


# ------------------------------------------- (g) what is not supplied

def test_unsupported_options_raise_by_name():
    cfg = GigaChat35Config.tiny()
    kw = dict(tokenizer=_Ids(), batch_slots=2, max_len=32, block_size=8)
    with pytest.raises(ValueError, match="kv_dtype"):
        LLMEngine(cfg, kv_cache_dtype="int8", **kw)
    with pytest.raises(NotImplementedError, match="gigachat3_5.*mesh"):
        LLMEngine(cfg, mesh=object(), **kw)
    with pytest.raises(NotImplementedError,
                       match="gigachat3_5.*prefill_chunk"):
        LLMEngine(cfg, prefill_chunk=16, **kw)
    eng = LLMEngine(cfg, **kw)
    with pytest.raises(NotImplementedError, match="gigachat3_5.*handoff"):
        eng.submit([1, 2, 3], prefill_only=True)
    with pytest.raises(NotImplementedError, match="handoff"):
        eng.export_kv(0)
    with pytest.raises(NotImplementedError, match="prefix hits"):
        gc.gather_prefix(eng.pool, jnp.ones((2,), jnp.int32), cfg)
    with pytest.raises(NotImplementedError):
        gigachat3_5_apply(None, jnp.zeros((1, 2), jnp.int32), cfg,
                          mesh=object())
    with pytest.raises(ValueError, match="full_attention_layers"):
        GigaChat35Config.tiny(full_attention_layers=(7,))
    with pytest.raises(ValueError, match="deepseek_v3"):
        GigaChat35Config.tiny(full_attention_layers=(0, 1, 2, 3))
    toy = family.TOY_MODEL
    assert family.config(toy).expert_layers == toy["num_layers"] == 3
    with pytest.raises(ValueError, match="hidden_layers"):
        family.config(dict(toy, hidden_layers=5))
    with pytest.raises(ValueError, match="linear"):
        family.config(dict(toy, rope_scaling=dict(toy["rope_scaling"],
                                                  type="linear")))


def test_presets_resolve_by_name():
    from ray_tpu.llm.serving import _build_engine

    assert preset("gigachat3_5_tiny") == GigaChat35Config.tiny()
    big = preset("gigachat3_5_432b")
    assert (big.hidden_size, big.num_experts, big.v_head_dim,
            big.dense_layers, big.expert_layers, big.attention_blocks,
            big.delta_layers, big.conv_channels) == (
                7168, 256, 128, 3, 37, 10, 30, 16384)
    assert big.full_attention_layers == tuple(range(3, 40, 4))
    assert round(big.softmax_scale, 6) == 0.105304
    assert gc.layer_types(big) == {
        "latent": {"layers": 10, "window": None},
        "state": {"layers": 30, "window": None, "state": True}}
    served = preset("gigachat3_5_432b", serve_max_len=4112)
    assert served.param_dtype == jnp.bfloat16 and served.max_seq_len == 4112
    eng = _build_engine({"model": "gigachat3_5_tiny", "batch_slots": 2,
                         "max_len": 32, "block_size": 8}, 1)
    assert eng.model.name == "gigachat3_5"
    assert eng.cfg.param_dtype == jnp.float32  # a tiny preset stays as it is
    assert family.config(family.model_of(big)) == big
