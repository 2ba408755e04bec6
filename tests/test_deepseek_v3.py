"""DeepSeek-V3's family (GigaChat3.1-702B-A36B) as ``LLMEngine`` serves it,
against the plain reference the benchmark keeps for it
(``cells/families/deepseek_v3_reference.py``: float32 ``jax.numpy``, written
from the published configuration, importing nothing of the program).

Seeded float32 at toy widths on the CPU, so the tolerance is float32's
rounding through three layers: 2e-5 on logits of magnitude ~0.6:

(a) ``models/deepseek_v3.py``'s forward against the reference, for the
    whole range of experts and for a held range;
(b) the shares add up: the shares' routed parts plus the shared expert
    ONCE equal the uncut layer of the reference; ``moe_group_tokens``
    against a count by hand;
(c) the router against five plain lines on cases that separate it from the
    softmax one, and LongCat's and SmallThinker's routers bit for bit what
    ``route_top_k`` gave before it knew sigmoid scores and groups;
(d) the YaRN table and scale against the published constants;
(e) prefill then decode through ``LLMEngine``'s latent cache against the
    reference's full forward, on logits; freed slots skipped and uncounted;
(f) the control of the cell's ``correct`` reads not correct;
(g) what the model does not supply raises by name.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from cells.families import deepseek_v3 as family
from cells.families import deepseek_v3_reference as reference
from ray_tpu.llm import SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import deepseek_v3 as ds
from ray_tpu.models.deepseek_v3 import (DeepseekV3Config, _moe, decode_step,
                                        deepseek_v3_apply, deepseek_v3_init,
                                        init_latent_pool, prefill_suffix)
from ray_tpu.models.served import preset, served_model
from ray_tpu.ops.experts import route_top_k
from ray_tpu.ops.layers import (rope_frequencies, yarn_correction_range,
                                yarn_rope_frequencies)

TOL = 2e-5

_model = family.model_of  # the configuration as the reference takes it


def _params(cfg, seed=3):
    """Seeded weights with a selection bias that matters (zeros at init)."""
    params = deepseek_v3_init(jax.random.PRNGKey(seed), cfg)
    for i, lp in enumerate(params["layers"]):
        if "moe" in lp:
            bias = lp["moe"]["router"]["bias"]
            lp["moe"]["router"]["bias"] = 0.05 * jax.random.normal(
                jax.random.PRNGKey(seed + 1 + i), bias.shape)
    return params


# ------------------------------------------------------ (a) the forward

@pytest.mark.parametrize("held", [None, (4, 8)],
                         ids=["all-experts", "held-4..11"])
def test_forward_matches_the_plain_reference(held):
    cfg = DeepseekV3Config.tiny() if held is None else DeepseekV3Config.tiny(
        first_expert=held[0], held_experts=held[1])
    params = _params(cfg)
    assert [sorted(lp) for lp in params["layers"]] == [
        ["attn", "ffn"], ["attn", "moe"], ["attn", "moe"]]
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, 256)
    got, stats = deepseek_v3_apply(params, tokens, cfg, return_stats=True)
    for i in range(2):
        want = reference.logits(params, tokens[i], _model(cfg))
        assert float(jnp.max(jnp.abs(got[i] - want))) < TOL
    # and the reference notices a wrong model: no selection bias, a softmax
    # scale without YaRN's temperature
    zeroed = jax.tree.map(lambda a: a, params)
    for lp in zeroed["layers"][1:]:
        lp["moe"]["router"]["bias"] *= 0
    other = reference.logits(zeroed, tokens[0], _model(cfg))
    assert float(jnp.max(jnp.abs(other - got[0]))) > 100 * TOL
    plain = dict(_model(cfg), rope_scaling=None)
    other = reference.logits(params, tokens[0], plain)
    assert float(jnp.max(jnp.abs(other - got[0]))) > 100 * TOL
    # 2 expert layers x 80 tokens x 3 picks; no zero-compute expert
    pairs, hit, zero, grouped = (int(s) for s in stats)
    assert zero == 0 and 0 < pairs <= 480 and (pairs == 480) == (held is None)
    assert 0 < hit <= 2 * cfg.num_held
    # 2 of 4 groups are kept: a held range of two groups sees most tokens
    assert grouped == 160 if held is None else 80 < grouped < 160


# ------------------------------------------------- (b) the shares add up

def test_the_shares_add_up_to_the_uncut_layer():
    """16 routed experts in 4 shares of 4 (a share a group): every share
    routes over all 16, computes its own four experts' part and the shared
    expert; the routed parts plus the shared expert ONCE are what the uncut
    reference gives for the layer."""
    whole = DeepseekV3Config.tiny(num_layers=2)
    lp = _params(whole, seed=11)["layers"][1]
    mp = lp["moe"]
    # experts large enough that their part is of the stream's own size
    mp["experts"] = jax.tree.map(lambda a: 6.0 * a, mp["experts"])
    model = _model(whole)

    def share_of(c):
        return (dict(mp, experts=jax.tree.map(lambda a: a[4 * c:4 * c + 4],
                                              mp["experts"])),
                dict(model, first_expert=4 * c, held_experts=4))

    h = jax.random.normal(jax.random.PRNGKey(12), (1, 24, 64))
    live = jnp.ones((1, 24), bool).at[0, 20:].set(False)
    want_routed, want_shared = reference.moe_parts(h[0], mp, model)
    assert float(jnp.max(jnp.abs(want_routed))) > 0.3
    assert float(jnp.max(jnp.abs(want_shared))) > 0.001
    chosen, weight, kept = reference.route(h[0], mp["router"], model)
    total = jnp.zeros_like(h[0])
    picks = 0
    for c in range(4):
        mp_c, model_c = share_of(c)
        cfg_c = dataclasses.replace(whole, first_expert=4 * c, held_experts=4)
        assert cfg_c.held_groups == tuple(g == c for g in range(4))
        s, stats = _moe(h, mp_c, cfg_c, jnp.ones((1, 24), bool))
        total += s[0] - want_shared  # this share's routed part
        picks += int(stats[0])
        # the program's share is the reference's, given the same range
        ref_routed, ref_shared = reference.moe_parts(h[0], mp_c, model_c)
        assert float(jnp.max(jnp.abs(s[0] - ref_routed - ref_shared))) < TOL
        # moe_group_tokens by hand: tokens one of whose kept groups is c;
        # of the 20 live ones alone where four are not
        assert int(stats[3]) == int(jnp.sum(kept[:, c]))
        _, stats = _moe(h, mp_c, cfg_c, live)
        assert int(stats[3]) == int(jnp.sum(kept[:20, c]))
        assert int(stats[0]) == int(jnp.sum(chosen[:20] // 4 == c))
    assert float(jnp.max(jnp.abs(total - want_routed))) < TOL
    # every pick landed on exactly one share; every token kept 2 groups
    assert picks == 24 * 3 and int(jnp.sum(kept)) == 24 * 2
    # and the layer: the expert layer joins the stream additively, so the
    # uncut layer is share 0's with the other shares' routed parts added
    uncut = reference.layer(h[0], lp, model)
    mp_0, model_0 = share_of(0)
    share0 = reference.layer(h[0], dict(lp, moe=mp_0), model_0)
    h1 = h[0] + reference._mla(
        reference._rms_norm(h[0], lp["attn"]["norm"], 1e-6), lp["attn"],
        model)
    y = reference._rms_norm(h1, mp["norm"], 1e-6)
    routed_all, _ = reference.moe_parts(y, mp, model)
    routed_0, _ = reference.moe_parts(y, mp_0, model_0)
    assert float(jnp.max(jnp.abs(routed_all - routed_0))) > 0.1
    assert float(jnp.max(jnp.abs(
        uncut - (share0 - routed_0 + routed_all)))) < TOL


# ------------------------------------------------------ (c) the router

def _five_plain_lines(y, w, bias, k, scale, n_group, topk_group):
    s = 1 / (1 + np.exp(-(y.astype(np.float64) @ w.astype(np.float64))))
    c = (s + bias).reshape(len(y), n_group, -1)
    best = np.argsort(-np.sort(c, -1)[..., -2:].sum(-1), -1)[:, :topk_group]
    keep = (best[:, :, None] == np.arange(n_group)).any(1)
    idx = np.argsort(-np.where(keep[:, :, None], c, -np.inf).reshape(s.shape),
                     -1, kind="stable")[:, :k]
    picked = np.take_along_axis(s, idx, -1)
    return idx, picked / picked.sum(-1, keepdims=True) * scale, keep


ROUTER_CASES = ["a-bias-picks-and-does-not-weigh",
                "a-best-expert-in-a-dropped-group", "seeded"]


@pytest.mark.parametrize("case", ROUTER_CASES)
def test_the_router_against_five_plain_lines(case):
    """16 outputs in 4 groups of which 2 stay, 3 picks, weights that sum to
    2.5.  The router's input is the identity, so a logit is what the case
    writes."""
    N, k, groups = 16, 3, (4, 2)
    bias = np.zeros(N, np.float32)
    if case == "seeded":
        rng = np.random.default_rng(5)
        logits = rng.normal(size=(32, N)).astype(np.float32)
        bias = 0.3 * rng.normal(size=N).astype(np.float32)
    else:
        # experts 0-3 | 4-7 | 8-11 | 12-15
        logits = np.full((1, N), -3.0, np.float32)
        logits[0, [4, 5, 8, 9]] = [1.0, 0.9, 0.8, 0.7]
        logits[0, 12] = 2.0  # the best expert, alone in its group
    if case == "a-bias-picks-and-does-not-weigh":
        bias[9] = 0.5  # lifts 9 over 4, 5 and 8; 12's group still loses
    y, w = jnp.asarray(logits), jnp.eye(N, dtype=jnp.float32)
    idx, weight, kept = route_top_k(y, w, jnp.asarray(bias), k, 2.5,
                                    renormalise=True, score="sigmoid",
                                    groups=groups)
    want_idx, want_weight, want_kept = _five_plain_lines(
        logits, np.eye(N), bias, k, 2.5, *groups)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_array_equal(np.asarray(kept), want_kept)
    np.testing.assert_allclose(np.asarray(weight), want_weight, atol=1e-6)
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 2.5, atol=1e-6)
    # the reference's own router says the same
    model = dict(_model(DeepseekV3Config.tiny()), experts_per_token=k)
    r_idx, r_weight, r_kept = reference.route(
        y, {"w": w, "bias": jnp.asarray(bias)}, model)
    np.testing.assert_array_equal(np.asarray(r_idx), want_idx)
    np.testing.assert_array_equal(np.asarray(r_kept), want_kept)
    np.testing.assert_allclose(np.asarray(r_weight), want_weight, atol=1e-6)
    if case == "seeded":
        return
    sig = 1 / (1 + np.exp(-logits[0].astype(np.float64)))
    # the softmax router over all outputs takes the best expert
    soft, _ = route_top_k(y, w, jnp.asarray(bias), k, 2.5, renormalise=True)
    assert 12 in np.asarray(soft[0]) and 12 not in np.asarray(idx[0])
    if case == "a-best-expert-in-a-dropped-group":
        # group 3's two best are 12 and a -3: 0.88 + 0.05 < 4 + 5, 8 + 9
        assert list(np.asarray(kept[0])) == [False, True, True, False]
        assert list(np.asarray(idx[0])) == [4, 5, 8]
    else:
        assert list(np.asarray(idx[0])) == [9, 4, 5]
        # 9 weighs its own sigmoid, not sigmoid + 0.5
        np.testing.assert_allclose(
            float(weight[0, 0]), 2.5 * sig[9] / sig[[9, 4, 5]].sum(),
            rtol=1e-6)


def _route_top_k_before(y, w_router, bias, k, scale, renormalise=False):
    """``route_top_k`` as the parent commit had it (softmax over all
    outputs, no groups), kept here word for word."""
    logits = jnp.matmul(y.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    _, idx = lax.top_k(p if bias is None else p + bias.astype(jnp.float32),
                       k)
    weight = jnp.take_along_axis(p, idx, axis=-1)
    if renormalise:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), weight * scale


@pytest.mark.parametrize("model", ["longcat", "smallthinker"])
def test_the_softmax_routers_are_bit_for_bit_what_they_were(model):
    """LongCat's call (a bias, 12 of 768, times 6, not renormalised) and
    SmallThinker's (no bias, 6 of 64, renormalised), eagerly and jitted."""
    N, k, scale, renorm = (768, 12, 6.0, False) if model == "longcat" \
        else (64, 6, 1.0, True)
    y = jax.random.normal(jax.random.PRNGKey(1), (96, 128), jnp.bfloat16)
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (128, N))
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(3), (N,)) \
        if model == "longcat" else None
    for wrap in (lambda f: f, jax.jit):
        got = wrap(functools.partial(route_top_k, k=k, scale=scale,
                                     renormalise=renorm))(y, w, bias)
        want = wrap(functools.partial(_route_top_k_before, k=k, scale=scale,
                                      renormalise=renorm))(y, w, bias)
        assert len(got) == 2
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


# ------------------------------------------------------------ (d) YaRN

def test_yarn_at_the_published_settings():
    """theta 1e5, factor 64 over an original context of 4096, 32 rotary
    pairs: the blend runs from pair 8 to pair 19 (8.378 and 18.011 before
    rounding), cos and sin keep their size (mscale == mscale_all_dim) and
    the softmax scale is 192^-0.5 * (0.1 ln 64 + 1)^2."""
    cfg = DeepseekV3Config()
    assert yarn_correction_range(64, 1e5, 4096, 32, 1) == (8, 19)
    assert reference.yarn_range(64, 1e5, _model(cfg)["rope_scaling"]) \
        == (8, 19)
    m = 0.1 * math.log(64) + 1
    assert round(m, 5) == 1.41589
    assert round(cfg.softmax_scale, 6) == 0.144680
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    assert reference.softmax_scale(_model(cfg)) == pytest.approx(
        cfg.softmax_scale)
    cos, sin = yarn_rope_frequencies(
        64, 5120, 1e5, factor=64.0, original_max_len=4096, beta_fast=32,
        beta_slow=1, mscale=1.0, mscale_all_dim=1.0)
    assert cos.shape == sin.shape == (5120, 32)
    i = np.arange(32)
    f = 1e5 ** (-2 * i / 64)
    ramp = np.clip((i - 8) / 11, 0, 1)
    inv = f * (1 - ramp) + f / 64 * ramp
    pos = 4999  # the table acts at every position, not only past 4096
    np.testing.assert_allclose(np.asarray(cos[pos]), np.cos(pos * inv),
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin[1]), np.sin(inv), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(reference.yarn_inv_freq(_model(cfg))), inv, rtol=1e-5)
    # below the range the plain table, above it 64 times slower
    plain_cos, _ = rope_frequencies(64, 5120, 1e5)
    np.testing.assert_allclose(np.asarray(cos[:, :9]),
                               np.asarray(plain_cos[:, :9]), atol=1e-6)
    assert float(jnp.max(jnp.abs(cos[:, 19:] - plain_cos[:, 19:]))) > 0.1
    # an unequal pair of mscales scales the table itself
    cos2, _ = yarn_rope_frequencies(
        64, 8, 1e5, factor=64.0, original_max_len=4096, mscale=1.0,
        mscale_all_dim=0.0)
    np.testing.assert_allclose(np.asarray(cos2[0]), m, rtol=1e-6)
    # a factor of 1 is the plain table, and the plain scale
    one = DeepseekV3Config.tiny(rope_factor=1.0)
    assert one.softmax_scale == 24 ** -0.5
    np.testing.assert_allclose(np.asarray(ds._rope_table(one, 16)[0]),
                               np.asarray(rope_frequencies(8, 16, 1e4)[0]),
                               atol=1e-6)


# ----------------------------------------------------- (e) the engine

class _Ids:
    """Token ids in, token ids out."""
    eos_id = None
    vocab_size = 256

    def encode(self, text):
        return [1]

    def decode(self, ids):
        return ""


@pytest.mark.parametrize("chunk", [0, 16], ids=["whole", "chunked"])
def test_engine_decodes_through_the_latent_cache(chunk):
    cfg = dataclasses.replace(preset("deepseek_v3_tiny"), first_expert=4,
                              held_experts=4)
    eng = LLMEngine(cfg, tokenizer=_Ids(), batch_slots=4, max_len=96,
                    block_size=8, seed=5, prefill_chunk=chunk)
    assert eng.model is served_model(cfg)
    assert eng.attn == "gather" and set(eng.pool) == {"kv"}
    assert eng.pool["kv"].shape == (3, eng.num_blocks, 8, 128)
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 256, 24).tolist()
    prompts = [shared + rng.integers(0, 256, n).tolist()
               for n in (5, 17, 30)] + [rng.integers(0, 256, 9).tolist()]
    sp = SamplingParams(max_tokens=12, temperature=0.0, stop_token_id=None)
    # the first alone, so that the others find its blocks cached
    outs = eng.generate(prompts[:1], sp) + eng.generate(prompts[1:], sp)
    st = eng.stats()
    assert st["model"] == "deepseek_v3" and st["experts"] == "grouped"
    assert st["prefix_cache"]["prefix_blocks_reused"] >= 6
    assert (st["prefill_chunks"] > 0) == bool(chunk)
    assert st["prefill_attention"] == {"plain": sum(
        st["prefill_attention"].values())}
    c = st["counters"]
    assert c["decode_steps"] > 0 and c["moe_pairs_held"] > 0
    assert 0 < c["moe_experts_hit"] and c["moe_zero_picks"] == 0
    # 2 expert layers, a token a slot and step at the most
    assert 0 < c["moe_group_tokens"] <= 2 * 4 * c["decode_steps"]
    assert c["prefill_calls"] >= len(prompts) and c["prefill_moe_pairs_held"]
    assert 0 < c["prefill_moe_group_tokens"] <= 2 * sum(map(len, prompts))
    eng.blocks.assert_integrity()
    for prompt, out in zip(prompts, outs):
        assert len(out.token_ids) == 12
        seq = jnp.asarray(prompt + out.token_ids)
        lg = reference.logits(eng.params, seq[:-1], _model(cfg))
        rows = lg[len(prompt) - 1:]
        chosen = jnp.take_along_axis(rows, seq[len(prompt):, None], -1)[:, 0]
        assert float(jnp.max(jnp.max(rows, -1) - chosen)) < TOL


def test_decode_step_logits_match_the_reference_and_skip_freed_slots():
    """Prefill, then decode token by token through the latent cache, on
    logits; a freed slot (its table row all scratch) is routed nowhere and
    counted by none of the four counters."""
    cfg = DeepseekV3Config.tiny(first_expert=4, held_experts=8)
    params = _params(cfg)
    model = _model(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (21,), 0, 256)
    want = reference.logits(params, tokens, model)
    pool = init_latent_pool(cfg, 9, 4)
    assert pool["kv"].shape == (3, 9, 4, 128)
    # the first 8 tokens by the prefill, into blocks 1 and 2
    empty = lambda w: jnp.zeros((3, 0, w), jnp.float32)  # noqa: E731
    last, pool, st = jax.jit(functools.partial(prefill_suffix, cfg=cfg))(
        params, tokens[None, :8], jnp.int32(8), jnp.int32(0), empty(32),
        empty(8), jnp.int32(0), jnp.asarray([1] * 4 + [2] * 4, jnp.int32),
        jnp.asarray(list(range(4)) * 2, jnp.int32), pool)
    assert float(jnp.max(jnp.abs(last[0] - want[7]))) < TOL
    assert st.shape == (4,) and int(st[2]) == 0 and 0 < int(st[3]) <= 16
    tables = jnp.asarray([[1, 2, 3, 4, 5, 6], [0] * 6], jnp.int32)
    step = jax.jit(functools.partial(decode_step, cfg=cfg))
    stats = []
    for pos in range(8, 21):
        tok = jnp.asarray([tokens[pos], 7], jnp.int32)
        logits, pool, st = step(
            params, tok, jnp.asarray([pos, 0], jnp.int32), tables, pool)
        assert float(jnp.max(jnp.abs(logits[0] - want[pos]))) < TOL
        stats.append(np.asarray(st))
    # only the live slot's 2 expert layers x 3 picks are counted
    assert all(s[0] <= 6 and s[1] <= 6 and s[2] == 0 and s[3] <= 2
               for s in stats)
    assert sum(s[3] for s in stats) > 0


def test_an_uncached_prompt_through_the_flash_kernel():
    """No cached prefix: the flash path (forced; the interpreter) at keys
    and values of one width under the YaRN scale gives the last position's
    logits and the cache rows of the plain path."""
    cfg = DeepseekV3Config.tiny()
    params = _params(cfg)
    tokens = np.random.default_rng(6).integers(0, 256, 64)
    S, bs, length = 64, 8, 50
    live = np.arange(S) < length
    dst_b = np.where(live, 1 + np.arange(S) // bs, 0).astype(np.int32)
    dst_o = np.where(live, np.arange(S) % bs, 0).astype(np.int32)
    empty = lambda w: jnp.zeros((3, 0, w), jnp.float32)  # noqa: E731
    out = {}
    for impl in ("flash", "ref"):
        assert ds.prefill_attention_path(S, 0, impl) == (
            "flash" if impl == "flash" else "plain")
        out[impl] = prefill_suffix(
            params, jnp.asarray(tokens[None], jnp.int32), jnp.int32(length),
            jnp.int32(0), empty(32), empty(8), jnp.int32(0),
            jnp.asarray(dst_b), jnp.asarray(dst_o),
            init_latent_pool(cfg, 12, bs), cfg=cfg, attn_impl=impl)
    want = reference.logits(params, jnp.asarray(tokens[:length]),
                            _model(cfg))[-1]
    for impl, (logits, pool, stats) in out.items():
        assert float(jnp.max(jnp.abs(logits[0] - want))) < TOL, impl
    flash, plain = out["flash"], out["ref"]
    assert float(jnp.max(jnp.abs(
        flash[1]["kv"][:, 1:] - plain[1]["kv"][:, 1:]))) < TOL
    assert np.array_equal(flash[2], plain[2])
    assert ds.prefill_attention_path(64, 32, "flash") == "plain"


def test_engine_on_the_expert_kernel_decodes_what_the_grouped_path_does(
        monkeypatch):
    """The decode program forced onto ``ops/pallas/expert_decode.py``
    (interpreter) returns token for token what the grouped path returns,
    with the four counters the grouped path counts."""
    from ray_tpu.ops import experts

    cfg = dataclasses.replace(preset("deepseek_v3_tiny"), first_expert=4,
                              held_experts=4)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).tolist() for n in (5, 17, 11)]
    sp = SamplingParams(max_tokens=10, temperature=0.0, stop_token_id=None)

    def run():
        eng = LLMEngine(cfg, tokenizer=_Ids(), batch_slots=4, max_len=96,
                        block_size=8, decode_window=4, seed=5)
        outs = eng.generate(prompts, sp)
        return eng.stats(), [o.token_ids for o in outs]

    st, want = run()  # the CPU backend: grouped
    assert st["experts"] == "grouped"
    monkeypatch.setattr(
        experts, "expert_path",
        lambda T, *a: "decode_kernel" if T == 4 else "grouped")
    st2, got = run()
    assert got == want and st2["experts"] == "decode_kernel"
    c, c2 = st["counters"], st2["counters"]
    assert c2["expert_kernel_windows"] == c2["decode_windows"] > 0
    assert {n: c2[n] for n in c if n.startswith(("moe_", "prefill_"))} \
        == {n: c[n] for n in c if n.startswith(("moe_", "prefill_"))}


# --------------------------------------------------- (f) the control

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_reads_not_correct(seed):
    """The control of the cell's ``correct``: the reference with every
    weight product's operands rounded to float8_e4m3fn, in the program's
    place, against the program in bfloat16 (as the configuration states).
    The median over positions of the logit error (``tests/test_longcat.py``
    says why the median at toy widths): readings at this size, seeds 1-5,
    are in the assertion's message."""
    cfg = DeepseekV3Config.tiny(first_expert=4, held_experts=8,
                                dtype=jnp.bfloat16)
    params = deepseek_v3_init(jax.random.PRNGKey(seed), cfg)
    model = _model(DeepseekV3Config.tiny(first_expert=4, held_experts=8))
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 10), (1, 64), 0,
                                256)
    want = reference.logits(params, tokens[0], model)

    def readings(got):
        """Over positions, the median and the largest logit error."""
        err = jnp.max(jnp.abs(got - want), axis=-1)
        return float(jnp.median(err)), float(jnp.max(err))

    sound = readings(deepseek_v3_apply(params, tokens, cfg)[0])
    wrong = readings(reference.logits(
        params, tokens[0], dict(model, control_dtype="float8_e4m3fn")))
    assert sound[0] < 0.008 < wrong[0], (sound, wrong)
    assert wrong[0] > 3 * sound[0]


# ------------------------------------------- (g) what is not supplied

def test_unsupported_options_raise_by_name():
    cfg = DeepseekV3Config.tiny()
    kw = dict(tokenizer=_Ids(), batch_slots=2, max_len=32, block_size=8)
    with pytest.raises(ValueError, match="kv_dtype"):
        LLMEngine(cfg, kv_cache_dtype="int8", **kw)
    with pytest.raises(NotImplementedError, match="deepseek_v3.*mesh"):
        LLMEngine(cfg, mesh=object(), **kw)
    eng = LLMEngine(cfg, **kw)
    with pytest.raises(NotImplementedError, match="deepseek_v3.*handoff"):
        eng.submit([1, 2, 3], prefill_only=True)
    with pytest.raises(NotImplementedError, match="handoff"):
        eng.export_kv(0)
    with pytest.raises(NotImplementedError, match="handoff"):
        eng.adopt_prefilled({})
    with pytest.raises(NotImplementedError):
        deepseek_v3_apply(None, jnp.zeros((1, 2), jnp.int32), cfg,
                          mesh=object())
    # the family's configuration: a depth that does not add up, a rotary
    # scaling that is not YaRN
    toy = family.TOY_MODEL
    assert family.config(toy).expert_layers == toy["num_layers"] == 2
    with pytest.raises(ValueError, match="hidden_layers"):
        family.config(dict(toy, hidden_layers=4))
    with pytest.raises(ValueError, match="linear"):
        family.config(dict(toy, rope_scaling=dict(toy["rope_scaling"],
                                                  rope_type="linear")))


def test_presets_resolve_by_name():
    from ray_tpu.llm.serving import _build_engine

    assert preset("deepseek_v3_tiny") == DeepseekV3Config.tiny()
    big = preset("gigachat3_1_702b")
    assert (big.hidden_size, big.num_experts, big.v_head_dim,
            big.dense_layers, big.expert_layers) == (7168, 256, 192, 3, 61)
    served = preset("gigachat3_1_702b", serve_max_len=5120)
    assert served.param_dtype == jnp.bfloat16 and served.max_seq_len == 5120
    eng = _build_engine({"model": "deepseek_v3_tiny", "batch_slots": 2,
                         "max_len": 32, "block_size": 8}, 1)
    assert eng.model.name == "deepseek_v3"
    assert eng.cfg.param_dtype == jnp.float32  # a tiny preset stays as it is
    assert family.config(family.model_of(big)) == big
