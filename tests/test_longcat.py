"""LongCat-Flash's language model: the program against its plain reference.

CPU, float32, tiny shapes.  The reference is the benchmark's
(``cells/families/longcat_flash_reference.py``: written from the published
architecture, importing nothing of the program): one set of equations for
these tests and for the cell's ``correct``.

(a) ``models/longcat.py``'s forward against the reference on seeded
    weights, all experts and a held range;
(b) the shares add up: the routed parts of all shares plus the
    zero-compute part counted once are the uncut reference's layer;
(c) ``LLMEngine`` with the tiny preset: prefill, then decode through the
    latent cache, with a prefix hit and with a chunked prefill, on logits;
(d) the kernel's latent arm against the gather path;
(e) the control (weight products rounded to an 8-bit float) reads not
    correct;
(f) what the model does not supply raises;
(g) a prompt with no cached prefix through the flash kernel (interpreter)
    against the plain path and the reference, and the engine's label of
    which ran;
(h) the absorbed pair (PR 45: ``w_uk`` and ``w_uv``, the two halves of
    ``w_kvb`` as the decode products read them): equal to the halves
    element for element, a decode step over them the step over slices of
    ``w_kvb`` (bit for bit from the size at which the CPU runs both through
    one routine), and re-derived after ``w_kvb`` is replaced.

Tolerances: float32 on both sides, different orders of summation (the
absorbed form against the plain one, grouped products against a loop over
experts): logits of magnitude ~0.6 agree to 2e-5.  A returned token's gap
under the reference's largest logit is 0 unless two logits tie to 2e-5.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cells.families import longcat_flash_reference as reference
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm import SamplingParams
from ray_tpu.models import longcat
from ray_tpu.models.longcat import (LongcatConfig, _moe, absorbed_pair,
                                    gather_latent_prefix, init_latent_pool,
                                    latent_decode_step,
                                    latent_prefill_suffix, longcat_apply,
                                    longcat_init, prefill_attention_path)
from ray_tpu.models.paged_generation import decode_attention_path
from ray_tpu.models.served import preset, served_model
from ray_tpu.ops.pallas.paged_attention import latent_paged_attention

TOL = 2e-5


def _model(cfg):
    """The configuration as the reference takes it: a plain dict."""
    return dataclasses.asdict(cfg)


def _params(cfg, seed=3):
    """Seeded weights with a selection bias that matters (zeros at init)."""
    params = longcat_init(jax.random.PRNGKey(seed), cfg)
    for i, lp in enumerate(params["layers"]):
        lp["router"]["bias"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(seed + 1 + i), lp["router"]["bias"].shape)
    return params


# ------------------------------------------------------ (a) the forward

@pytest.mark.parametrize("held", [None, (2, 4)],
                         ids=["all-experts", "held-2..5"])
def test_forward_matches_the_plain_reference(held):
    cfg = LongcatConfig.tiny() if held is None else LongcatConfig.tiny(
        first_expert=held[0], held_experts=held[1])
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, 256)
    got, stats = longcat_apply(params, tokens, cfg, return_stats=True)
    for i in range(2):
        want = reference.logits(params, tokens[i], _model(cfg))
        assert float(jnp.max(jnp.abs(got[i] - want))) < TOL
    # and the reference notices a wrong model: no selection bias
    zeroed = jax.tree.map(lambda a: a, params)
    for lp in zeroed["layers"]:
        lp["router"]["bias"] = jnp.zeros_like(lp["router"]["bias"])
    other = reference.logits(zeroed, tokens[0], _model(cfg))
    assert float(jnp.max(jnp.abs(other - got[0]))) > 100 * TOL
    # 2 layers x 80 tokens x 3 picks; a third of the router is zero-compute
    pairs, hit, zero = (int(s) for s in stats)
    assert 0 < zero < 480 and pairs + zero <= 480
    assert (pairs + zero == 480) == (held is None)
    assert 0 < hit <= 2 * cfg.num_held


# ------------------------------------------------- (b) the shares add up

def test_the_shares_add_up_to_the_uncut_layer():
    """8 routed + 4 zero-compute experts in 4 shares of 2: every share
    routes over all 12, computes its own two experts' part and the
    zero-compute part; the routed parts plus the zero-compute part ONCE
    are what the uncut reference gives for the layer."""
    whole = LongcatConfig.tiny(num_layers=1)
    lp = _params(whole, seed=11)["layers"][0]
    # experts large enough that their part is of the stream's own size
    lp["experts"] = jax.tree.map(lambda a: 6.0 * a, lp["experts"])
    router, ep = lp["router"], lp["experts"]
    model = _model(whole)

    def share_of(c):
        return (jax.tree.map(lambda a: a[2 * c:2 * c + 2], ep),
                dict(model, first_expert=2 * c, held_experts=2))

    h = jax.random.normal(jax.random.PRNGKey(12), (1, 24, 64))
    live = jnp.ones((1, 24), bool)
    want_routed, want_zero = reference.moe_parts(h[0], router, ep, model)
    assert float(jnp.max(jnp.abs(want_routed))) > 0.3
    total = jnp.zeros_like(h[0])
    picks = 0
    for c in range(4):
        ep_c, model_c = share_of(c)
        s, stats = _moe(h, router, ep_c, LongcatConfig.tiny(
            num_layers=1, first_expert=2 * c, held_experts=2), live)
        total += s[0] - want_zero  # this share's routed part
        picks += int(stats[0])
        # the program's share is the reference's, given the same range
        ref_routed, ref_zero = reference.moe_parts(h[0], router, ep_c,
                                                   model_c)
        assert float(jnp.max(jnp.abs(s[0] - ref_routed - ref_zero))) < TOL
    assert float(jnp.max(jnp.abs(total - want_routed))) < TOL
    # every pick of a routed expert landed on exactly one share
    chosen, _ = reference.route(h[0], router, model)
    assert picks == int(jnp.sum(chosen < 8))
    # and the layer: the expert branch joins the stream additively, so the
    # uncut layer is share 0's with the other shares' routed parts added
    # (taken where the branch reads: y = norm_post0(h1))
    uncut = reference.layer(h[0], lp, model)
    ep_0, model_0 = share_of(0)
    share0 = reference.layer(h[0], dict(lp, experts=ep_0), model_0)
    at0 = lp["attn"][0]
    h1 = h[0] + reference._mla(
        reference._rms_norm(h[0], at0["norm"], 1e-5), at0, model)
    y = reference._rms_norm(h1, lp["ffn"][0]["norm"], 1e-5)
    routed_all, _ = reference.moe_parts(y, router, ep, model)
    routed_0, _ = reference.moe_parts(y, router, ep_0, model_0)
    assert float(jnp.max(jnp.abs(routed_all - routed_0))) > 0.1
    assert float(jnp.max(jnp.abs(
        uncut - (share0 - routed_0 + routed_all)))) < TOL


# ----------------------------------------------------- (c) the engine

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
    cfg = preset("longcat_flash_tiny")
    cfg = dataclasses.replace(cfg, first_expert=2, held_experts=4)
    eng = LLMEngine(cfg, tokenizer=_Ids(), batch_slots=4, max_len=96,
                    block_size=8, seed=5, prefill_chunk=chunk)
    assert eng.model is served_model(cfg)
    assert eng.attn == "gather" and set(eng.pool) == {"kv"}
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 256, 24).tolist()
    prompts = [shared + rng.integers(0, 256, n).tolist()
               for n in (5, 17, 30)] + [rng.integers(0, 256, 9).tolist()]
    sp = SamplingParams(max_tokens=12, temperature=0.0, stop_token_id=None)
    # the first alone, so that the others find its blocks cached
    outs = eng.generate(prompts[:1], sp) + eng.generate(prompts[1:], sp)
    st = eng.stats()
    assert st["model"] == "longcat_flash"
    assert st["prefix_cache"]["prefix_blocks_reused"] >= 6
    assert (st["prefill_chunks"] > 0) == bool(chunk)
    c = st["counters"]
    assert c["decode_steps"] > 0 and c["moe_pairs_held"] > 0
    assert 0 < c["moe_experts_hit"] and 0 < c["moe_zero_picks"]
    # prefills count under names and a denominator of their own: every
    # prompt token picks 2 experts in each of the 2 layers' one router
    assert c["prefill_calls"] >= len(prompts) and c["prefill_moe_pairs_held"]
    assert 0 < c["prefill_moe_zero_picks"] <= 2 * 2 * sum(map(len, prompts))
    eng.blocks.assert_integrity()
    for prompt, out in zip(prompts, outs):
        assert len(out.token_ids) == 12
        seq = jnp.asarray(prompt + out.token_ids)
        lg = reference.logits(eng.params, seq[:-1], _model(cfg))
        rows = lg[len(prompt) - 1:]
        chosen = jnp.take_along_axis(rows, seq[len(prompt):, None], -1)[:, 0]
        assert float(jnp.max(jnp.max(rows, -1) - chosen)) < TOL


def test_decode_step_logits_match_the_reference_and_skip_freed_slots():
    """One decode step through the latent cache on logits, and a freed
    slot (its table row all scratch) is routed nowhere and not counted."""
    cfg = LongcatConfig.tiny(first_expert=2, held_experts=4)
    params = _params(cfg)
    model = _model(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (13,), 0, 256)
    pool = init_latent_pool(cfg, 9, 4)
    tables = jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)
    stats = []
    step = jax.jit(functools.partial(latent_decode_step, cfg=cfg))
    for pos in range(13):  # token by token: every row goes through decode
        tok = jnp.asarray([tokens[pos], 7], jnp.int32)
        logits, pool, st = step(
            params, tok, jnp.asarray([pos, 0], jnp.int32), tables, pool)
        stats.append(np.asarray(st))
    want = reference.logits(params, tokens, model)
    assert float(jnp.max(jnp.abs(logits[0] - want[-1]))) < TOL
    # only the live slot's 2 layers x 3 picks are counted
    assert all(s[0] + s[2] <= 6 for s in stats)


# ------------------------------------------------ (d) the kernel's arm

LENGTHS = {"empty-and-one": [0, 1, 0, 5], "block-edge": [4, 8, 3, 12],
           "many-blocks": [24, 17, 0, 23]}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(LENGTHS))
def test_latent_arm_matches_the_gather_path(case, dtype):
    """The same pool, tables and lengths through the kernel's latent arm
    (Pallas interpreter) and through a gather of the whole table: H heads
    against one shared row a token, values the row's leading columns, the
    caller's scale.  Lengths 0 (zeros by contract), 1, a block's edge and
    many blocks; pages no slot may read hold NaN."""
    A, NB, bs, W, kr, H, MB = 3, 40, 4, 128, 32, 4, 6
    lengths = LENGTHS[case]
    rng = np.random.default_rng(7)
    free = list(range(1, NB))
    rng.shuffle(free)
    tables = np.zeros((4, MB), np.int32)
    live = set()
    for s, n in enumerate(lengths):
        for p in range(-(-n // bs)):
            tables[s, p] = free.pop()
            live.add(int(tables[s, p]))
        tables[s, -(-n // bs):] = free[-1]  # never to be read
    pool = np.array(jax.random.normal(jax.random.PRNGKey(1),
                                      (A, NB, bs, W)), np.float32)
    pool[:, [b for b in range(NB) if b not in live]] = np.nan
    pool = jnp.asarray(pool, dtype)
    q = jax.random.normal(jax.random.PRNGKey(2), (4, H, W), dtype)
    lens = jnp.asarray(lengths, jnp.int32)
    scale = 24 ** -0.5
    got = latent_paged_attention(q, pool, jnp.asarray(tables), lens,
                                 layer=1, value_width=kr, scale=scale,
                                 pages_per_block=2)
    rows = jnp.nan_to_num(pool[1][jnp.asarray(tables)].reshape(4, MB * bs, W))
    scores = jnp.einsum("bhw,btw->bht", q, rows,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(MB * bs)[None, None, :] < lens[:, None, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), -1).astype(dtype)
    want = jnp.einsum("bht,btk->bhk", probs, rows[..., :kr],
                      preferred_element_type=jnp.float32)
    want = jnp.where(lens[:, None, None] > 0, want, 0).astype(dtype)
    assert got.shape == (4, H, kr) and got.dtype == dtype
    assert not bool(jnp.any(jnp.isnan(got)))
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32)))) < tol


def test_decode_attention_path_reads_the_pool(monkeypatch):
    cfg = LongcatConfig.tiny()
    tiny = init_latent_pool(cfg, 4, 8)
    real = {"kv": jnp.zeros((2, 3, 16, 640), jnp.bfloat16)}
    dense = {"k": jnp.zeros((1, 3, 16, 8, 128), jnp.bfloat16),
             "v": jnp.zeros((1, 3, 16, 8, 128), jnp.bfloat16)}
    assert decode_attention_path(real) == "gather"  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert decode_attention_path(real) == "latent_kernel"
    assert decode_attention_path(dense) == "paged_kernel"
    assert decode_attention_path(tiny) == "gather"  # 8 rows a page
    assert decode_attention_path(real, mesh=object()) == "gather"


# --------------------------------------------------- (e) the control

@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_control_reads_not_correct(seed):
    """The control of the cell's ``correct``: the reference with every
    weight product's operands rounded to float8_e4m3fn, in the program's
    place, against the program in bfloat16 (as the configuration states).
    Readings at this size (seeds 1-5, logits of magnitude 0.6): the median
    over positions of the logit error is 0.003 sound and 0.02-0.05 control.
    The median, not the largest: at toy widths the stream is the embedding
    (0.02) plus what the zero-compute experts return (their weight times a
    normed vector), so ONE pick that rounding flips moves one position's
    logits by 0.2-0.5, sound or not.  At the published widths the dense
    blocks' outputs carry the stream and a flipped pick (weight 6/768) is
    lost in it: the cell judges every returned token (PERF.md section 6)."""
    cfg = LongcatConfig.tiny(first_expert=2, held_experts=4,
                             dtype=jnp.bfloat16)
    params = longcat_init(jax.random.PRNGKey(seed), cfg)
    model = _model(LongcatConfig.tiny(first_expert=2, held_experts=4))
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 10), (1, 64), 0,
                                256)
    want = reference.logits(params, tokens[0], model)

    def readings(got):
        """Over positions, the median and the largest logit error."""
        err = jnp.max(jnp.abs(got - want), axis=-1)
        return float(jnp.median(err)), float(jnp.max(err))

    sound = readings(longcat_apply(params, tokens, cfg)[0])
    wrong = readings(reference.logits(
        params, tokens[0], dict(model, control_dtype="float8_e4m3fn")))
    assert sound[0] < 0.008 < wrong[0], (sound, wrong)
    assert wrong[0] > 3 * sound[0]


# ------------------------------------------- (f) what is not supplied

def test_unsupported_options_raise():
    cfg = LongcatConfig.tiny()
    kw = dict(tokenizer=_Ids(), batch_slots=2, max_len=32, block_size=8)
    with pytest.raises(ValueError, match="kv_dtype"):
        LLMEngine(cfg, kv_cache_dtype="int8", **kw)
    with pytest.raises(NotImplementedError, match="mesh"):
        LLMEngine(cfg, mesh=object(), **kw)
    eng = LLMEngine(cfg, **kw)
    with pytest.raises(NotImplementedError, match="handoff"):
        eng.submit([1, 2, 3], prefill_only=True)
    with pytest.raises(NotImplementedError, match="handoff"):
        eng.export_kv(0)
    with pytest.raises(NotImplementedError, match="handoff"):
        eng.adopt_prefilled({})
    with pytest.raises(NotImplementedError):
        longcat_apply(None, jnp.zeros((1, 2), jnp.int32), cfg, mesh=object())
    # a configuration nobody registered is refused by name
    from ray_tpu.models.moe import MoEConfig
    with pytest.raises(TypeError, match="MoEConfig"):
        LLMEngine(MoEConfig.tiny_moe(), **kw)
    with pytest.raises(ValueError, match="longcat_flash_tiny"):
        preset("no-such-model")


def test_presets_resolve_by_name_for_every_served_model():
    from ray_tpu.llm.serving import _build_engine
    from ray_tpu.models.llama import LlamaConfig

    assert preset("tiny") == LlamaConfig.tiny()
    assert preset("llama3_8b") == LlamaConfig.llama3_8b()
    assert preset("longcat_flash_tiny") == LongcatConfig.tiny()
    assert preset("longcat_flash").num_experts == 512
    eng = _build_engine({"model": "longcat_flash_tiny", "batch_slots": 2,
                         "max_len": 32, "block_size": 8}, 1)
    assert eng.model.name == "longcat_flash"
    assert eng.cfg.param_dtype == jnp.float32  # a tiny preset stays as it is
    assert _build_engine({"batch_slots": 2}, 1).model.name == "llama"


def test_a_replica_admits_as_many_requests_as_its_engine_has_slots():
    from ray_tpu.llm import build_llm_deployment

    def caps(kw):
        c = build_llm_deployment(kw).deployment.config
        return c.max_ongoing_requests, c.max_queued_requests

    assert caps(None) == caps({"batch_slots": 32}) == (32, 64)
    assert caps({"batch_slots": 8}) == (32, 64)
    assert caps({"batch_slots": 128, "max_len": 3584}) == (128, 256)


def test_engine_on_the_expert_kernel_decodes_what_the_grouped_path_does(
        monkeypatch):
    """The decode program forced onto ``ops/pallas/expert_decode.py``
    (interpreter; a held range, so most pairs are sentinels) returns token
    for token what the grouped path returns, with the counters the grouped
    path counts, and ``stats()["experts"]`` says which ran."""
    from ray_tpu.ops import experts

    cfg = preset("longcat_flash_tiny")
    cfg = dataclasses.replace(cfg, first_expert=2, held_experts=4)
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
    assert st["counters"]["expert_kernel_windows"] == 0
    monkeypatch.setattr(
        experts, "expert_path",
        lambda T, *a: "decode_kernel" if T == 4 else "grouped")
    st2, got = run()
    assert got == want and st2["experts"] == "decode_kernel"
    c, c2 = st["counters"], st2["counters"]
    assert c2["expert_kernel_windows"] == c2["decode_windows"] > 0
    assert {n: c2[n] for n in c if n.startswith(("moe_", "prefill_"))} \
        == {n: c[n] for n in c if n.startswith(("moe_", "prefill_"))}


def test_a_model_without_experts_has_no_experts_label():
    from ray_tpu.models.llama import LlamaConfig

    eng = LLMEngine(LlamaConfig.tiny(num_layers=1, dtype=jnp.float32),
                    tokenizer=_Ids(), batch_slots=2, max_len=32)
    st = eng.stats()
    assert st["experts"] is None
    assert "expert_kernel_windows" not in st["counters"]


# ------------------------------------- (g) the prefill's attention paths

def _kernels(jaxpr):
    """The ``pallas_call``s of a jaxpr, inner jaxprs included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _kernels(sub)
    return n


def _prefill(cfg, params, tokens, length, prefix_blocks, impl):
    """``latent_prefill_suffix`` of ``tokens [S]`` (``length`` live) behind
    ``prefix_blocks`` cached blocks of 8, as the engine calls it, on a pool
    whose rows are random (a prefix's are read): a function of nothing but
    its arguments, to run or to trace."""
    S, bs = len(tokens), 8
    pool = init_latent_pool(cfg, 24, bs)
    pool = {"kv": jax.random.normal(jax.random.PRNGKey(8), pool["kv"].shape)}
    cached = len(prefix_blocks) * bs
    pos = cached + np.arange(S)
    live = np.arange(S) < length
    dst_b = np.where(live, 8 + pos // bs, 0).astype(np.int32)
    dst_o = np.where(live, pos % bs, 0).astype(np.int32)
    prefix = gather_latent_prefix(
        pool, jnp.asarray(prefix_blocks, jnp.int32), cfg)
    return functools.partial(
        latent_prefill_suffix, cfg=cfg, attn_impl=impl), (
        params, jnp.asarray([tokens], jnp.int32), jnp.int32(length),
        jnp.int32(cached), *prefix, jnp.int32(cached), jnp.asarray(dst_b),
        jnp.asarray(dst_o), pool)


@pytest.mark.parametrize("length", [64, 50], ids=["whole", "pad-lanes"])
def test_an_uncached_prompt_through_the_flash_kernel(length):
    """No cached prefix: the flash path (forced; the interpreter) gives the
    last position's logits and the cache rows of the plain path, and both
    stay within the limit of the float32 reference.  Pad lanes at the
    bucket's tail attend differently on the two paths and are read by
    nobody: they land in the scratch block."""
    cfg = LongcatConfig.tiny()
    params = _params(cfg)
    tokens = np.random.default_rng(6).integers(0, 256, 64).tolist()
    out = {}
    for impl in ("flash", "ref"):
        fn, args = _prefill(cfg, params, tokens, length, [], impl)
        assert _kernels(jax.make_jaxpr(fn)(*args).jaxpr) == (
            2 * cfg.num_layers if impl == "flash" else 0)
        out[impl] = fn(*args)
    want = reference.logits(params, jnp.asarray(tokens[:length]),
                            _model(cfg))[-1]
    for impl, (logits, pool, stats) in out.items():
        assert float(jnp.max(jnp.abs(logits[0] - want))) < TOL, impl
    flash, plain = out["flash"], out["ref"]
    assert float(jnp.max(jnp.abs(flash[0] - plain[0]))) < TOL
    # every block but the scratch one: the rows the prompt wrote
    assert float(jnp.max(jnp.abs(
        flash[1]["kv"][:, 1:] - plain[1]["kv"][:, 1:]))) < TOL
    assert np.array_equal(flash[2], plain[2])


def test_a_prefix_hit_keeps_the_plain_path():
    """Behind cached blocks the program is the plain one whatever the rule
    for the kernel says: the prefix's mask is not causal."""
    cfg = LongcatConfig.tiny()
    params = _params(cfg)
    tokens = np.random.default_rng(6).integers(0, 256, 64).tolist()
    texts = []
    for impl in ("flash", "ref"):
        fn, args = _prefill(cfg, params, tokens, 50, [3, 4, 0, 0], impl)
        jaxpr = jax.make_jaxpr(fn)(*args)
        assert _kernels(jaxpr.jaxpr) == 0
        texts.append(str(jaxpr))
    assert texts[0] == texts[1]
    assert prefill_attention_path(64, 32, "flash") == "plain"
    assert prefill_attention_path(64, 0, "flash") == "flash"
    # the rule itself, on this backend: dot_product_attention's
    assert prefill_attention_path(1024, 0) == "plain"


class _Open:
    """One entered annotation: ``set_metadata`` lands on its own stats
    (another may open inside it: an ``xla.build`` at a compile's end)."""

    def __init__(self, stats):
        self.stats = stats

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **more):
        self.stats.update(more)


class _Spans:
    """Stands in for ``tracing.annotate`` in the engine: keeps each
    annotation's stats by name (``set_metadata`` included)."""

    def __init__(self):
        self.seen = []

    def __call__(self, name, **stats):
        self.seen.append((name, stats))
        return _Open(stats)

    def named(self, name):
        return [s for n, s in self.seen if n == name]


def test_the_engine_says_which_attention_a_prefill_ran(monkeypatch):
    """``engine.admit`` carries ``attention`` and ``stats()`` counts the
    prefills by path: flash for an uncached prompt of a bucket the rule
    gives the kernel (here: 32 tokens on, the interpreter), plain for a
    prefix hit and for a short prompt; greedy tokens are the plain
    engine's.  A model with one path reports neither key."""
    from ray_tpu.llm import engine as engine_mod
    from ray_tpu.models import longcat

    cfg = dataclasses.replace(preset("longcat_flash_tiny"), first_expert=2,
                              held_experts=4)
    rng = np.random.default_rng(3)
    shared = rng.integers(0, 256, 24).tolist()
    prompts = [shared + rng.integers(0, 256, 16).tolist(),
               shared + rng.integers(0, 256, 9).tolist(),
               rng.integers(0, 256, 5).tolist()]
    sp = SamplingParams(max_tokens=6, temperature=0.0, stop_token_id=None)

    def run():
        spans = _Spans()
        monkeypatch.setattr(engine_mod.tracing, "annotate", spans)
        eng = LLMEngine(cfg, tokenizer=_Ids(), batch_slots=4, max_len=96,
                        block_size=8, seed=5)
        outs = eng.generate(prompts[:1], sp) + eng.generate(prompts[1:], sp)
        admits = [s for s in spans.named("engine.admit")
                  if s["kind"] == "full"]
        return eng.stats(), admits, [o.token_ids for o in outs]

    st, admits, want = run()  # the CPU: dot_product_attention's rule
    assert st["prefill_attention"] == {"plain": 3}
    assert [s["attention"] for s in admits] == ["plain"] * 3
    monkeypatch.setattr(longcat, "attention_impl",
                        lambda seq: "flash" if seq >= 32 else "ref")
    st, admits, got = run()
    assert got == want
    assert st["prefill_attention"] == {"flash": 1, "plain": 2}
    assert [(s["bucket"], s["cached_tokens"], s["attention"])
            for s in admits] == [(64, 0, "flash"), (16, 24, "plain"),
                                 (8, 0, "plain")]

    from ray_tpu.models.llama import LlamaConfig

    spans = _Spans()
    monkeypatch.setattr(engine_mod.tracing, "annotate", spans)
    eng = LLMEngine(LlamaConfig.tiny(num_layers=1, dtype=jnp.float32),
                    tokenizer=_Ids(), batch_slots=2, max_len=32)
    eng.generate(prompts[2:], sp)
    assert "prefill_attention" not in eng.stats()
    admits = spans.named("engine.admit")
    assert admits and not any("attention" in s for s in admits)


# ------------------------------------------------- (h) the absorbed pair

def _blocks(params):
    return [ap for lp in params["layers"] for ap in lp["attn"]]


def test_the_absorbed_pair_is_the_two_halves_of_w_kvb():
    """``longcat_init``'s ``w_uk`` / ``w_uv`` are what ``absorbed_pair``
    makes of ``w_kvb``, and that is a slice and a transposition: every
    element of the matrix is in exactly one of the two, unchanged."""
    cfg = LongcatConfig.tiny()
    kr, nh = cfg.kv_lora_rank, cfg.num_heads
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    blocks = _blocks(longcat_init(jax.random.PRNGKey(3), cfg))
    assert len(blocks) == 2 * cfg.num_layers
    for ap in blocks:
        assert ap["w_kvb"].shape == (kr, nh * (dn + dv))
        w_uk, w_uv = absorbed_pair(ap["w_kvb"], cfg)
        np.testing.assert_array_equal(ap["w_uk"], w_uk)
        np.testing.assert_array_equal(ap["w_uv"], w_uv)
        w = np.asarray(ap["w_kvb"]).reshape(kr, nh, dn + dv)
        assert w_uk.shape == (nh, dn, kr) and w_uv.shape == (nh, kr, dv)
        np.testing.assert_array_equal(
            np.asarray(w_uk), w[..., :dn].transpose(1, 2, 0))
        np.testing.assert_array_equal(
            np.asarray(w_uv), w[..., dn:].transpose(1, 0, 2))


def _absorbed_over_w_kvb(q_nope, q_pe, ap, cfg, attend_rows):
    """``_mla_absorbed`` as it was until PR 45: both products over strided
    halves of the one leaf."""
    b, nh, dn = q_nope.shape
    dt, kr, dv = cfg.dtype, cfg.kv_lora_rank, cfg.v_head_dim
    w_kvb = ap["w_kvb"].astype(dt).reshape(kr, nh, dn + dv)
    q_lat = jnp.einsum("bhd,khd->bhk", q_nope, w_kvb[..., :dn],
                       preferred_element_type=jnp.float32).astype(dt)
    pad = cfg.latent_width - kr - q_pe.shape[-1]
    o_lat = attend_rows(jnp.concatenate(
        [q_lat, q_pe, jnp.zeros((b, nh, pad), dt)], axis=-1))
    out = jnp.einsum("bhk,khd->bhd", o_lat, w_kvb[..., dn:],
                     preferred_element_type=jnp.float32).astype(dt)
    return out.reshape(b, nh * dv) @ ap["w_o"].astype(dt)


def _decode(params, cfg, tokens, attn, block_size=4):
    """``tokens`` through ``latent_decode_step`` one at a time beside a
    freed slot: the last step's logits for the live slot."""
    n = len(tokens)
    pool = init_latent_pool(cfg, 1 + -(-n // block_size), block_size)
    tables = jnp.zeros((2, -(-n // block_size)), jnp.int32).at[0].set(
        jnp.arange(1, 1 + -(-n // block_size)))
    step = jax.jit(functools.partial(latent_decode_step, cfg=cfg, attn=attn))
    for pos in range(n):
        logits, pool, _ = step(
            params, jnp.asarray([tokens[pos], 7], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32), tables, pool)
    return logits[0]


@pytest.mark.parametrize("attn", ["gather", "latent_kernel"])
def test_a_decode_step_over_the_pair_is_the_step_over_w_kvb(
        attn, monkeypatch):
    """Float32, through the gathered path and through the latent kernel
    (interpreter; pages of 16 rows): the same logits to float32's rounding,
    far inside ``TOL``.  Not to the last bit at this size: the products are
    the parent's multiplications, but for two slots and 16-wide heads
    XLA:CPU unrolls a dot itself and adds along the operands' memory
    order, which the pair's layout changes (the test below has the size
    at which it does not)."""
    cfg = LongcatConfig.tiny(first_expert=2, held_experts=4)
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (21,), 0, 256)
    got = _decode(params, cfg, tokens, attn, block_size=16)
    monkeypatch.setattr(longcat, "_mla_absorbed", _absorbed_over_w_kvb)
    want = _decode(params, cfg, tokens, attn, block_size=16)
    assert got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) < 1e-6  # logits of ~0.6
    ref = reference.logits(params, tokens, _model(cfg))[-1]
    assert float(jnp.max(jnp.abs(got - ref))) < TOL


def test_the_absorbed_products_are_the_ones_over_w_kvb_bit_for_bit():
    """``_mla_absorbed`` against the spelling over ``w_kvb``'s halves, 64
    slots of 4 heads 64 wide over a latent space of 128: from here on
    XLA:CPU hands both spellings to its one matrix product routine, and
    the two products and ``W_o`` after them agree to the last bit."""
    cfg = LongcatConfig.tiny(qk_nope_head_dim=64, v_head_dim=64,
                             kv_lora_rank=128)
    ap = _blocks(longcat_init(jax.random.PRNGKey(1), cfg))[0]
    q_nope, q_pe = (jax.random.normal(jax.random.PRNGKey(k), (64, 4, d))
                    for k, d in ((2, 64), (3, cfg.qk_rope_head_dim)))

    def through(absorbed):
        return jax.jit(lambda *a: absorbed(
            *a, cfg, lambda q: jnp.tanh(q[..., :128])))(q_nope, q_pe, ap)

    got, want = through(longcat._mla_absorbed), through(_absorbed_over_w_kvb)
    assert got.shape == (64, cfg.hidden_size) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(want))) > 0.01
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_a_replaced_w_kvb_decodes_once_the_pair_is_derived_again():
    """What a checkpoint loader does: read ``kv_b_proj`` into ``w_kvb``,
    then ``absorbed_pair``.  The decode step follows the new matrix (the
    reference reads ``w_kvb`` alone); with the pair left stale it does
    not."""
    cfg = LongcatConfig.tiny(first_expert=2, held_experts=4)
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (13,), 0, 256)
    for i, ap in enumerate(_blocks(params)):
        ap["w_kvb"] = 0.02 * jax.random.normal(
            jax.random.PRNGKey(40 + i), ap["w_kvb"].shape)
    want = reference.logits(params, tokens, _model(cfg))[-1]
    stale = _decode(params, cfg, tokens, "gather")
    assert float(jnp.max(jnp.abs(stale - want))) > 100 * TOL
    for ap in _blocks(params):
        ap["w_uk"], ap["w_uv"] = absorbed_pair(ap["w_kvb"], cfg)
    got = _decode(params, cfg, tokens, "gather")
    assert float(jnp.max(jnp.abs(got - want))) < TOL
