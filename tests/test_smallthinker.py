"""SmallThinker's decoder: the program against its plain reference, and the
engine's two pools.

CPU, float32, tiny shapes (``smallthinker_tiny``: two periods of full,
window, window, window; a window of 8 = two blocks of 4).  The reference is
the benchmark's (``cells/families/smallthinker_reference.py``: written from
the published architecture, importing nothing of the program): one set of
equations for these tests and for the cell's ``correct``.

(a) prefill, then decode through ``LLMEngine``'s two pools to five times
    the window, on logits against the reference's full forward pass;
(b) both block managers pass ``assert_integrity`` after decode past the
    window, after ``abort`` and after a preemption, and the window type
    holds at most ``_window_blocks`` blocks a slot;
(c) the windowed flash forward (Pallas interpreter) against
    ``reference_attention(window=...)``, and ``window=None`` bit-equal to
    the kernel without the argument;
(d) ``route_top_k(renormalise=True)`` and the ReGLU dispatch against a loop
    over tokens and picks;
(e) four shares of the experts add up to the uncut reference layer;
(f) what a model with a window type does not take raises by name.

Tolerances: float32 on both sides, different orders of summation (grouped
products against a loop over experts, blocks of queries against a whole
score matrix): logits of magnitude ~0.3 agree to 2e-5.  A returned token's
gap under the reference's largest logit is 0 unless two logits tie to 2e-5.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cells.families import smallthinker_reference as reference
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import smallthinker as st
from ray_tpu.llm import SamplingParams
from ray_tpu.models.served import preset, served_model
from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.experts import held_experts_ffn, reglu, route_top_k
from ray_tpu.ops.pallas import flash_attention as fa

TOL = 2e-5


def _model(cfg):
    """The configuration as the reference takes it: a plain dict."""
    return dataclasses.asdict(cfg)


class _Ids:
    """Token ids in, token ids out."""
    eos_id = None
    vocab_size = 256

    def encode(self, text):
        return [1]

    def decode(self, ids):
        return ""


def _engine(**kw):
    cfg = preset("smallthinker_tiny")
    kw = dict(dict(tokenizer=_Ids(), batch_slots=4, max_len=96, block_size=4,
                   decode_window=4, seed=5), **kw)
    return cfg, LLMEngine(cfg, **kw)


def _integrity(eng):
    for p in eng._pools:
        p.blocks.assert_integrity()


def _window_rows_are_bounded(eng):
    """No slot holds more of the window type's blocks than the window, a
    decode window ahead of it and the two blocks the ends cut, beside the
    rest of the run it was last handed."""
    window = eng._pools[1]
    most = eng._window_blocks(window.window)
    assert most == 8 // 4 + 1 + 1  # window / block + 1, and the one ahead
    held = (window.tables != 0).sum(axis=1)
    assert held.max() <= most + window.blocks.run - 1, held
    return held


# ---------------------------------------- (a) the engine, past the window

def test_forward_matches_the_plain_reference():
    cfg = st.SmallThinkerConfig.tiny(first_expert=2, held_experts=4)
    params = st.smallthinker_init(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, 256)
    got, stats = st.smallthinker_apply(params, tokens, cfg,
                                       return_stats=True)
    for i in range(2):
        want = reference.logits(params, tokens[i], _model(cfg))
        assert float(jnp.max(jnp.abs(got[i] - want))) < TOL
    # and the reference notices a wrong model: every layer a full one
    other = reference.logits(params, tokens[0],
                             dict(_model(cfg), layer_period=["full"]))
    assert float(jnp.max(jnp.abs(other - got[0]))) > 100 * TOL
    pairs, hit, zero = (int(s) for s in stats)
    assert 0 < pairs < 8 * 80 * 3 and 0 < hit <= 8 * 4 and zero == 0


def test_engine_decodes_through_two_pools_past_the_window():
    cfg, eng = _engine()
    assert eng.model is served_model(cfg)
    assert set(eng.pool) == {"full", "window"} and eng.attn == "gather"
    assert [(p.name, p.layers, p.window) for p in eng._pools] == [
        ("full", 2, None), ("window", 6, 8)]
    # the window type's default pool: what four slots can ever hold
    assert eng.num_blocks == {"full": 4 * 24 + 1, "window": 4 * 4 + 1}
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).tolist() for n in (5, 20, 33, 9, 14)]
    sp = SamplingParams(max_tokens=40, temperature=0.0, stop_token_id=None)
    outs = eng.generate(prompts, sp)
    st_ = eng.stats()
    assert st_["model"] == "smallthinker"
    assert st_["pools"]["window"] == {"total": 16, "available": 16,
                                      "held": 0}
    assert st_["blocks_total"] == 96 + 16 == st_["blocks_available"]
    assert st_["prefix_cache"]["prefix_hits"] == 0
    c = st_["counters"]
    assert c["window_blocks_released"] > 20 and c["moe_zero_picks"] == 0
    assert c["moe_pairs_held"] > 0 and c["prefill_calls"] == len(prompts)
    _integrity(eng)
    for prompt, out in zip(prompts, outs):
        assert len(out.token_ids) == 40  # five times the window
        seq = jnp.asarray(prompt + out.token_ids)
        lg = reference.logits(eng.params, seq[:-1], _model(cfg))
        rows = lg[len(prompt) - 1:]
        chosen = jnp.take_along_axis(rows, seq[len(prompt):, None], -1)[:, 0]
        assert float(jnp.max(jnp.max(rows, -1) - chosen)) < TOL


def test_decode_step_logits_match_the_reference_with_blocks_given_back():
    """Token by token through the two pools on logits, the window type's
    table holding only what ``_release_behind_window`` would leave; a freed
    slot is routed nowhere and not counted."""
    cfg = st.SmallThinkerConfig.tiny()
    params = st.smallthinker_init(jax.random.PRNGKey(7), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (30,), 0, 256)
    pool = st.init_pools(cfg, {"full": 12, "window": 12}, 4)
    blocks = np.arange(1, 9, dtype=np.int32)
    step = jax.jit(functools.partial(st.decode_step, cfg=cfg))
    want = reference.logits(params, tokens, _model(cfg))
    for pos in range(30):
        kept = blocks.copy()
        kept[:max(0, pos + 1 - cfg.sliding_window) // 4] = 0
        tables = {"full": jnp.asarray([blocks, 0 * blocks]),
                  "window": jnp.asarray([kept, 0 * blocks])}
        logits, pool, stats = step(
            params, jnp.asarray([tokens[pos], 7], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32), tables, pool)
        assert float(jnp.max(jnp.abs(logits[0] - want[pos]))) < TOL
        assert int(stats[0]) == 8 * 3  # the live slot's 8 layers x 3 picks


# ------------------------------------------- (b) the two block managers

def test_block_managers_keep_their_books_through_abort_and_preemption():
    cfg, eng = _engine(num_blocks={"full": 30, "window": 13})
    rng = np.random.default_rng(1)
    sp = SamplingParams(max_tokens=48, temperature=0.0, stop_token_id=None)
    ids = [eng.submit(rng.integers(0, 256, n).tolist(), sp)
           for n in (21, 6, 30, 11)]
    held_most = 0
    aborted = False
    outs = {}
    while eng.has_unfinished():
        for out in eng.step():
            outs[out.request_id] = out
        _integrity(eng)
        held_most = max(held_most, int(_window_rows_are_bounded(eng).max()))
        # the window type never holds a block wholly behind the window
        for i, req in enumerate(eng._slots):
            if req is not None and not req.done:
                dead = max(0, int(eng._cur_len[i]) + 1 - 8) // 4
                assert not eng._pools[1].tables[i, :dead].any()
                assert req.more_blocks[0][:dead] == [0] * dead
        if not aborted and eng._cur_len.max() > 40:
            assert eng.abort(ids[1])
            aborted = True
    assert held_most == 4
    # 29 blocks of the full type cannot hold four sequences of up to 78
    assert eng.blocks.stats["preemptions"] >= 1
    assert len(outs[ids[1]].token_ids) < 48  # cut short by the abort
    for i in (0, 2, 3):
        assert len(outs[ids[i]].token_ids) == 48 and not outs[ids[i]].error
    _integrity(eng)
    for p in eng._pools:
        assert p.blocks.available() == p.blocks.num_blocks - 1
        assert not p.tables.any()
    # a preempted request's answer is still the reference's choice
    seq = jnp.asarray(outs[ids[2]].prompt_tokens + outs[ids[2]].token_ids)
    lg = reference.logits(eng.params, seq[:-1], _model(cfg))[29:]
    chosen = jnp.take_along_axis(lg, seq[30:, None], -1)[:, 0]
    assert float(jnp.max(jnp.max(lg, -1) - chosen)) < TOL


def test_a_long_prompt_is_admitted_into_its_last_window_of_blocks():
    cfg, eng = _engine()
    prompt = np.random.default_rng(2).integers(0, 256, 61).tolist()
    eng.submit(prompt, SamplingParams(max_tokens=12, temperature=0.0,
                                      stop_token_id=None))
    eng._carries = lambda: False
    eng.step()  # admission, the first token and one window of 4
    full, window = (p.tables[0] for p in eng._pools)
    cur = int(eng._cur_len[0])
    ahead = -(-17 // eng.blocks.run) * eng.blocks.run  # a run held ahead
    assert cur == 65 and full[:17].all() and not full[ahead:].any()
    dead = (cur + 1 - 8) // 4
    assert not window[:dead].any() and window[dead:17].all()
    # the 13 blocks before the prompt's last window were never allocated:
    # what has been given back is what the one decode window left behind
    assert eng.counters["window_blocks_released"] == dead - 13 == 1
    assert _window_rows_are_bounded(eng)[0] == 3 + ahead - 17
    while eng.has_unfinished():
        eng.step()
    _integrity(eng)


def test_a_pool_too_small_for_one_sequence_fails_the_request_by_name():
    cfg, eng = _engine(num_blocks={"full": 97, "window": 4})
    out = eng.generate([[3] * 20], SamplingParams(max_tokens=30))[0]
    assert "cannot hold one sequence" in out.error
    _integrity(eng)


# ------------------------------------------ (c) the windowed flash forward

@pytest.mark.parametrize("seq,window", [
    (96, 128), (256, 256), (300, 128), (640, 256), (700, 100)],
    ids=["under", "at", "padded-tail", "several-blocks-over", "unaligned"])
def test_windowed_flash_forward_matches_the_reference(seq, window):
    ks = jax.random.split(jax.random.PRNGKey(seq), 3)
    q = jax.random.normal(ks[0], (1, seq, 4, 128), jnp.float32)
    k = jax.random.normal(ks[1], (1, seq, 2, 128), jnp.float32)
    v = jax.random.normal(ks[2], (1, seq, 2, 128), jnp.float32)
    got = fa.flash_attention(q, k, v, window=window, block_q=128,
                             block_k=128)
    want = reference_attention(q, k, v, window=window)
    assert float(jnp.max(jnp.abs(got - want))) < 5e-6
    # and the argument left out is the kernel of before, bit for bit
    plain = fa.flash_attention(q, k, v, block_q=128, block_k=128)
    explicit = fa._flash(q, k, v, True, 128, 128, True, None)
    assert bool(jnp.all(plain == explicit))
    assert float(jnp.max(jnp.abs(plain - reference_attention(q, k, v)))) \
        < 5e-6
    if window < seq:
        assert float(jnp.max(jnp.abs(plain - got))) > 1e-3


def test_window_none_traces_the_program_of_before():
    """``window=None`` adds nothing to what is traced: the kernel's jaxpr
    is that of a call that never heard of the argument."""
    q = jnp.zeros((1, 512, 4, 128), jnp.bfloat16)
    k = jnp.zeros((1, 512, 2, 128), jnp.bfloat16)

    def text(**kw):
        return str(jax.make_jaxpr(functools.partial(
            fa._flash_fwd_impl, causal=True, block_q=256, block_k=256,
            interpret=False, **kw))(q, k, k))

    assert text() == text(window=None)
    assert text() != text(window=300)


def test_the_flash_backward_refuses_a_window_by_name():
    q = jnp.ones((1, 128, 2, 128), jnp.float32)
    with pytest.raises(NotImplementedError, match="no sliding window"):
        jax.grad(lambda x: fa.flash_attention(x, q, q, window=64).sum())(q)
    with pytest.raises(ValueError, match="requires causal"):
        fa.flash_attention(q, q, q, causal=False, window=64)


# ----------------------------------------- (d) the router and the dispatch

def test_renormalised_router_and_reglu_dispatch_against_a_loop():
    T, H, N, F, k = 24, 32, 8, 16, 3
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    y = jax.random.normal(ks[0], (T, H))
    w_r = jax.random.normal(ks[1], (H, N))
    gate, up = (jax.random.normal(ks[i], (N, H, F)) for i in (2, 3))
    down = jax.random.normal(ks[4], (N, F, H))
    idx, weight = route_top_k(y, w_r, None, k, 1.0, renormalise=True)
    plain_idx, plain_w = route_top_k(y, w_r, jnp.zeros(N), k, 1.0)
    p = jax.nn.softmax(y @ w_r, axis=-1)
    assert bool(jnp.all(idx == plain_idx))
    assert float(jnp.max(jnp.abs(jnp.sum(weight, -1) - 1.0))) < 1e-6
    assert float(jnp.max(jnp.abs(
        weight - plain_w / plain_w.sum(-1, keepdims=True)))) < 1e-6
    assert float(jnp.max(jnp.abs(
        plain_w - jnp.take_along_axis(p, idx, -1)))) < 1e-6
    got, pairs, hit = held_experts_ffn(y, idx, weight, gate, up, down,
                                       first=0, activation=reglu)
    want = np.zeros((T, H), np.float32)
    for t in range(T):  # one by one
        for j in range(k):
            e = int(idx[t, j])
            h = np.maximum(np.asarray(y[t] @ gate[e]), 0) * np.asarray(
                y[t] @ up[e])
            want[t] += float(weight[t, j]) * (h @ np.asarray(down[e]))
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4  # sums of ~50
    assert int(pairs) == T * k and 0 < int(hit) <= N
    swiglu_out, _, _ = held_experts_ffn(y, idx, weight, gate, up, down,
                                        first=0)
    assert float(jnp.max(jnp.abs(swiglu_out - got))) > 1.0


# ------------------------------------------------- (e) the shares add up

def test_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    """16 experts in 4 shares of 4 (``first_expert`` 0 / 4 / 8 / 12): every
    share routes over all 16 and computes its own four experts' part; the
    parts add up to what the uncut reference gives for the layer."""
    whole = st.SmallThinkerConfig.tiny(num_layers=1, num_experts=16,
                                       experts_per_token=6)
    lp = st.smallthinker_init(jax.random.PRNGKey(11), whole)["layers"][0]
    # experts large enough that their part is of the stream's own size
    lp["experts"] = jax.tree.map(lambda a: 8.0 * a, lp["experts"])
    model = _model(whole)
    x = jax.random.normal(jax.random.PRNGKey(12), (1, 24, 64))
    live = jnp.ones((1, 24), bool)
    uncut = reference.layer(x[0], lp, "full", model)
    none = reference.layer(x[0], dict(lp, experts=dict(
        lp["experts"], w_down=0 * lp["experts"]["w_down"])), "full", model)
    assert float(jnp.max(jnp.abs(uncut - none))) > 0.3
    total, picks = jnp.zeros_like(none), 0
    for c in range(4):
        cfg_c = dataclasses.replace(whole, first_expert=4 * c, held_experts=4)
        lp_c = dict(lp, experts=jax.tree.map(lambda a: a[4 * c:4 * c + 4],
                                             lp["experts"]))
        got, stats = st._layer(
            x, lp_c, st.FULL, cfg_c, None, None, None,
            lambda q, k, v: reference_attention(q, k, v), live)
        want = reference.layer(x[0], lp_c, "full", _model(cfg_c))
        assert float(jnp.max(jnp.abs(got[0] - want))) < TOL
        total += got[0] - none  # this share's experts' part
        picks += int(stats[0])
    assert picks == 24 * 6  # every pick landed on exactly one share
    assert float(jnp.max(jnp.abs(none + total - uncut))) < TOL


# ------------------------------------------------- (f) what is left out

def test_a_model_with_a_window_type_refuses_what_it_does_not_take():
    cfg = preset("smallthinker_tiny")
    with pytest.raises(NotImplementedError, match="prefill_chunk"):
        LLMEngine(cfg, tokenizer=_Ids(), max_len=64, block_size=4,
                  prefill_chunk=16)
    _, eng = _engine()
    prompt = list(range(40))
    sp = SamplingParams(max_tokens=4, temperature=0.0, stop_token_id=None)
    first = eng.generate([prompt], sp)[0]
    again = eng.generate([prompt + [7, 8]], sp)[0]  # no hit on its prefix
    assert eng.blocks.stats["prefix_hits"] == 0
    assert eng.blocks.stats["prefix_blocks_reused"] == 0
    assert not eng.blocks.by_key and not eng.blocks.lru
    assert len(first.token_ids) == len(again.token_ids) == 4
    with pytest.raises(NotImplementedError, match="prefill_only|handoff"):
        eng.submit(prompt, sp, prefill_only=True)
    # the programs say so themselves, never a wrong answer
    with pytest.raises(NotImplementedError, match="no prefix hits"):
        eng.model.gather_prefix(eng.pool, jnp.zeros((2,), jnp.int32), cfg)
    some = jnp.zeros((8, 4, 2, 16))
    with pytest.raises(NotImplementedError, match="no cached prefix"):
        eng.model.prefill_suffix(
            eng.params, jnp.zeros((1, 8), jnp.int32), 8, 4, some, some, 4,
            None, None, eng.pool, cfg=cfg)
    with pytest.raises(ValueError, match="at least one 'full'"):
        st.SmallThinkerConfig.tiny(layer_period=("window",))


# ------------------------------ (g) the expert layer's two paths (PR 32)

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


def test_engine_on_the_expert_kernel_decodes_what_the_grouped_path_does(
        monkeypatch):
    """The decode program forced onto ``ops/pallas/expert_decode.py``
    (interpreter) returns token for token what the grouped path returns,
    and the engine says which ran: ``stats()["experts"]``, the
    ``engine.dispatch_window`` / ``engine.admit`` stat, the counter."""
    from ray_tpu.llm import engine as engine_mod
    from ray_tpu.ops import experts

    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (5, 19, 9)]
    sp = SamplingParams(max_tokens=12, temperature=0.0, stop_token_id=None)

    def run():
        spans = _Spans()
        monkeypatch.setattr(engine_mod.tracing, "annotate", spans)
        cfg, eng = _engine()
        outs = eng.generate(prompts, sp)
        return eng, spans, [o.token_ids for o in outs]

    eng, spans, want = run()  # the CPU backend: grouped
    assert eng.experts == eng.stats()["experts"] == "grouped"
    assert eng.stats()["counters"]["expert_kernel_windows"] == 0
    windows = spans.named("engine.dispatch_window")
    assert windows and all(s["experts"] == "grouped" for s in windows)
    # the decode program alone (4 slots a step) takes the kernel
    monkeypatch.setattr(
        experts, "expert_path",
        lambda T, *a: "decode_kernel" if T == 4 else "grouped")
    eng, spans, got = run()
    assert got == want
    st_ = eng.stats()
    assert eng.experts == st_["experts"] == "decode_kernel"
    c = st_["counters"]
    assert c["expert_kernel_windows"] == c["decode_windows"] > 0
    assert c["moe_pairs_held"] > 0 and c["moe_experts_hit"] > 0
    windows = spans.named("engine.dispatch_window")
    assert windows and all(s["experts"] == "decode_kernel" for s in windows)
    admits = [s for s in spans.named("engine.admit") if s["kind"] == "full"]
    assert len(admits) == 3
    assert all(s["experts"] == "grouped" and s["bucket"] > 4 for s in admits)
