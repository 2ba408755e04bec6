"""Phi-4-mini-flash's decoder (``phi4flash``): the program against its plain
reference, and the engine's three pools (full, window, state records).

CPU, float32, tiny shapes (``phi4flash_tiny``: 2 pairs (state-space,
window), the pair (state-space, full), 1 pair (memory unit, cross-attention);
a window of 8 = two blocks of 4).  The reference is the benchmark's
(``cells/families/phi4flash_reference.py``: written from the published
architecture, importing nothing of the program): one set of equations for
these tests and for the cell's ``correct``.

(a) the building blocks of ``ops/ssm.py`` against loops written out, and the
    ``scale`` the attention ops gained;
(b) the forward, and prefill then decode through the three pools, on logits
    against the reference's full forward pass; the pages' write, a slab a
    token (PR 40), against a row-by-row write, bit for bit;
(c) a record's life: the same prompt in two buckets leaves the same record,
    a reused slot and a preempted request answer as the reference does, the
    books of every pool and of the state type are back at zero;
(d) what the model does not take raises by name;
(e) what the engine says (spans, counters, ``stats``) and the name scopes.

Tolerances: float32 on both sides, different orders of summation (a chunked
scan against a loop over positions, a padded query against two products):
logits of magnitude ~0.6 agree to 2e-5.  A returned token's gap under the
reference's largest logit is 0 unless two logits tie to 2e-5.  bfloat16 in
place of the float32 stated here misses the first by a factor of more than
100 (the last test of (b)).
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cells.families import phi4flash_reference as reference
from ray_tpu._private import tracing
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.models import phi4flash as pf
from ray_tpu.llm import SamplingParams
from ray_tpu.models.served import preset, served_model
from ray_tpu.ops import ssm
from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.pallas import flash_attention as fa
from ray_tpu.ops.pallas.paged_attention import paged_attention

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
    cfg = preset("phi4flash_tiny")
    kw = dict(dict(tokenizer=_Ids(), batch_slots=4, max_len=96, block_size=4,
                   decode_window=4, seed=5), **kw)
    return cfg, LLMEngine(cfg, **kw)


def _integrity(eng):
    for p in eng._pools:
        p.blocks.assert_integrity()


def _gap(eng, cfg, prompt, ids):
    """The largest distance of a returned token's reference logit under its
    position's largest."""
    seq = jnp.asarray(list(prompt) + list(ids))
    rows = reference.logits(eng.params, seq[:-1], _model(cfg))[
        len(prompt) - 1:]
    chosen = jnp.take_along_axis(rows, seq[len(prompt):, None], -1)[:, 0]
    return float(jnp.max(jnp.max(rows, -1) - chosen))


GREEDY = functools.partial(SamplingParams, temperature=0.0,
                           stop_token_id=None)


# ------------------------------------------------- (a) the building blocks

def test_layer_norm_subtracts_the_mean_and_adds_a_bias():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 32)) * 2 + 1
    scale = jnp.linspace(0.5, 1.5, 32)
    bias = jnp.linspace(-1, 1, 32)
    got = ssm.layer_norm(x, scale, bias, 1e-5)
    want = (x - x.mean(-1, keepdims=True)) / np.sqrt(
        np.asarray(x).var(-1, keepdims=True) + 1e-5) * scale + bias
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


@pytest.mark.parametrize("length", [1, 2, 5, 11, 16])
def test_the_convolution_carries_its_tail_at_the_true_length(length):
    """A sequence cut in two gives what the whole gives, and a padded
    bucket leaves the tail of the true length."""
    K, I, S = 4, 6, 16
    key = jax.random.PRNGKey(length)
    x = jax.random.normal(key, (2, S, I))
    w = jax.random.normal(jax.random.fold_in(key, 1), (K, I))
    b = jax.random.normal(jax.random.fold_in(key, 2), (I,))
    zeros = jnp.zeros((2, K - 1, I))
    whole, tail_end = ssm.causal_conv1d(x, w, b, zeros)
    xp = np.pad(np.asarray(x), ((0, 0), (K - 1, 0), (0, 0)))
    want = sum(xp[:, j:j + S] * np.asarray(w[j]) for j in range(K)) + b
    assert float(jnp.max(jnp.abs(whole - want))) < 1e-5
    # padded: everything past ``length`` is another sequence's rubbish
    padded = x.at[:, length:].set(7.0)
    head, tail = ssm.causal_conv1d(padded, w, b, zeros, jnp.int32(length))
    assert float(jnp.max(jnp.abs(head[:, :length] - whole[:, :length]))) \
        < 1e-5
    assert np.array_equal(np.asarray(tail), xp[:, length:length + K - 1])
    if length < S:  # and the rest, from the tail on, is the whole's
        rest, tail2 = ssm.causal_conv1d(x[:, length:], w, b, tail)
        assert float(jnp.max(jnp.abs(rest - whole[:, length:]))) < 1e-5
        assert np.array_equal(np.asarray(tail2), np.asarray(tail_end))


def _scan_args(key, b, s, I, N):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (b, s, I))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, I)) - 2)
    A = -jnp.exp(jax.random.normal(ks[2], (N, I)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, N))
    C = jax.random.normal(ks[4], (b, s, N))
    D = jax.random.normal(ks[5], (I,))
    return x, dt, A, B, C, D


@pytest.mark.parametrize("s,length", [(16, 16), (32, 19), (40, 3),
                                      (48, 48)])
def test_the_scan_is_the_recurrence_and_stops_at_the_true_length(s, length):
    b, I, N = 2, 5, 3
    x, dt, A, B, C, D = _scan_args(jax.random.PRNGKey(s), b, s, I, N)
    state0 = jax.random.normal(jax.random.PRNGKey(9), (b, N, I))
    y, state = ssm.selective_scan(x, dt, A, B, C, D, state0,
                                  jnp.int32(length))
    st = np.asarray(state0, np.float64)
    for t in range(length):  # the recurrence, written out
        d = np.asarray(dt[:, t], np.float64)
        xt = np.asarray(x[:, t], np.float64)
        st = np.exp(d[:, None] * np.asarray(A)[None]) * st \
            + (d * xt)[:, None] * np.asarray(B[:, t])[:, :, None]
        yt = (st * np.asarray(C[:, t])[:, :, None]).sum(1) + np.asarray(D) * xt
        assert np.abs(np.asarray(y[:, t]) - yt).max() < 1e-4
    assert np.abs(np.asarray(state) - st).max() < 1e-4
    # the same prompt in a larger bucket: the same state, bit for bit
    pad = lambda a: jnp.pad(a, ((0, 0), (0, 16), (0, 0)),  # noqa: E731
                            constant_values=3.0)
    _, again = ssm.selective_scan(pad(x), pad(dt), A, pad(B), pad(C), D,
                                  state0, jnp.int32(length))
    assert np.array_equal(np.asarray(again), np.asarray(state))


def test_one_update_is_one_position_of_the_scan():
    x, dt, A, B, C, D = _scan_args(jax.random.PRNGKey(1), 3, 16, 4, 2)
    state = jnp.zeros((3, 2, 4))
    y_all, end = ssm.selective_scan(x, dt, A, B, C, D, state)
    for t in range(16):
        y, state = ssm.selective_update(x[:, t], dt[:, t], A, B[:, t],
                                        C[:, t], D, state)
        # the same arithmetic, fused another way: the last bit may differ
        assert np.abs(np.asarray(y) - np.asarray(y_all[:, t])).max() < 1e-6
    assert np.abs(np.asarray(state) - np.asarray(end)).max() < 1e-6


@pytest.mark.parametrize("window", [None, 24])
def test_the_attention_ops_take_a_scale_of_their_own(window):
    """``reference_attention``, the flash forward (interpreter) and the
    paged kernel's dense arm (interpreter) at a scale that is not
    ``head_dim ** -0.5``; without the argument all three are what they
    were."""
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (1, 40, 4, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 40, 2, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 40, 2, 32))
    want = reference_attention(q * (0.25 / 32 ** -0.5), k, v, window=window)
    got = reference_attention(q, k, v, window=window, scale=0.25)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    flash = fa.flash_attention(q, k, v, window=window, scale=0.25,
                               block_q=16, block_k=16)
    assert float(jnp.max(jnp.abs(flash - want))) < 1e-5
    assert np.array_equal(
        np.asarray(reference_attention(q, k, v, window=window)),
        np.asarray(reference_attention(q, k, v, window=window,
                                       scale=32 ** -0.5)))
    # the paged kernel: one query (the last position) over 10 pages of 4
    pool_k = k[0].reshape(1, 10, 4, 2, 32)
    pool_v = v[0].reshape(1, 10, 4, 2, 32)
    tables = jnp.arange(10, dtype=jnp.int32)[None]
    out = paged_attention(q[:, -1], pool_k, pool_v, tables,
                          jnp.asarray([40], jnp.int32), layer=0,
                          window=window, scale=0.25, pages_per_block=2)
    assert float(jnp.max(jnp.abs(out - want[:, -1]))) < 1e-5
    # and with the pools handed over as pages, [L, NB, bs * KVH, hd]
    paged = paged_attention(q[:, -1], pool_k.reshape(1, 10, 8, 32),
                            pool_v.reshape(1, 10, 8, 32), tables,
                            jnp.asarray([40], jnp.int32), layer=0,
                            window=window, scale=0.25, kv_heads=2,
                            pages_per_block=2)
    assert np.array_equal(np.asarray(paged), np.asarray(out))


# --------------------------------- (b) the forward and the three pools

def test_forward_matches_the_plain_reference():
    cfg = pf.Phi4FlashConfig.tiny()
    assert cfg.layer_kinds == ("ssm", "window", "ssm", "window", "ssm",
                               "full", "gmu", "cross")
    assert tuple(reference.kinds(_model(cfg))) == cfg.layer_kinds
    params = pf.phi4flash_init(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, 256)
    got = pf.phi4flash_apply(params, tokens, cfg)
    for i in range(2):
        want = reference.logits(params, tokens[i], _model(cfg))
        assert float(jnp.max(jnp.abs(got[i] - want))) < TOL
    # and the reference notices a wrong model: no window, another epsilon
    for wrong in (dict(sliding_window=40), dict(layer_norm_eps=0.3)):
        other = reference.logits(params, tokens[0],
                                 dict(_model(cfg), **wrong))
        assert float(jnp.max(jnp.abs(other - got[0]))) > 100 * TOL


def test_the_published_layout_and_its_parameter_count():
    cfg = pf.Phi4FlashConfig()
    kinds = cfg.layer_kinds
    assert len(kinds) == 32 and cfg.inner_size == 5120
    assert kinds[:16] == ("ssm", "window") * 8
    assert kinds[16:18] == ("ssm", "full")
    assert kinds[18:] == ("gmu", "cross") * 7
    assert pf.layer_types(cfg) == {
        "full": {"layers": 1, "window": None, "readers": 8},
        "window": {"layers": 8, "window": 512},
        "state": {"layers": 9, "window": None, "state": True}}
    shapes = jax.eval_shape(functools.partial(pf.phi4flash_init, cfg=cfg),
                            jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    by_kind = {}
    for kind, lp in zip(kinds, shapes["layers"]):
        by_kind[kind] = sum(a.size for a in jax.tree.leaves(lp["mix"]))
        assert sum(a.size for a in jax.tree.leaves(lp["mlp"])) == 78_643_200
    assert by_kind == {"ssm": 41_241_600, "window": 19_668_864,
                       "full": 19_668_864, "gmu": 26_214_400,
                       "cross": 13_112_704}
    # the issue's 3 852 557 824 and the final LayerNorm it left out
    assert sum(a.size for a in jax.tree.leaves(shapes)) \
        == 3_852_557_824 + 2 * 2560


def test_engine_decodes_through_three_pools_past_the_window():
    cfg, eng = _engine()
    assert eng.model is served_model(cfg)
    assert set(eng.pool) == {"full", "window", "state"}
    assert eng.attn == "gather"
    assert [(p.name, p.layers, p.readers, p.window, p.state)
            for p in eng._pools] == [
        ("full", 1, 2, None, False), ("window", 2, 2, 8, False),
        ("state", 3, 3, None, True)]
    # the defaults: what four slots can ever hold, a record a slot
    assert eng.num_blocks == {"full": 4 * 24 + 1, "window": 4 * 4 + 1,
                              "state": 4 + 1}
    assert eng.pool["state"]["ssm"].shape == (3, 5, 4, 128)
    assert eng.pool["window"]["k"].shape == (2, 17, 4 * 2, 32)  # pages
    assert eng.pool["state"]["ssm"].dtype == jnp.float32
    assert [p.tables.shape for p in eng._pools] == [(4, 24), (4, 24), (4, 1)]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).tolist() for n in (5, 20, 33, 9, 14)]
    outs = eng.generate(prompts, GREEDY(max_tokens=40))
    st_ = eng.stats()
    assert st_["model"] == "phi4flash"
    assert st_["pools"]["state"] == {"total": 4, "available": 4, "held": 0}
    assert st_["pools"]["window"] == {"total": 16, "available": 16,
                                      "held": 0}
    # the blocks are the pools of positions; records are not blocks
    assert st_["blocks_total"] == 96 + 16 == st_["blocks_available"]
    c = st_["counters"]
    assert c["window_blocks_released"] > 20
    # the prefill ran the cross-decoder for one position a prompt
    assert c["prefill_calls"] == c["prefill_cross_positions"] == 5
    assert c["prefill_positions"] == sum(map(len, prompts)) \
        == c["prefill_tokens"]
    assert c["positions"] == c["cross_positions"] > 0
    _integrity(eng)
    for prompt, out in zip(prompts, outs):
        assert len(out.token_ids) == 40  # five times the window
        assert _gap(eng, cfg, prompt, out.token_ids) < TOL


@pytest.mark.parametrize("attn", ["gather", "paged_kernel"])
def test_decode_step_logits_match_the_forward_with_blocks_given_back(attn):
    """Prefill, then token by token through the three pools on LOGITS
    against ``apply`` on the whole sequence (and the reference), the window
    type's table holding only what ``_release_behind_window`` would leave;
    on the gathered cache and on the paged kernel (interpreter).  The
    second slot is idle: its record is the scratch one."""
    cfg = pf.Phi4FlashConfig.tiny()
    params = pf.phi4flash_init(jax.random.PRNGKey(7), cfg)
    n_prompt, n_all = 11, 26
    tokens = jax.random.randint(jax.random.PRNGKey(8), (n_all,), 0, 256)
    want = pf.phi4flash_apply(params, tokens[None], cfg)[0]
    ref = reference.logits(params, tokens, _model(cfg))
    assert float(jnp.max(jnp.abs(want - ref))) < TOL
    pool = pf.init_pools(cfg, {"full": 12, "window": 12, "state": 3}, 4)
    blocks = np.arange(1, 9, dtype=np.int32)
    S = 16
    dst = np.zeros(S, np.int32)
    dst[:n_prompt] = blocks[np.arange(n_prompt) // 4]
    off = np.zeros(S, np.int32)
    off[:n_prompt] = np.arange(n_prompt) % 4
    empty = jnp.zeros((1, 0, 2, 32))
    padded = jnp.zeros((1, S), jnp.int32).at[0, :n_prompt].set(
        tokens[:n_prompt])
    logits, pool, counts = jax.jit(functools.partial(
        pf.prefill_suffix, cfg=cfg))(
        params, padded, jnp.int32(n_prompt), jnp.int32(0), empty, empty,
        jnp.int32(0), {"full": jnp.asarray(dst), "window": jnp.asarray(dst),
                       "state": jnp.asarray([2], jnp.int32)},
        jnp.asarray(off), pool)
    assert float(jnp.max(jnp.abs(logits[0] - want[n_prompt - 1]))) < TOL
    assert counts.tolist() == [n_prompt, 1]
    step = jax.jit(functools.partial(pf.decode_step, cfg=cfg, attn=attn))
    for pos in range(n_prompt, n_all):
        kept = blocks.copy()
        kept[:max(0, pos + 1 - cfg.sliding_window) // 4] = 0
        tables = {"full": jnp.asarray([blocks, 0 * blocks]),
                  "window": jnp.asarray([kept, 0 * blocks]),
                  "state": jnp.asarray([[2], [0]], jnp.int32)}
        logits, pool, counts = step(
            params, jnp.asarray([tokens[pos], 7], jnp.int32),
            jnp.asarray([pos, 0], jnp.int32), tables, pool)
        assert float(jnp.max(jnp.abs(logits[0] - want[pos]))) < TOL, pos
        assert counts.tolist() == [1, 1]  # the live slot, twice


def test_bfloat16_in_place_of_float32_fails_the_tolerances():
    """The tolerances are tight enough to tell the precision: the same
    weights through the program in bfloat16 miss ``TOL`` by orders."""
    cfg = pf.Phi4FlashConfig.tiny()
    params = pf.phi4flash_init(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 40), 0, 256)
    want = reference.logits(params, tokens[0], _model(cfg))
    low = pf.phi4flash_apply(params, tokens,
                             dataclasses.replace(cfg, dtype=jnp.bfloat16))
    assert float(jnp.max(jnp.abs(low[0] - want))) > 100 * TOL


def _rows_one_by_one(pages, layer, blocks, offsets, slabs):
    """``store_slabs``' contract as the write it replaced: a token's ``g``
    rows of a page, one ``set`` each, in the tokens' order."""
    n, g, _ = slabs.shape
    for i in range(n):
        for j in range(g):
            pages = pages.at[layer, blocks[i], offsets[i] * g + j].set(
                slabs[i, j])
    return pages


def _write_case(case, cfg, params, pool):
    """(program, arguments, blocks of the full and the window pool that
    must have changed) of one case of the test below: pools of 12 blocks of
    4 positions, block 0 the scratch one."""
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    bs, w = 4, cfg.sliding_window
    if case.startswith("prefill"):
        S, n = (16, 11) if case == "prefill-pad-tail" else (32, 30)
        held = np.arange(1, 1 + S // bs, dtype=np.int32)
        pos = np.arange(S)
        dst = np.where(pos < n, held[pos // bs], 0)
        # as ``_run_prefill``: a position whose block the window type does
        # not hold (no later step can see it) has the scratch block's
        behind = pos // bs < (n - w) // bs
        window = np.where(behind, 0, dst)
        assert (case == "prefill-window-scratch") == bool(behind.any())
        tokens = jax.random.randint(jax.random.PRNGKey(21), (1, S), 0, 256)
        empty = jnp.zeros((1, 0, cfg.num_kv_heads // 2, 2 * cfg.head_dim),
                          cfg.dtype)
        return (functools.partial(pf.prefill_suffix, cfg=cfg),
                (params, tokens, i32(n), i32(0), empty, empty, i32(0),
                 {"full": i32(dst), "window": i32(window),
                  "state": i32([2])}, i32(np.where(pos < n, pos % bs, 0)),
                 pool),
                (dst[dst > 0], window[window > 0]))
    # a decode step of four slots; in the second case slots 2 and 3 hold
    # nothing and both write position 0 of the scratch block
    cur = np.asarray([9, 6, 13, 2], np.int32)
    tables = np.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9],
                         [10, 0, 0, 0]], np.int32)
    records = np.asarray([[1], [2], [3], [4]], np.int32)
    if case == "decode-idle-slots":
        cur[2:], tables[2:], records[2:] = 0, 0, 0
    written = tables[np.arange(4), cur // bs]
    return (functools.partial(pf.decode_step, cfg=cfg, attn="gather"),
            (params, i32([5, 6, 7, 8]), i32(cur),
             {"full": i32(tables), "window": i32(tables),
              "state": i32(records)}, pool),
            (written[written > 0],) * 2)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", [
    "decode-step", "prefill-pad-tail", "prefill-window-scratch",
    "decode-idle-slots"])
def test_a_tokens_slab_lands_where_its_rows_did(case, dtype, monkeypatch):
    """PR 40: ``store_slabs`` writes a token's ``[pairs, 2 hd]`` slab as ONE
    update through a view of the page (``[bs, pairs / r, r, 2 hd]``, ``r``
    rows a packed sublane: 1 in float32, 2 in bfloat16).  The three pools
    after a program with it equal, bit for bit, the pools the same program
    leaves with the row-by-row write: every block, the scratch one too
    (idle slots and pad lanes race there, and on the CPU both forms settle
    a race the same way: the last update stays)."""
    cfg = pf.Phi4FlashConfig.tiny(dtype=jnp.dtype(dtype))
    params = pf.phi4flash_init(jax.random.PRNGKey(11), cfg)
    blank = pf.init_pools(cfg, {"full": 12, "window": 12, "state": 5}, 4)
    leaves, tree = jax.tree.flatten(blank)
    # pools that hold something everywhere: a stray write shows
    before = jax.tree.unflatten(tree, [
        jax.random.normal(jax.random.PRNGKey(30 + i), x.shape, x.dtype)
        for i, x in enumerate(leaves)])
    program, args, written = _write_case(case, cfg, params, before)

    def pools_after():
        # a function new to jit, so traced with the write then in force
        return jax.jit(lambda *a: program(*a))(*args)[1]

    got = pools_after()
    monkeypatch.setattr(pf, "store_slabs", _rows_one_by_one)
    want = pools_after()
    for t in ("full", "window", "state"):
        for name in got[t]:
            assert got[t][name].dtype == before[t][name].dtype
            np.testing.assert_array_equal(
                np.asarray(got[t][name].astype(jnp.float32)),
                np.asarray(want[t][name].astype(jnp.float32)),
                err_msg=f"{t}.{name}")
    for t, blocks in zip(("full", "window"), written):
        for name in ("k", "v"):  # the case wrote where it says it did
            changed = np.any(np.asarray(got[t][name] != before[t][name]),
                             axis=(0, 2, 3))
            assert set(np.flatnonzero(changed)) - {0} == set(
                blocks.tolist()), (t, name)


# --------------------------------------------------- (c) a record's life

def test_the_same_prompt_in_two_buckets_leaves_the_same_record():
    cfg = pf.Phi4FlashConfig.tiny()
    params = pf.phi4flash_init(jax.random.PRNGKey(1), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (13,), 0, 256)
    empty = jnp.zeros((1, 0, 2, 32))
    run = jax.jit(functools.partial(pf.prefill_suffix, cfg=cfg))
    records = []
    for S in (16, 32):
        pool = pf.init_pools(cfg, {"full": 12, "window": 12, "state": 3}, 4)
        # what the last request left in the record must not matter
        pool["state"] = jax.tree.map(lambda a: a + 5, pool["state"])
        dst = np.zeros(S, np.int32)
        dst[:13] = 1 + np.arange(13) // 4
        off = np.zeros(S, np.int32)
        off[:13] = np.arange(13) % 4
        tokens = jnp.full((1, S), 9, jnp.int32).at[0, :13].set(prompt)
        logits, pool, _ = run(
            params, tokens, jnp.int32(13), jnp.int32(0), empty, empty,
            jnp.int32(0), {"full": jnp.asarray(dst),
                           "window": jnp.asarray(dst),
                           "state": jnp.asarray([1], jnp.int32)},
            jnp.asarray(off), pool)
        records.append((np.asarray(pool["state"]["ssm"][:, 1]),
                        np.asarray(pool["state"]["conv"][:, 1]),
                        np.asarray(logits)))
        # the other records were not touched
        assert np.all(np.asarray(pool["state"]["ssm"][:, 2]) == 5)
    (s16, c16, l16), (s32, c32, l32) = records
    assert np.abs(s16).max() > 0 and np.abs(c16).max() > 0
    # the products that feed them run at another shape: the last bit
    assert np.abs(s16 - s32).max() < 1e-6 and np.abs(c16 - c32).max() < 1e-6
    assert np.abs(l16 - l32).max() < TOL


def test_a_reused_slot_gives_the_second_request_its_own_answer():
    """One slot, so one record: the second request decodes in the slot and
    the record the first one left, and answers as on a fresh engine."""
    cfg, eng = _engine(batch_slots=1)
    rng = np.random.default_rng(3)
    first, second = (rng.integers(0, 256, n).tolist() for n in (17, 6))
    sp = GREEDY(max_tokens=20)
    eng.generate([first], sp)
    assert np.abs(np.asarray(eng.pool["state"]["ssm"][:, 1])).max() > 0
    again = eng.generate([second], sp)[0]
    _, fresh = _engine(batch_slots=1)
    assert fresh.generate([second], sp)[0].token_ids == again.token_ids
    assert _gap(eng, cfg, second, again.token_ids) < TOL
    _integrity(eng)


def test_records_and_blocks_keep_their_books_through_abort_and_preemption():
    cfg, eng = _engine(num_blocks={"full": 30, "window": 13, "state": 5})
    rng = np.random.default_rng(1)
    sp = GREEDY(max_tokens=48)
    ids = [eng.submit(rng.integers(0, 256, n).tolist(), sp)
           for n in (21, 6, 30, 11)]
    state = eng._pools[2]
    aborted, outs, most = False, {}, 0
    while eng.has_unfinished():
        for out in eng.step():
            outs[out.request_id] = out
        _integrity(eng)
        busy = [i for i, r in enumerate(eng._slots) if r is not None]
        # a record a request, a request a record, and none for an idle slot
        held = [int(state.tables[i, 0]) for i in busy]
        assert all(held) and len(set(held)) == len(held)
        assert state.held() == len(busy)
        assert not any(state.tables[i, 0] for i in range(4)
                       if i not in busy)
        most = max(most, len(busy))
        if not aborted and eng._cur_len.max() > 40:
            assert eng.abort(ids[1])
            aborted = True
    assert most >= 3
    # 29 blocks of the full type cannot hold four sequences of up to 78
    assert eng.blocks.stats["preemptions"] >= 1
    assert len(outs[ids[1]].token_ids) < 48  # cut short by the abort
    for i in (0, 2, 3):
        assert len(outs[ids[i]].token_ids) == 48 and not outs[ids[i]].error
    for p in eng._pools:  # every book back at zero
        assert p.blocks.available() == p.blocks.num_blocks - 1
        assert p.held() == 0 and not p.tables.any()
    # a preempted request resumes to the tokens it would have had: the
    # reference's choice at every position, through the re-prefill
    for i in (0, 2, 3):
        assert _gap(eng, cfg, outs[ids[i]].prompt_tokens,
                    outs[ids[i]].token_ids) < TOL


def test_a_preempted_request_resumes_to_the_same_tokens():
    cfg, eng = _engine()
    prompt = np.random.default_rng(4).integers(0, 256, 10).tolist()
    sp = GREEDY(max_tokens=30)
    want = eng.generate([prompt], sp)[0].token_ids
    rid = eng.submit(prompt, sp)
    eng._carries = lambda: False
    for _ in range(3):
        eng.step()
    record = int(eng._pools[2].tables[0, 0])
    assert record and eng._slots[0].more_blocks[-1] == [record]
    assert eng._preempt_youngest() == 0
    assert eng._pools[2].held() == 0 and not eng._pools[2].tables.any()
    out = None
    while eng.has_unfinished():
        out = next((o for o in eng.step() if o.request_id == rid), out)
    assert out.token_ids == want
    assert eng.blocks.stats["preemptions"] == 1
    _integrity(eng)


def test_a_long_prompt_holds_its_last_window_of_blocks_and_one_record():
    cfg, eng = _engine()
    prompt = np.random.default_rng(2).integers(0, 256, 61).tolist()
    eng.submit(prompt, GREEDY(max_tokens=12))
    eng._carries = lambda: False
    eng.step()  # admission, the first token and one window of 4
    full, window, state = (p.tables[0] for p in eng._pools)
    cur = int(eng._cur_len[0])
    # past a block boundary; what lies behind is the run held ahead
    ahead = -(-17 // eng.blocks.run) * eng.blocks.run
    assert cur == 65 and full[:17].all() and not full[ahead:].any()
    dead = (cur + 1 - 8) // 4
    assert not window[:dead].any() and window[dead:17].all()
    assert eng.counters["window_blocks_released"] == dead - 13 == 1
    assert state.shape == (1,) and state[0] != 0
    outs = []
    while eng.has_unfinished():
        outs += eng.step()
    assert _gap(eng, cfg, prompt, outs[0].token_ids) < TOL
    _integrity(eng)


def test_no_record_left_fails_the_request_by_name_not_the_batch():
    cfg, eng = _engine(num_blocks={"full": 97, "window": 17, "state": 1})
    out = eng.generate([[3] * 20], GREEDY(max_tokens=8))[0]
    assert "cannot hold one sequence" in out.error
    _integrity(eng)
    # two records for four slots: two requests at a time, all answered
    cfg, eng = _engine(num_blocks={"full": 97, "window": 17, "state": 3})
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 256, n).tolist() for n in (7, 12, 9, 15)]
    most, outs = 0, {}
    ids = [eng.submit(p, GREEDY(max_tokens=10)) for p in prompts]
    while eng.has_unfinished():
        for o in eng.step():
            outs[o.request_id] = o
        most = max(most, eng._pools[2].held())
    assert most == 2
    for rid, prompt in zip(ids, prompts):
        assert _gap(eng, cfg, prompt, outs[rid].token_ids) < TOL


# ------------------------------------------------- (d) what is left out

@pytest.mark.parametrize("kwargs,match", [
    (dict(prefill_chunk=16), "prefill_chunk"),
    (dict(mesh=object()), "mesh"),
    (dict(kv_cache_dtype="int8"), "kv_dtype"),
])
def test_what_the_model_does_not_supply_is_refused_by_name(kwargs, match):
    cfg = preset("phi4flash_tiny")
    with pytest.raises((NotImplementedError, ValueError), match=match):
        LLMEngine(cfg, tokenizer=_Ids(), max_len=64, block_size=4, **kwargs)


def test_no_prefix_hit_no_handoff_and_the_programs_say_so():
    cfg, eng = _engine()
    prompt = list(range(40))
    sp = GREEDY(max_tokens=4)
    first = eng.generate([prompt], sp)[0]
    again = eng.generate([prompt + [7, 8]], sp)[0]  # no hit on its prefix
    assert eng.blocks.stats["prefix_hits"] == 0
    assert not eng.blocks.by_key and not eng.blocks.lru
    assert not eng._pools[2].blocks.by_key  # a record is never published
    assert len(first.token_ids) == len(again.token_ids) == 4
    with pytest.raises(NotImplementedError, match="prefill_only|handoff"):
        eng.submit(prompt, sp, prefill_only=True)
    with pytest.raises(NotImplementedError, match="handoff"):
        eng.export_kv(0)
    with pytest.raises(NotImplementedError, match="no prefix hits"):
        eng.model.gather_prefix(eng.pool, jnp.zeros((2,), jnp.int32), cfg)
    some = jnp.zeros((1, 4, 2, 32))
    with pytest.raises(NotImplementedError, match="no cached prefix"):
        eng.model.prefill_suffix(
            eng.params, jnp.zeros((1, 8), jnp.int32), 8, 4, some, some, 4,
            None, None, eng.pool, cfg=cfg)
    with pytest.raises(NotImplementedError, match="sharded"):
        pf.phi4flash_apply(eng.params, jnp.zeros((1, 4), jnp.int32), cfg,
                           mesh=object())
    with pytest.raises(ValueError, match="multiple of 4"):
        pf.Phi4FlashConfig.tiny(num_layers=6)
    with pytest.raises(ValueError, match="pair up"):
        pf.Phi4FlashConfig.tiny(num_kv_heads=2)


# ------------------------------------ (e) what the engine and scopes say

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


def test_the_spans_say_what_a_step_reads_by_type(monkeypatch):
    from ray_tpu.llm import engine as engine_mod

    spans = _Spans()
    monkeypatch.setattr(engine_mod.tracing, "annotate", spans)
    cfg, eng = _engine()
    eng._carries = lambda: False
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 256, n).tolist() for n in (30, 7)]
    eng.generate(prompts, GREEDY(max_tokens=6))
    w = spans.named("engine.dispatch_window")[0]
    assert w["active"] == 2 and w["state_records_held"] == 2
    assert w["live_tokens_state"] == 2  # a record an active slot
    assert w["live_tokens_full"] == 30 + 7
    assert w["live_tokens_window"] == 8 + 7  # at most the window
    # the mean over the layers that READ a pool: 2 the full, 2 the window
    assert w["live_tokens"] == round((37 * 2 + 15 * 2) / 4)
    assert "blocks_held_state" not in w and w["blocks_held_full"] > 0
    first = spans.named("engine.first_tokens")[0]
    assert first["prefill_positions"] == 37
    assert first["prefill_cross_positions"] == 2 == first["n"]
    fetch = spans.named("engine.fetch_window")[0]
    assert fetch["positions"] == fetch["cross_positions"] == 2 * fetch["k"]


def test_a_model_without_a_state_type_reads_as_before(monkeypatch):
    """SmallThinker's window stats: no state key, the mean over its
    layers (every layer reads what it stores)."""
    from ray_tpu.llm import engine as engine_mod

    spans = _Spans()
    monkeypatch.setattr(engine_mod.tracing, "annotate", spans)
    eng = LLMEngine(preset("smallthinker_tiny"), tokenizer=_Ids(),
                    batch_slots=2, max_len=64, block_size=4,
                    decode_window=4, seed=5)
    assert eng._kv_pools == eng._pools
    assert [(p.layers, p.readers, p.state) for p in eng._pools] == [
        (2, 2, False), (6, 6, False)]
    eng.generate([list(range(20))], GREEDY(max_tokens=4))
    w = spans.named("engine.dispatch_window")[0]
    assert set(w) == {"k", "active", "attn", "carried", "experts",
                      "live_tokens", "live_tokens_full",
                      "live_tokens_window", "blocks_held_full",
                      "blocks_held_window", "pages_live", "pages_in_runs"}
    assert w["live_tokens"] == round((20 * 2 + 8 * 6) / 8)
    one = LLMEngine(preset("tiny"), tokenizer=_Ids(), batch_slots=2,
                    max_len=64)
    assert one._kv_pools == one._pools and not one._by_type


HEAVY = re.compile(r"stablehlo\.dot_general|stablehlo\.convolution|"
                   r"tpu_custom_call")
LOC = re.compile(r'^(#loc\d+) = loc\("([^"]*)"', re.M)


def _ops(jitted, args):
    text = jitted.lower(*args).as_text(debug_info=True)
    names = dict(LOC.findall(text))
    return [(line.strip().split(" : ")[0][:100], names[m.group(1)])
            for line in text.splitlines()
            if (m := re.search(r" loc\((#loc\d+)\)$", line))
            and m.group(1) in names]


def test_every_product_names_its_program_and_part_and_the_third_level():
    """The two levels of every served model, and under ``attn.core`` the
    third: ``ssm.conv``, ``ssm.update`` (decode) / ``ssm.scan`` (prefill),
    ``gmu``, ``diff``."""
    cfg, eng = _engine()
    seen = {}
    for attr, key in (("_decode1", "engine.decode"),
                      ("_prefill", "engine.prefill")):
        jitted = getattr(eng, attr)

        def recording(*args, jitted=jitted, key=key):
            if key not in seen:
                seen[key] = _ops(jitted, jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                                   jnp.result_type(a)),
                    args))
            return jitted(*args)
        setattr(eng, attr, recording)
    eng.generate([[3, 4, 5, 6, 7]], GREEDY(max_tokens=3))
    for program, ops in seen.items():
        heavy = [name for op, name in ops if HEAVY.search(op)]
        assert len(heavy) >= 30
        for name in heavy:
            words = re.split(r"[/()]", name)
            assert program in words, name
            assert any(w in tracing.PART_SCOPES for w in words), name
        parts = {w for name in heavy for w in re.split(r"[/()]", name)
                 if w in tracing.PART_SCOPES}
        assert parts == {"attn.proj", "attn.core", "attn.out", "ffn",
                         "head"}, parts
        details = {}
        for _, name in ops:
            words = re.split(r"[/()]", name)
            for d in tracing.DETAIL_SCOPES:
                if d in words:  # always inside attn.core
                    assert "attn.core" in words[:words.index(d)], name
                    details[d] = details.get(d, 0) + 1
        scan = "ssm.update" if program == "engine.decode" else "ssm.scan"
        assert set(details) == {"ssm.conv", scan, "gmu", "diff"}, details
