"""The serve loop's block manager hands a slot its blocks in aligned runs of
adjacent blocks (``llm/engine.py:_BlockManager``; the run's length is the
decode kernel's ``page_run``), so that one copy descriptor moves a run.

(a) the manager alone: runs are handed out whole and re-form in whatever
order their blocks come back; one block at a time prefers broken runs;
cached contents are evicted last; with runs of one the order of allocation
is the old one.  (b) ``LLMEngine`` with runs on, a tiny model on the CPU:
the books balance after every step through admission, decode growth,
retirement, abort, preemption and releases behind a window; a pool with no
whole run serves the same tokens one block at a time; a prefix hit may end
inside a run; the window's stats count the pages that lie in runs.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import LLMEngine, SamplingParams
from ray_tpu.llm.engine import _BlockManager
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.models.served import preset


# ------------------------------------------------------ (a) the manager

def test_runs_are_aligned_and_the_rest_are_single_blocks():
    bm = _BlockManager(22, run=4)  # blocks 1..21: runs 4-7 .. 16-19
    assert list(bm.whole_free) == [4, 8, 12, 16]
    assert list(bm.free) == [1, 2, 3, 20, 21]  # beside scratch; the tail
    assert bm.available() == bm.free_count() == 21
    assert bm.alloc_run() == 4 and bm.alloc_run() == 8
    assert all(bm.refs[b] == 1 for b in range(4, 12))
    assert bm.available() == 13
    # an entry that starts no group, or has no room for a run: one block
    assert bm.take(3, 100) == [1] and bm.take(4, 3) == [2]
    assert bm.take(8, 4) == [12, 13, 14, 15]
    bm.assert_integrity()


@pytest.mark.parametrize("order", list(itertools.permutations(range(4)))[::5])
def test_a_run_is_whole_again_in_whatever_order_its_blocks_return(order):
    bm = _BlockManager(13, run=4)  # runs 4-7, 8-11
    first = bm.alloc_run()
    assert first == 4 and list(bm.whole_free) == [8]
    for n, k in enumerate(order):
        assert first not in bm.whole_free
        assert len(bm.free) == 4 + n  # 1, 2, 3, 12 and what came back
        bm.release(first + k)
        bm.assert_integrity()
    assert list(bm.whole_free) == [8, 4]  # in the order they became whole
    assert list(bm.free) == [1, 2, 3, 12]  # its blocks left the singles


def test_one_block_at_a_time_uses_up_broken_runs_first():
    bm = _BlockManager(16, run=4)  # 1-3 single; runs 4-7, 8-11, 12-15
    assert [bm.alloc() for _ in range(3)] == [1, 2, 3]
    assert bm.alloc() == 4  # the oldest whole run breaks ...
    assert list(bm.whole_free) == [8, 12] and list(bm.free) == [5, 6, 7]
    assert [bm.alloc() for _ in range(3)] == [5, 6, 7]  # ... and is used up
    assert list(bm.whole_free) == [8, 12]
    bm.release(6)  # a block of a broken run comes back: the next single
    assert bm.alloc() == 6 and list(bm.whole_free) == [8, 12]
    bm.assert_integrity()


def test_cached_blocks_are_evicted_last_and_a_hit_breaks_a_whole_run():
    bm = _BlockManager(12, run=4)  # runs 4-7, 8-11
    run = bm.take(0, 8)
    assert run == [4, 5, 6, 7]
    for b in run[:2]:
        bm.register(b, ("key", b))
    for b in run:
        bm.release(b)
    # whole again, two of its blocks cached: behind the runs all free
    assert list(bm.whole_free) == [8] and list(bm.whole_cached) == [4]
    assert len(bm.lru) == 2 and bm.available() == 11
    bm.assert_integrity()
    # a prefix hit takes a block out of the whole run: it breaks
    assert bm.acquire_cached(("key", 4)) == 4
    assert not bm.whole_cached and list(bm.free) == [1, 2, 3, 6, 7]
    bm.release(4)
    assert list(bm.whole_cached) == [4] and bm.stats["evictions"] == 0
    # runs: the free one first, then the cached one, evicting its two
    assert bm.alloc_run() == 8 and bm.stats["evictions"] == 0
    assert bm.alloc_run() == 4 and bm.stats["evictions"] == 2
    assert not bm.lru and not bm.by_key and not bm.key_of
    assert bm.alloc_run() is None  # no run whole: the caller takes singles
    assert bm.take(0, 8) == [1]
    bm.assert_integrity()


def test_single_blocks_take_free_ones_before_the_cache():
    bm = _BlockManager(8, run=4)  # 1-3 single; run 4-7
    got = [bm.alloc() for _ in range(3)]
    for b in got:
        bm.register(b, ("k", b))
        bm.release(b)
    assert len(bm.lru) == 3 and list(bm.whole_free) == [4]
    # free blocks (a whole run's: it breaks) before any cached one
    assert [bm.alloc() for _ in range(4)] == [4, 5, 6, 7]
    assert bm.stats["evictions"] == 0
    assert bm.alloc() == 1 and bm.stats["evictions"] == 1  # the oldest
    assert bm.acquire_cached(("k", 2)) == 2  # still there
    bm.assert_integrity()


def test_runs_of_one_allocate_in_the_old_order():
    bm = _BlockManager(6, run=1)
    assert [bm.alloc() for _ in range(5)] == [1, 2, 3, 4, 5]
    assert bm.alloc() is None
    bm.register(2, "a")
    for b in (3, 2, 5, 1):
        bm.release(b)
    assert bm.available() == 4 and bm.free_count() == 3
    # free blocks first in, first out, then the cache's oldest
    assert [bm.alloc() for _ in range(4)] == [3, 5, 1, 2]
    assert bm.stats["evictions"] == 1
    bm.assert_integrity()


def test_adopt_takes_single_blocks_and_rolls_back_whole():
    bm = _BlockManager(12, run=4)
    bids = bm.adopt(["a", "b", None, None, "c"])
    assert bids == [1, 2, 3, 4, 5] and bm.stats["adopted_blocks"] == 5
    assert bm.adopt([None] * 7) is None  # 6 left: all or nothing
    assert bm.available() == 6
    bm.assert_integrity()
    bm.unpublish_free(bids)
    assert not bm.by_key and bm.available() == 11
    assert list(bm.whole_free) == [8, 4]
    bm.assert_integrity()


@pytest.mark.parametrize("run", [1, 2, 4, 8])
def test_the_books_balance_through_random_traffic(run):
    rng = np.random.default_rng(run)
    bm = _BlockManager(70, run=run)
    held, keys = [], itertools.count()
    for _ in range(1500):
        op = rng.integers(5)
        if op <= 1:  # a slot's table grows from some entry on
            got = bm.take(int(rng.integers(0, 3 * run)), int(rng.integers(9)))
            if got is None:
                assert bm.available() == 0
            else:
                assert len(got) in (1, run)
                assert got == list(range(got[0], got[0] + len(got)))
                held += got
        elif op == 2 and held:  # a block is filled and published, once
            bid = held[int(rng.integers(len(held)))]
            if bid not in bm.key_of:
                bm.register(bid, next(keys))
        elif op == 3 and held:
            bm.release(held.pop(int(rng.integers(len(held)))))
        elif op == 4 and bm.by_key:  # a prefix hit
            key = list(bm.by_key)[int(rng.integers(len(bm.by_key)))]
            held.append(bm.acquire_cached(key))
        bm.assert_integrity()
        assert bm.available() == 69 - len(set(held))
    for b in held:
        bm.release(b)
    bm.assert_integrity()
    assert bm.available() == 69
    # every run of the pool is whole again
    assert len(bm.whole_free) + len(bm.whole_cached) == (
        69 if run == 1 else 70 // run - 1)


# ------------------------------------------------- (b) under the engine

GREEDY = dict(temperature=0.0, stop_token_id=None)


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    return cfg, llama_init(jax.random.PRNGKey(0), cfg)


def _engine(tiny, run=None, **kw):
    """A tiny engine; ``run``: the length of its runs where the test needs
    one its pages would not give (the rule reads a page's bytes)."""
    cfg, params = tiny
    kw = dict(batch_slots=4, max_len=128, block_size=4, decode_window=4, **kw)
    if run is None:
        return LLMEngine(cfg, params, **kw)
    rule = LLMEngine._page_plan
    LLMEngine._page_plan = lambda self, pool: (rule(self, pool)[0], run)
    try:
        return LLMEngine(cfg, params, **kw)
    finally:
        LLMEngine._page_plan = rule


def _integrity(eng):
    for p in eng._pools:
        p.blocks.assert_integrity()
    # the tables list what the requests hold, entry for entry
    for i, req in enumerate(eng._slots):
        if req is not None and not req.done:
            for p, held in zip(eng._pools, [req.blocks] + req.more_blocks):
                assert p.tables[i, :len(held)].tolist() == held
                assert not p.tables[i, len(held):].any()


def _prompts(n, seed=0, lo=5, hi=40):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, 250, int(k)).tolist()
            for k in rng.integers(lo, hi, n)]


def test_the_rule_gives_the_engine_the_kernels_run(tiny):
    eng = _engine(tiny)
    # a tiny page is far under 64 KiB: the longest run the rule gives
    assert eng.blocks.run == 8 and eng._pools[0].blocks is eng.blocks
    assert _engine(tiny, run=4).blocks.run == 4


def test_slots_hold_runs_and_the_books_balance_through_a_batch(tiny):
    eng = _engine(tiny, run=4)
    prompts = _prompts(9)
    sp = SamplingParams(max_tokens=30, **GREEDY)
    ids = [eng.submit(p, sp) for p in prompts]
    outs, seen_ahead = {}, False
    while eng.has_unfinished():
        for out in eng.step():
            outs[out.request_id] = out
        _integrity(eng)
        for i, req in enumerate(eng._slots):
            if req is None or req.done:
                continue
            # a big pool: every group of a slot's table is a run, and the
            # last one may reach past what the sequence fills
            assert len(req.blocks) % 4 == 0
            table = eng._tables[i, :len(req.blocks)].reshape(-1, 4)
            assert (table == table[:, :1] + np.arange(4)).all()
            seen_ahead |= len(req.blocks) * 4 >= eng._cur_len[i] + 4 + 4
    assert seen_ahead
    assert eng.blocks.available() == eng.blocks.num_blocks - 1
    assert not eng._tables.any()
    # the same tokens one block at a time
    ref = _engine(tiny, run=1)
    for p, rid in zip(prompts, ids):
        assert ref.generate([p], sp)[0].token_ids == outs[rid].token_ids


def test_a_pool_with_no_whole_run_serves_the_same_tokens_singly(tiny):
    prompts = _prompts(6, seed=1)
    sp = SamplingParams(max_tokens=24, **GREEDY)
    want = [o.token_ids for o in _engine(tiny, run=1).generate(prompts, sp)]
    # blocks 1..34 in runs of 32: the only aligned run does not fit
    eng = _engine(tiny, run=32, num_blocks=35)
    assert not eng.blocks.whole_free
    ids = [eng.submit(p, sp) for p in prompts]
    outs = {}
    while eng.has_unfinished():
        for out in eng.step():
            outs[out.request_id] = out
        _integrity(eng)
    assert [outs[i].token_ids for i in ids] == want
    assert not any(outs[i].error for i in ids)
    # and a pool so small that it preempts: the same tokens still
    eng = _engine(tiny, run=4, num_blocks=26)
    got = eng.generate(prompts, sp)
    assert eng.blocks.stats["preemptions"] >= 1
    assert [o.token_ids for o in got] == want
    _integrity(eng)
    assert eng.blocks.available() == 25


def test_abort_and_preemption_give_back_what_was_held_ahead(tiny):
    eng = _engine(tiny, run=4)
    sp = SamplingParams(max_tokens=40, **GREEDY)
    ids = [eng.submit(p, sp) for p in _prompts(4, seed=2)]
    eng._carries = lambda: False
    eng.step()
    _integrity(eng)
    held = eng.blocks.num_blocks - 1 - eng.blocks.available()
    assert held == sum(len(r.blocks) for r in eng._slots)
    assert eng.abort(ids[1])
    eng.step()  # the abort retires through the ordinary path
    _integrity(eng)
    assert all(r is None or r.request_id != ids[1] for r in eng._slots)
    victim = eng._preempt_youngest()
    assert victim is not None and eng._slots[victim] is None
    _integrity(eng)
    assert eng.blocks.num_blocks - 1 - eng.blocks.available() == sum(
        len(r.blocks) for r in eng._slots if r is not None)
    outs = {}
    while eng.has_unfinished():
        for out in eng.step():
            outs[out.request_id] = out
        _integrity(eng)
    assert len(outs[ids[3]].token_ids) == 40  # the preempted one resumed
    assert eng.blocks.available() == eng.blocks.num_blocks - 1
    assert len(eng.blocks.whole_free) + len(eng.blocks.whole_cached) == (
        eng.blocks.num_blocks // 4 - 1)  # every run whole again


def test_a_prefix_hit_may_end_inside_a_run(tiny):
    eng = _engine(tiny, run=4)
    sp = SamplingParams(max_tokens=6, **GREEDY)
    shared = list(range(10, 36))  # 26 tokens: six full blocks and a half
    first = eng.generate([shared + [7, 8, 9]], sp)[0]
    _integrity(eng)
    assert list(eng.blocks.whole_cached)  # its runs wait, contents kept
    rid = eng.submit(shared + [40, 41, 42, 43], sp)
    eng._carries = lambda: False
    eng.step()
    req = eng._slots[0]
    assert req.cached_prefix_len == 24 and eng.blocks.stats["prefix_hits"] == 1
    # six hit blocks, the first request's: a run and half of the next one;
    # entries 6 and 7 single blocks, then runs again
    # (the step's decode window grew the table into its third group)
    assert len(req.blocks) == 12
    assert np.diff(req.blocks[:4]).tolist() == [1, 1, 1]
    assert np.diff(req.blocks[4:8]).tolist() != [1, 1, 1]
    assert np.diff(req.blocks[8:]).tolist() == [1, 1, 1]
    _integrity(eng)
    out = None
    while eng.has_unfinished():
        out = next((o for o in eng.step() if o.request_id == rid), out)
        _integrity(eng)
    fresh = _engine(tiny, run=1).generate([shared + [40, 41, 42, 43]], sp)[0]
    assert out.token_ids == fresh.token_ids
    assert first.token_ids  # and the first one's answer was its own


def test_releases_behind_a_window_break_runs_and_they_form_again():
    cfg = preset("smallthinker_tiny")
    eng = LLMEngine(cfg, batch_slots=2, max_len=96, block_size=4,
                    decode_window=4, seed=3)
    window = eng._pools[1]
    assert window.window == 8 and window.blocks.run > 1
    whole = len(window.blocks.whole_free)
    sp = SamplingParams(max_tokens=40, **GREEDY)
    eng.submit(np.random.default_rng(5).integers(0, 256, 30).tolist(), sp)
    broken = False
    while eng.has_unfinished():
        eng.step()
        _integrity(eng)
        broken |= len(window.blocks.free) > (window.blocks.run - 1)
    assert broken  # blocks came back one at a time, out of held runs
    assert eng.counters["window_blocks_released"] > 8
    for p in eng._pools:
        assert p.blocks.available() == p.blocks.num_blocks - 1
    assert len(window.blocks.whole_free) == whole


def test_the_window_counts_live_pages_and_those_in_runs(tiny):
    eng = _engine(tiny, run=4)
    sp = SamplingParams(max_tokens=20, **GREEDY)
    # 26 tokens shared: the second request's table starts with six hits
    shared = list(range(10, 36))
    eng.generate([shared + [7]], sp)
    eng.submit(shared + [40, 41], sp)
    eng.submit(list(range(50, 71)), sp)  # 21 tokens: five pages and one
    eng._carries = lambda: False
    eng.step()
    active = [0, 1]
    lens = eng._cur_len[active]
    assert lens.tolist() == [28 + 4, 21 + 4]
    pages = eng._live_pages(active)
    # slot 0: 8 pages, entries 4-7 no run (two hits, two singles), so the
    # kernel copies its one compute block by the page; slot 1: 7 pages,
    # the first group a run, the second cut by the length
    assert pages == {"pages_live": 8 + 7, "pages_in_runs": 0 + 4}
    assert eng._live_tokens(active) == {"live_tokens": int(lens.sum()),
                                        **pages}
