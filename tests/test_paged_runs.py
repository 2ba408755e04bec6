"""The paged decode kernel's two cuts of its scalar core's work: one copy
descriptor a run of adjacent blocks, and several slots a grid step
(``ops/pallas/paged_attention.py``: ``page_run``, ``run_flags``,
``_SLOTS_A_STEP``).

CPU, through the Pallas interpreter, at small shapes.  The same logical
pages (a slot's page p holds the same numbers) are laid out in the pool
three ways: every group of ``run`` table entries a run of adjacent blocks,
no two consecutive entries adjacent, and a mix (whole runs, single pages,
entries that ascend by two, a run with two entries swapped), with lengths
and windows that cut flagged groups at either end.  Every block the slots
do not read holds NaN, so one page copied too many shows.  The outputs must
be the same to the bit whatever the layout, and the same as the kernel's
with a page a descriptor and a slot a grid step, which is the kernel of
before; against the gather path they keep ``test_paged_attention.py``'s
tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from types import SimpleNamespace

from ray_tpu.models.mla import attend_rows
from ray_tpu.ops.pallas import paged_attention as kernel_module
from ray_tpu.ops.pallas.paged_attention import (latent_paged_attention,
                                                page_run, paged_attention,
                                                pipeline_plan, run_flags)
from test_paged_attention import CELL_PAGES, _reference

L, LAYER, BS, KVH, HD, H = 2, 1, 4, 2, 16, 8
MB, PAGES, RUN = 12, 4, 4  # 48 positions a slot, three compute blocks
W, VW = 256, 128
NB = 1024

LENGTHS = {
    # 11 slots: a grid step a slot (no divisor under 8 but 1)
    "b11": [48, 17, 0, 33, 5, 0, 40, 16, 1, 29, 12],
    # 12 slots: six a grid step
    "b12": [13, 48, 32, 0, 0, 21, 47, 3, 0, 36, 8, 25],
    # 16 slots: eight a step, a whole step of empty slots
    "b16": [0] * 8 + [44, 0, 19, 31, 0, 48, 2, 15],
    "all_empty": [0] * 8,
    "one_slot": [37],
}
# (arm, window): a window of 10 starts inside a flagged group for most
ARMS = [("dense", None), ("dense", 10), ("latent", None)]


def _table(lengths, layout, rng):
    """``[b, MB]`` block ids and the set of blocks a slot may read.  A
    group of RUN entries is laid out by ``layout``; block ids are unique."""
    at = [RUN]  # next unused aligned block

    def fresh(n):
        first = at[0]
        at[0] += -(-n // RUN) * RUN
        return first

    def group(kind):
        if kind == "run":
            return fresh(RUN) + np.arange(RUN)
        if kind == "by_two":  # ascends, not by 1
            return fresh(2 * RUN) + 2 * np.arange(RUN)
        if kind == "swapped":  # adjacent blocks, two out of order
            ids = fresh(RUN) + np.arange(RUN)
            ids[[1, 2]] = ids[[2, 1]]
            return ids
        # "single": no entry follows its neighbour
        return fresh(2 * RUN) + 2 * rng.permutation(RUN) + 1

    kinds = {"runs": ["run"], "singles": ["single"],
             "mixed": ["run", "single", "by_two", "run", "swapped"]}[layout]
    table = np.zeros((len(lengths), MB), np.int32)
    n = 0
    for s, length in enumerate(lengths):
        for g in range(-(-length // (BS * RUN))):
            table[s, g * RUN:(g + 1) * RUN] = group(kinds[n % len(kinds)])
            n += 1
    assert at[0] <= NB
    return table


def _live(lengths, window):
    """[b, MB] booleans: the pages a slot reads."""
    n = np.asarray(lengths)[:, None]
    page = np.arange(MB)[None, :]
    first = np.maximum(n - (window or 10 ** 6), 0) // BS
    return (page < -(-n // BS)) & (page >= first)


def _pool(data, table, live):
    """The pool ``[L, NB, *page]`` that holds slot s's page p at
    ``table[s, p]`` where it is live, and NaN everywhere else."""
    pool = np.full((L, NB) + data.shape[3:], np.nan, np.float32)
    pool[:, 0] = 0  # the scratch block
    s, p = np.nonzero(live)
    pool[:, table[s, p]] = data[:, s, p]
    return jnp.asarray(pool)


def _run(arm, window, lengths, layout, seed=0):
    b = len(lengths)
    rng = np.random.default_rng(seed)
    table = _table(lengths, layout, rng)
    live = _live(lengths, window)
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    n = jnp.asarray(lengths, jnp.int32)
    if arm == "dense":
        k, v = (np.asarray(jax.random.normal(
            key, (L, b, MB, BS, KVH, HD), jnp.float32)) for key in keys[:2])
        q = jax.random.normal(keys[2], (b, H, HD), jnp.float32)
        pool = {"k": _pool(k, table, live), "v": _pool(v, table, live)}
        got = paged_attention(q, pool["k"], pool["v"], jnp.asarray(table), n,
                              layer=LAYER, window=window,
                              pages_per_block=PAGES, interpret=True)
        clean = {"k": _pool(k, table, live | True),
                 "v": _pool(v, table, live | True)}
        want = _reference(q, clean, jnp.asarray(table), n, window)
    else:
        rows = np.asarray(jax.random.normal(keys[0], (L, b, MB, BS, W),
                                            jnp.float32))
        q = jax.random.normal(keys[2], (b, H, W), jnp.float32)
        got = latent_paged_attention(
            q, _pool(rows, table, live), jnp.asarray(table), n, layer=LAYER,
            value_width=VW, scale=0.11, pages_per_block=PAGES,
            interpret=True)
        cfg = SimpleNamespace(dtype=jnp.float32, kv_lora_rank=VW,
                              softmax_scale=0.11)
        want = attend_rows(_pool(rows, table, live | True), LAYER,
                           jnp.asarray(table), n - 1, n, cfg, "gather")(q)
        want = jnp.where(n[:, None, None] > 0, want, 0)
    return np.asarray(got), np.asarray(want), table


@pytest.fixture
def kernel_of_before(monkeypatch):
    """A page a descriptor and a slot a grid step: what the kernel did
    before it knew of runs.  Both are read off shapes by ``_call``, so the
    rules are replaced, and the jitted functions forget what they traced."""
    jitted = (kernel_module._paged_attention,
              kernel_module._latent_paged_attention)

    def force():
        monkeypatch.setattr(kernel_module, "page_run", lambda *a: 1)
        monkeypatch.setattr(kernel_module, "_SLOTS_A_STEP", 1)
        for f in jitted:
            f.clear_cache()

    yield force
    monkeypatch.undo()
    for f in jitted:
        f.clear_cache()


@pytest.mark.parametrize("arm,window", ARMS,
                         ids=["dense", "dense-window", "latent"])
@pytest.mark.parametrize("case", LENGTHS)
def test_any_layout_of_the_same_pages_gives_the_same_bits(
        case, arm, window, kernel_of_before):
    lengths = LENGTHS[case]
    assert page_run(BS * (KVH if arm == "dense" else 1),
                    HD if arm == "dense" else W, 4, PAGES) == RUN
    outs, flagged = {}, {}
    for layout in ("singles", "runs", "mixed"):
        got, want, table = _run(arm, window, lengths, layout)
        assert np.isfinite(got).all(), layout  # no page read too many
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        assert (got[np.asarray(lengths) == 0] == 0).all()
        in_runs, whole, _ = run_flags(table, np.asarray(lengths), run=RUN,
                                   pages=PAGES, block_size=BS, window=window,
                                   xp=np)
        outs[layout] = got
        # of the blocks with a group live whole: those copied in runs
        flagged[layout] = in_runs[whole.any(axis=-1)]
    # the layouts are what they say: every such block in runs, none, some
    assert flagged["runs"].all() and not flagged["singles"].any()
    if len(flagged["mixed"]) > 2:
        assert 0 < flagged["mixed"].sum() < len(flagged["mixed"])
    for layout in ("runs", "mixed"):
        assert np.array_equal(outs[layout], outs["singles"]), layout
    kernel_of_before()
    before, _, _ = _run(arm, window, lengths, "singles")
    assert np.array_equal(before, outs["singles"])


def test_run_flags_reads_adjacent_ascending_groups_live_whole():
    table = np.array([[4, 5, 6, 7, 9, 10, 11, 13, 20, 21, 22, 23, 0, 0],
                      [8, 7, 6, 5, 30, 31, 32, 33, 2, 4, 6, 8, 40, 41],
                      [50, 51, 52, 53, 54, 55, 56, 57, 60, 61, 62, 63, 0, 0]],
                     np.int32)
    kw = dict(run=4, pages=8, block_size=2, xp=np)
    # slot 0: its second group is no run; slot 1: its first descends, its
    # third ascends by two; slot 2: runs throughout.  Blocks of 8 pages.
    in_runs, whole, live = run_flags(table, np.array([24, 24, 24]),
                                     window=None, **kw)
    assert live.tolist() == [12, 12, 12]
    assert whole.shape == (3, 2, 2) and whole[:, :, 0].all()
    assert whole[:, 0].all() and not whole[:, 1, 1].any()  # 12 pages live
    assert in_runs.tolist() == [[False, True], [False, False], [True, True]]
    # a group the length cuts is copied by the page: it breaks no block
    in_runs, whole, live = run_flags(table, np.array([9, 22, 17]),
                                     window=None, **kw)
    assert live.tolist() == [5, 11, 9]
    assert whole[:, 0].tolist() == [[True, False], [True, True], [True, True]]
    assert not whole[0, 1].any() and whole[2, 1].tolist() == [False, False]
    assert in_runs.tolist() == [[True, True], [False, True], [True, True]]
    # nor one the window's start cuts: slot 1 sees pages 5 to 10
    in_runs, whole, live = run_flags(table, np.array([0, 22, 0]), window=12,
                                     **kw)
    assert not whole[1].any() and in_runs.all() and live.tolist() == [0, 6, 0]
    # the device's tables read the same
    for window in (None, 12):
        for got, want in zip(
                run_flags(jnp.asarray(table), jnp.asarray([24, 22, 17]),
                          window=window, **dict(kw, xp=jnp)),
                run_flags(table, np.array([24, 22, 17]), window=window,
                          **kw)):
            assert np.array_equal(np.asarray(got), want)


def test_page_run_at_the_cells_pages():
    """64 KiB a descriptor where powers of two divide a block's pages:
    4 pages of the latent pools' 20 KB and SmallThinker's 16, 2 of
    Mistral's 32 and Phi-4-mini-flash's 40 (whose 6 pages a block take no
    4)."""
    runs = {}
    for cell, (page_rows, width, itemsize, n_pools, mb) in CELL_PAGES.items():
        pages, _ = pipeline_plan(page_rows, width, itemsize, n_pools, mb)
        runs[cell] = page_run(page_rows, width, itemsize, pages)
        assert pages % runs[cell] == 0
    assert list(runs.values()) == [2, 4, 2, 4, 4]
    assert page_run(16, 640, 2, 1) == 1  # a table one block wide
    assert page_run(16, 640, 2, 6) == 2  # no 4 divides 6
    assert page_run(1024, 4096, 4, 8) == 1  # a page past 64 KiB
    assert page_run(8, 16, 4, 64) == 8  # a tiny page: never past 8
