"""The paged decode-attention kernel against the gather path it replaces.

CPU, through the Pallas interpreter, at small shapes: the same pool, block
tables and lengths go through ``ops/pallas/paged_attention.py`` and through
``paged_generation._gather_kv`` + ``paged_generation._gqa_attend`` (the
plain reference, and still the path of int8 pools, meshes and every backend
but TPU).  The latent arm goes through the same length patterns (those with
no window: it takes none) against the gather of ``models/mla.py:attend_rows``.

The kernel's page copies are a pipeline ``depth`` buffers deep whose cursor
runs ahead of the block attended, over all slots.  At these shapes
``pipeline_plan`` gives the deepest it ever gives;
``test_pipeline_at_other_depths`` runs the patterns a pipeline can get wrong
under a shallower and a deeper one.

Tolerance.  With a float32 pool both sides multiply exact float32 values
and differ only in the order of their float32 sums (online softmax a block
at a time against one softmax over the table): 2e-5.  With a bfloat16 pool
the probabilities enter the second matmul rounded to bfloat16 on both sides
(relative 2^-9 each, normalized before the rounding on one side and after
it on the other) and the result is rounded to bfloat16 once more: outputs
are convex combinations of N(0,1) values, |out| < 4, where one bfloat16
step is 2^-6 = 0.0156; 2e-2 allows that one step plus the probabilities'.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from types import SimpleNamespace

from ray_tpu.models.mla import attend_rows
from ray_tpu.models.paged_generation import _gqa_attend
from ray_tpu.models.paged_generation import _gather_kv
from ray_tpu.ops.attention import sliding_window_mask
from ray_tpu.ops.pallas import paged_attention as kernel_module
from ray_tpu.ops.pallas.paged_attention import (latent_paged_attention,
                                                paged_attention,
                                                pipeline_plan)

L, LAYER, BS, HD = 3, 1, 4, 16
MB = 6  # 24 positions a slot
W, VW = 256, 128  # a latent row, and its leading columns that are values
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _reference(q, pool, tables, lengths, window):
    b = q.shape[0]
    k, v = _gather_kv(pool, LAYER, tables, q.dtype)
    k = k.reshape(b, -1, *k.shape[3:])
    v = v.reshape(b, -1, *v.shape[3:])
    pos = jnp.arange(k.shape[1])[None, None, :]
    last = (lengths - 1)[:, None, None]
    mask = pos <= last
    if window is not None:
        mask &= sliding_window_mask(last, pos, window)
    out = _gqa_attend(q[:, None], k, v, mask)[:, 0]
    # a slot that holds nothing: the kernel's contract is zeros
    return jnp.where(lengths[:, None, None] > 0, out, 0)


def _tables(lengths, order, num_blocks):
    """Block tables for ``lengths``; pages a slot may read are listed, the
    rest of a row points at blocks that hold NaN (``_pool`` poisons them)."""
    rng = np.random.default_rng(7)
    free = list(range(1, num_blocks))
    if order == "shuffled":
        rng.shuffle(free)
    rows, live = [], set()
    for n in lengths:
        n_pages = -(-n // BS)
        if order == "shared" and rows and n_pages:
            # a shared prefix: the first page(s) of the previous slot
            mine = rows[-1][:max(1, n_pages - 1)]
            mine = mine + [free.pop(0) for _ in range(n_pages - len(mine))]
        else:
            mine = [free.pop(0) for _ in range(n_pages)]
        live.update(mine)
        rows.append(mine)
    poison = free.pop(0)
    # a freed slot's row is all scratch (the engine zeroes it); a live
    # slot's tail is poisoned: reading one page too many shows as NaN
    full = [r + [poison if r else 0] * (MB - len(r)) for r in rows]
    return np.asarray(full, np.int32), sorted(live)


def _pool(key, num_blocks, kvh, dtype, live):
    kk, kv = jax.random.split(key)
    shape = (L, num_blocks, BS, kvh, HD)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    clean = {"k": k.astype(dtype), "v": v.astype(dtype)}
    dead = np.setdiff1d(np.arange(1, num_blocks), live)  # 0 = scratch
    poisoned = {n: a.at[:, dead].set(jnp.nan) for n, a in clean.items()}
    return clean, poisoned


CASES = {
    # name: (lengths, H, KVH, table order, pages a block, window)
    "ragged": ([7, 13, 2, 24], 8, 2, "ordered", 2, None),
    "freed_slots": ([0, 9, 0, 0, 5, 0], 8, 2, "ordered", 2, None),
    "all_freed": ([0, 0, 0], 8, 2, "ordered", 2, None),
    "length_one": ([1, 1], 8, 2, "ordered", 2, None),
    "page_boundary": ([4, 8, 16], 8, 2, "ordered", 2, None),
    "past_page_boundary": ([5, 9, 17], 8, 2, "ordered", 2, None),
    "block_boundary": ([8, 9, 16, 17], 8, 2, "ordered", 2, None),
    "full_capacity": ([24, 24], 8, 2, "ordered", 2, None),
    "group_of_four": ([11, 3, 20], 8, 2, "shuffled", 2, None),
    "group_of_one": ([11, 3, 20], 4, 4, "shuffled", 2, None),
    "one_kv_head": ([6, 19], 4, 1, "ordered", 3, None),
    "out_of_order_pages": ([23, 14, 6], 8, 2, "shuffled", 2, None),
    "shared_pages": ([13, 15, 10], 8, 2, "shared", 2, None),
    "one_page_a_block": ([7, 0, 22], 8, 2, "shuffled", 1, None),
    "block_wider_than_table": ([7, 0, 22], 8, 2, "shuffled", 16, None),
    "window_inside_a_page": ([7, 13, 24], 8, 2, "shuffled", 2, 3),
    "window_across_blocks": ([7, 13, 24, 0, 10], 8, 2, "shuffled", 2, 10),
    "window_wider_than_cache": ([7, 13, 24], 8, 2, "ordered", 2, 64),
    # what a pipeline over all slots can get wrong (PIPELINE, below)
    "empty_runs_between_live_slots": (
        [5, 0, 0, 7, 0, 3, 0, 0, 0, 8, 2, 0, 0], 8, 2, "shuffled", 2, None),
    "every_slot_one_block": (
        [3, 8, 1, 7, 8, 2, 5, 8, 4, 6, 8, 1], 8, 2, "shuffled", 2, None),
    "fewer_blocks_than_buffers": ([0, 12, 0], 8, 2, "shuffled", 2, None),
    "one_block_in_all": ([0, 0, 3, 0], 8, 2, "ordered", 2, None),
    "block_multiples_and_one_past": (
        [24, 1, 16, 17, 8, 9], 8, 2, "shuffled", 2, None),
    "late_first_block_after_one_block_slot": (
        [2, 24, 3, 22, 1, 0, 23], 8, 2, "shuffled", 1, 5),
    "pools_as_pages": ([7, 0, 22, 13, 24], 20, 5, "shuffled", 2, None),
    "last_slot_empty": ([24, 9, 5, 0], 8, 2, "shuffled", 1, None),
}
PIPELINE = list(CASES)[-8:]
# cases that hand the pools over as pages [L, NB, bs * KVH, hd] with
# ``kv_heads=`` (Phi-4-mini-flash's layout: 5 KV heads fill no tile)
AS_PAGES = {"pools_as_pages"}
ARMS = [(case, arm) for case in CASES for arm in ("dense", "latent")
        if arm == "dense" or CASES[case][5] is None]


def _dense(case, dtype):
    """(got, want, an array of the result's shape and dtype) of the dense
    arm on ``case``."""
    lengths, H, KVH, order, pages, window = CASES[case]
    num_blocks = 40
    tables, live = _tables(lengths, order, num_blocks)
    clean, poisoned = _pool(jax.random.PRNGKey(3), num_blocks, KVH, dtype,
                            live)
    q = jax.random.normal(jax.random.PRNGKey(4), (len(lengths), H, HD),
                          jnp.float32).astype(dtype)
    lengths = jnp.asarray(lengths, jnp.int32)
    tables = jnp.asarray(tables)
    # the kernel sees the pool whose dead pages are NaN: one page read
    # that is not the slot's own (a table's tail, a freed slot's row, a
    # page before the window) would reach the output through 0 x NaN
    k, v, kv_heads = poisoned["k"], poisoned["v"], None
    if case in AS_PAGES:
        k, v = (a.reshape(L, num_blocks, BS * KVH, HD) for a in (k, v))
        kv_heads = KVH
    got = paged_attention(q, k, v, tables, lengths, layer=LAYER,
                          window=window, kv_heads=kv_heads,
                          pages_per_block=pages, interpret=True)
    return got, _reference(q, clean, tables, lengths, window), q


def _latent(case, dtype):
    """(got, want, like) of the latent arm (one pool ``[L, NB, bs, W]``, values
    the row's first ``VW`` columns, the caller's scale) on ``case``'s
    lengths, against ``models/mla.py:attend_rows``'s gather."""
    lengths, H, _, order, pages, _ = CASES[case]
    num_blocks = 40
    tables, live = _tables(lengths, order, num_blocks)
    clean = jax.random.normal(jax.random.PRNGKey(8),
                              (L, num_blocks, BS, W), jnp.float32
                              ).astype(dtype)
    dead = np.setdiff1d(np.arange(1, num_blocks), live)
    poisoned = clean.at[:, dead].set(jnp.nan)
    q = jax.random.normal(jax.random.PRNGKey(9), (len(lengths), H, W),
                          jnp.float32).astype(dtype)
    lengths = jnp.asarray(lengths, jnp.int32)
    tables = jnp.asarray(tables)
    cfg = SimpleNamespace(dtype=dtype, kv_lora_rank=VW, softmax_scale=0.11)
    got = latent_paged_attention(
        q, poisoned, tables, lengths, layer=LAYER, value_width=VW,
        scale=cfg.softmax_scale, pages_per_block=pages, interpret=True)
    want = attend_rows(clean, LAYER, tables, lengths - 1, lengths, cfg,
                       "gather")(q)
    want = jnp.where(lengths[:, None, None] > 0, want, 0)
    return got, want, q[..., :VW]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case,arm", ARMS, ids=["-".join(c) for c in ARMS])
def test_kernel_matches_gather_attend(case, arm, dtype):
    got, want, like = (_dense if arm == "dense" else _latent)(case, dtype)
    assert got.shape == like.shape and got.dtype == like.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=TOL[dtype], rtol=TOL[dtype])
    assert (got[np.asarray(CASES[case][0]) == 0] == 0).all()


@pytest.fixture
def forced_depth(monkeypatch):
    """Sets the pipeline's depth, which a program derives from its pools'
    page and no caller can pass: the plan is wrapped, and the jitted
    functions forget what they traced under another."""
    jitted = (kernel_module._paged_attention,
              kernel_module._latent_paged_attention)
    plan = kernel_module.pipeline_plan

    def set_depth(depth):
        monkeypatch.setattr(kernel_module, "pipeline_plan",
                            lambda *a: (plan(*a)[0], depth))
        for f in jitted:
            f.clear_cache()

    yield set_depth
    monkeypatch.undo()
    for f in jitted:
        f.clear_cache()


@pytest.mark.parametrize("depth", [3, 7])
@pytest.mark.parametrize("case", PIPELINE)
def test_pipeline_at_other_depths(case, depth, forced_depth):
    """The look-ahead's patterns under other depths than these shapes get
    by rule: three buffers (the latent cells') and seven (deeper than the
    rule goes; no divisor of a pattern's period)."""
    forced_depth(depth)
    test_kernel_matches_gather_attend(case, "dense", jnp.float32)


# (page rows, width, bytes an element, pools, blocks a table row) of the
# five serving cells' decode programs
CELL_PAGES = {
    "serve-chat-steady": (16 * 8, 128, 2, 2, 160),
    "serve-smallthinker-long-context": (16 * 4, 128, 2, 2, 897),
    "serve-phi4flash-reasoning": (16 * 10, 128, 2, 2, 257),
    "serve-longcat-long-answers": (16, 640, 2, 1, 224),
    "serve-gigachat-long-answers": (16, 640, 2, 1, 320),
}


def test_pipeline_plan_at_the_cells_pages():
    """At the five cells' pages the plan keeps a block's 1024 rows (Phi-4's
    pages of 160 rows: 960) and at least three buffers a pool, with a MiB
    of copies in flight behind the block attended wherever the budget
    holds that (the latent cells' 1.25 MiB blocks: two of them), and all
    pools' buffers inside the budget; a table one block wide clips the
    pages to that one and deepens the pipeline, which counts blocks over
    all slots, to its most."""
    module = kernel_module
    depths = {}
    for cell, (page_rows, width, itemsize, n_pools, mb) in CELL_PAGES.items():
        pages, depth = pipeline_plan(page_rows, width, itemsize, n_pools, mb)
        assert pages == 1024 // page_rows, cell
        block = n_pools * pages * page_rows * width * itemsize
        assert depth >= 3 and depth * block <= module._PIPELINE_BYTES, cell
        assert (depth - 1) * block >= module._IN_FLIGHT_BYTES, cell
        assert pipeline_plan(page_rows, width, itemsize, n_pools, 1) == (
            1, module._MAX_DEPTH), cell
        depths[cell] = depth
    assert list(depths.values()) == [3, 3, 4, 3, 3]
    # a page of any size: never under three buffers, never over the most
    assert pipeline_plan(8, 128, 2, 1, 4) == (4, module._MAX_DEPTH)
    assert pipeline_plan(1024, 4096, 4, 2, 64) == (1, module._MIN_DEPTH)


def test_window_pages_before_it_are_not_read():
    """Under a sliding window the pages wholly before it are dead too: the
    kernel must skip them (fewer bytes), not mask them."""
    lengths, window = [24, 18], 6
    tables, live = _tables(lengths, "shuffled", 40)
    # only pages that overlap (length - window, length] stay alive
    keep = []
    for row, n in zip(tables, lengths):
        keep += [int(row[p]) for p in range((n - window) // BS,
                                            -(-n // BS))]
    clean, poisoned = _pool(jax.random.PRNGKey(5), 40, 2, jnp.float32, keep)
    q = jax.random.normal(jax.random.PRNGKey(6), (2, 8, HD), jnp.float32)
    lengths = jnp.asarray(lengths, jnp.int32)
    got = paged_attention(q, poisoned["k"], poisoned["v"],
                          jnp.asarray(tables), lengths, layer=LAYER,
                          window=window, pages_per_block=2, interpret=True)
    want = _reference(q, clean, jnp.asarray(tables), lengths, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
