"""The paged decode-attention kernel against the gather path it replaces.

CPU, through the Pallas interpreter, at small shapes: the same pool, block
tables and lengths go through ``ops/pallas/paged_attention.py`` and through
``paged_generation._gather_kv`` + ``paged_generation._gqa_attend`` (the
plain reference, and still the path of int8 pools, meshes and every backend
but TPU).

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

from ray_tpu.models.paged_generation import _gqa_attend
from ray_tpu.models.paged_generation import _gather_kv
from ray_tpu.ops.attention import sliding_window_mask
from ray_tpu.ops.pallas.paged_attention import paged_attention

L, LAYER, BS, HD = 3, 1, 4, 16
MB = 6  # 24 positions a slot
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
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_gather_attend(case, dtype):
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
    got = paged_attention(q, poisoned["k"], poisoned["v"], tables, lengths,
                          layer=LAYER, window=window, pages_per_block=pages,
                          interpret=True)
    want = _reference(q, clean, tables, lengths, window)
    assert got.shape == q.shape and got.dtype == q.dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=TOL[dtype], rtol=TOL[dtype])
    assert (got[np.asarray(lengths) == 0] == 0).all()


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
