"""Paged decode attention in Pallas for TPU: one query token a slot, the
slot's live blocks read in place out of the stacked KV pool.

The pool ``[L, NB, bs, KVH, hd]`` stays in HBM (``memory_space=ANY``) and is
never sliced as a value: the layer is a scalar the kernel is handed (so the
layers of a model share ONE traced and lowered kernel: lowering a Pallas
kernel is Python work no compile cache saves) and a page is addressed as
``pool[layer, block_tables[s, p]]``, ``bs * KVH * hd`` contiguous elements.
Per slot only pages ``p < ceil(length / bs)`` are copied (and, under a
sliding window, none that lie wholly before it), ``pages`` of them a compute
block.  A slot with length 0 copies nothing and returns zeros.

The page copies are a pipeline ``depth`` buffers a pool deep.  The kernel
takes a call's live compute blocks as ONE sequence over all slots (a slot's
blocks from the first its window leaves to its last, then those of the next
slot that holds anything) and a *cursor* walks that sequence ``depth - 1``
blocks ahead of the block being attended: the first grid step starts the
copies of the sequence's first ``depth - 1`` blocks, and every block, once
its own copies have been waited for, starts those of the block the cursor
is at, into the buffer attended just before, and moves the cursor on.  So
``depth - 1`` blocks' copies are on their way while one is attended,
whichever slots they belong to: a slot one block long (most of a windowed
layer's and of the latent cells' slots hold one to six) gives the pipeline
no reason to drain.  Empty slots cost the cursor one read of a table of
links (``_init``).  At the sequence's end the cursor stops, so every copy
started is waited for exactly once, by the block it belongs to, and none is
in flight when the last grid step returns.  Between grid steps the cursor
lives in SMEM, inside a slot's loop it is carried.

What the one scalar core does a call is what bounds a call of small pages
(PERF.md section 6, PR 50), so two things keep it short.  **Runs of pages.**
Where ``run`` consecutive entries of a slot's table name adjacent blocks of
the pool, in order, ONE descriptor copies the ``run`` pages
(``hbm[layer, blk:blk + run]`` into ``run`` pages of the buffer); the serve
loop's block manager hands a slot its blocks in such runs
(``llm/engine.py:_BlockManager``).  ``_call`` works out, from the tables and
the lengths (``run_flags``, a small XLA op), one flag a slot and compute
block: whether every group of ``run`` entries that is live whole is such a
run.  A flagged block starts a descriptor a group, and a page at a time
only what the length or the window's start cut off a group; any other
block starts a page a descriptor, as every block did before.  The bytes
copied, where they land and the semaphore's count are the same either way,
so the waits and every sum are too: for equal pool contents the output is
the same to the bit whatever the block ids.  A branch costs the core more
than a descriptor, so a block all live starts its copies in one straight
run at constant offsets and any other in straight runs of powers of two, a
branch a set bit of their number.  **Slots a grid step.**  A grid step
costs 0.4 us whether its slot holds anything, so a step takes up to
``_SLOTS_A_STEP`` slots (the largest divisor of their number) and loops
over them; the cursor walks the same sequence.

Who chooses: ``pipeline_plan`` and ``page_run``, static functions of what
``_call`` sees (a page's bytes, the number of pools, the table's width) and
of no option, so a program's depth and run are constants of that program.
``_init`` zeroes all
``depth`` buffers once: a page a block does not copy (past the length,
before the window) keeps what its buffer held before, zeros and then older
pages, which is finite, so its masked product stays 0.

A page's rows are ``(token, kv head)`` pairs, so a block of P pages is a
``[P * bs * KVH, hd]`` key matrix.  All H query heads are multiplied against
all of it in ONE matmul and the pairs of another KV group are masked out
with the positions past ``length``: the MXU is bound by the key tiles it
loads, which are the same either way, and no per-head slice of a page (a
strided sublane read of packed bf16) is ever taken.  Keys, values and
probabilities enter the matmuls in the pool's dtype, scores, softmax and the
accumulator are float32: what ``models/paged_generation._gqa_attend`` does
on the gathered cache, which stays the reference this kernel is tested
against.

Two arms of the one kernel, picked by what the caller hands it:

* ``paged_attention`` (the dense arm): two pools of equal head width,
  ``[L, NB, bs, KVH, hd]`` keys and values, ``hd ** -0.5``: a
  Llama/Mistral cache (``models/paged_generation.py``).
* ``latent_paged_attention`` (the latent arm): ONE pool ``[L, NB, bs, W]``
  of latent rows, one row a token that all H heads share.  A page is copied
  once; the keys are its whole width, the values its first ``value_width``
  columns (a lane-aligned slice of what is already in VMEM), and the scale
  is the caller's: absorbed latent attention (``models/mla.py``), whose
  softmax scale belongs to the up-projected head, not to the row's width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# words of the kernel's SMEM state before its table of links: the cursor
# (slot, block, buffer) of the next compute block whose copies start
_CURSOR = 3


def _kernel(layer_ref, len_ref, tab_ref, run_ref, q_ref, group_ref, tok_ref,
            *refs, window, pages, run, block_size, max_blocks, scale,
            value_width=None):
    if value_width is None:  # dense arm: a pool of keys and one of values
        k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, state = refs
        pools = ((k_hbm, kbuf), (v_hbm, vbuf))
    else:  # latent arm: the values are columns of the key page
        k_hbm, o_ref, kbuf, sems, state = refs
        pools = ((k_hbm, kbuf),)
    step = pl.program_id(0)
    layer = layer_ref[0]
    nslots = len_ref.shape[0]
    depth = kbuf.shape[0]  # buffers a pool
    rows = kbuf.shape[1] * kbuf.shape[2]  # (token, kv head) pairs a block
    tokens = pages * block_size

    def first_token(length):
        return 0 if window is None else jnp.maximum(length - window, 0)

    def first_block(length):
        return 0 if window is None else lax.div(first_token(length), tokens)

    def end_block(length):
        return lax.div(length + tokens - 1, tokens)

    def length_of(slot):  # ``slot`` may be the sequence's end, ``nslots``
        return len_ref[jnp.minimum(slot, nslots - 1)]

    def next_buffer(buf):
        return jnp.where(buf + 1 == depth, 0, buf + 1)

    def live_pages(length, j):
        """(first, number) of the pages of compute block j that a slot of
        ``length`` positions reads: one run of the block's ``pages``."""
        n_pages = lax.div(length + block_size - 1, block_size)
        lo_page = (0 if window is None
                   else lax.div(first_token(length), block_size))
        lo = jnp.clip(lo_page - j * pages, 0, pages)
        return lo, jnp.clip(n_pages - j * pages, 0, pages) - lo

    def start_copies(slot, length, j, buf):
        """start the copies of block j of ``slot`` into buffer ``buf``.
        Where the block's flag is set (every group of ``run`` table entries
        that is live whole names adjacent blocks of the pool, ``_call``),
        one descriptor a group and a page a descriptor for what the length
        or the window's start cut off a group; else a page a descriptor.
        A block all live (most are) starts its copies in one straight run
        at constant offsets, any other in straight runs of powers of two,
        a branch each: a branch costs the scalar core more than a copy."""
        lo, n = live_pages(length, j)

        def start(p, size):
            blk = tab_ref[slot * max_blocks + j * pages + p]
            if size > 1:  # a page is indexed, a run of them sliced
                blk, p = pl.ds(blk, size), pl.ds(p, size)
            for w, (hbm, buffer) in enumerate(pools):
                pltpu.make_async_copy(hbm.at[layer, blk], buffer.at[buf, p],
                                      sems.at[w, buf]).start()

        def start_many(first, count, most, size):
            """``count`` (under ``most``) copies of ``size`` pages each,
            from page ``first`` on: a branch a set bit of ``count``."""
            chunk = 1 << ((most - 1).bit_length() - 1) if most > 1 else 0
            while chunk:
                def straight(first=first, chunk=chunk):
                    for i in range(chunk):
                        start(first + i * size, size)

                took = jnp.bitwise_and(count, chunk)
                pl.when(took != 0)(straight)
                first = first + took * size
                chunk //= 2

        def by(size):
            """the block in copies of ``size`` pages; the pages that the
            length or the window's start cut off a group one by one"""
            def whole():  # all live: constant offsets
                for p in range(0, pages, size):
                    start(p, size)

            def part():
                head = 0 if window is None or size == 1 else jnp.minimum(
                    lax.rem(size - lax.rem(lo, size), size), n)
                groups_live = lax.div(n - head, size)
                if window is not None:
                    start_many(lo, head, size, 1)
                start_many(lo + head, groups_live, pages // size, size)
                start_many(lo + head + groups_live * size,
                           n - head - groups_live * size, size, 1)

            pl.when(n == pages)(whole)
            pl.when(n < pages)(part)

        if run == 1:
            by(1)
        else:
            at = slot * -(-max_blocks // pages) + j
            lax.cond(run_ref[at] != 0, functools.partial(by, run),
                     functools.partial(by, 1))

    def wait_copies(length, j, buf):
        """wait for what ``start_copies`` started for block j of a slot of
        ``length`` positions.  A DMA semaphore counts bytes, so one wait
        takes a run of pages: the block's live pages in power-of-two runs,
        a wait a set bit of their number (one for a block all live)."""
        _, n = live_pages(length, j)

        def wait(size):
            for w, (_, buffer) in enumerate(pools):
                span = buffer.at[buf, pl.ds(0, size)]  # its bytes, not where
                pltpu.make_async_copy(span, span, sems.at[w, buf]).wait()

        size = 1 << (pages.bit_length() - 1)
        while size:
            pl.when(jnp.bitwise_and(n, size) != 0)(
                functools.partial(wait, size))
            size //= 2

    def slot_after(slot):
        """(slot, first block) of the first slot after ``slot`` that holds
        anything; ``nslots`` is the sequence's end."""
        slot = state[_CURSOR + slot + 1]
        return slot, first_block(length_of(slot))

    def produce(cursor):
        """start the copies of the block the cursor ``(slot, block, buffer)``
        is at and return the cursor moved on; at the sequence's end, where
        it stays, nothing starts."""
        slot, j, buf = cursor
        more = slot < nslots
        length = length_of(slot)

        @pl.when(more)
        def _():
            start_copies(slot, length, j, buf)

        slot, j = lax.cond(more & (j + 1 >= end_block(length)),
                           lambda: slot_after(slot), lambda: (slot, j + 1))
        return slot, j, next_buffer(buf)

    @pl.when(step == 0)
    def _init():
        # pages a block does not copy keep what the buffer held before:
        # finite (zeros, then older pages), so a masked 0 x stale stays 0
        for _, buffer in pools:
            buffer[...] = jnp.zeros_like(buffer)

        # state[_CURSOR + i]: the first slot from slot i on that holds
        # anything, so the cursor steps over empty slots in one read
        def link(i, nxt):
            slot = nslots - 1 - i
            state[_CURSOR + slot + 1] = nxt
            return jnp.where(len_ref[slot] > 0, slot, nxt)

        state[_CURSOR] = lax.fori_loop(0, nslots, link, nslots)
        # the sequence's first depth - 1 blocks, whichever slots they are
        # of, go into buffers 0 .. depth - 2; each block attended then
        # starts one more
        zero = jnp.int32(0)
        cursor = lax.fori_loop(0, depth - 1, lambda _, c: produce(c),
                               (*slot_after(zero - 1), zero))
        for i, word in enumerate(cursor):
            state[i] = word

    def one_slot(i, _):
        length = len_ref[step * q_ref.shape[0] + i]

        @pl.when(length == 0)
        def _empty():
            o_ref[i] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

        @pl.when(length > 0)
        def _attend():
            q = q_ref[i]
            lo = first_token(length)

            def body(j, carry):
                m, l, acc, cursor = carry
                # the cursor's buffer is the one attended last; this
                # block's is the next of the ring.  Once it has landed, the
                # block depth - 1 ahead may go where the last one was
                buf = next_buffer(cursor[2])
                wait_copies(length, j, buf)
                cursor = produce(cursor)
                k = kbuf[buf].reshape(rows, kbuf.shape[-1])
                v = (vbuf[buf].reshape(rows, vbuf.shape[-1])
                     if value_width is None else k[:, :value_width])
                sc = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                sc = sc * scale + group_ref[...]
                pos = tok_ref[...] + j * tokens
                sc = jnp.where((pos < length) & (pos >= lo), sc, _NEG_INF)
                m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(sc - m_new)
                l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                acc = alpha * acc + lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                return m_new, l, acc, cursor

            H = q_ref.shape[1]
            m, l, acc, cursor = lax.fori_loop(
                first_block(length), end_block(length), body,
                (jnp.full((H, 1), _NEG_INF, jnp.float32),
                 jnp.zeros((H, 1), jnp.float32),
                 jnp.zeros(o_ref.shape[1:], jnp.float32),
                 tuple(state[n] for n in range(_CURSOR))))
            for n, word in enumerate(cursor):
                state[n] = word
            o_ref[i] = (acc / l).astype(o_ref.dtype)

    lax.fori_loop(0, q_ref.shape[0], one_slot, None)


# (token, kv head) rows a compute block holds unless the caller says
# otherwise: 8 pages of 16 tokens x 8 KV heads.  [H, rows] float32 scores
# are the kernel's largest value.
_BLOCK_ROWS = 1024

# Bytes of page copies to keep on their way while a block is attended: what
# HBM delivers in a copy's start-to-done latency (819 GB/s x 1.3 us).
# Measured alone on a v5e (PERF.md section 6, PR 49): Phi-4-mini-flash's
# full layers, 0.47 MiB a block, take 749 us a call with one block in
# flight and 460, 462, 462 with two, three and five.
_IN_FLIGHT_BYTES = 1 << 20
# VMEM the page buffers of all pools may take together.  A kernel is given
# 16 MiB unless its call asks for more; the [H, rows] float32 scores, their
# exponentials and the probabilities' copy are 0.6 MiB at 64 heads, q and
# the output (twice each) 0.3 MiB, so a quarter of the 16 is far from the
# limit.  A dense block of 1024 rows x 128 bf16 in two pools is 0.5 MiB, a
# latent block of 1024 rows x 640 1.25 MiB: three buffers are 3.75 MiB.
_PIPELINE_BYTES = 4 << 20
# buffers a pool.  Under 3 nothing is in flight across a block's own wait;
# past 8 a small block's look-ahead only lengthens the fill.
_MIN_DEPTH, _MAX_DEPTH = 3, 8


def pipeline_plan(page_rows: int, width: int, itemsize: int, n_pools: int,
                  max_blocks: int) -> tuple[int, int]:
    """``(pages, depth)`` of a call: pages a compute block, and buffers of
    that size a pool that the copy pipeline runs through.  A static function
    of the pools' page ``[page_rows, width]``, their number and the block
    table's width, so a constant of each program.

    ``pages``: as many as ``_BLOCK_ROWS`` rows hold, never more than a
    table lists.  ``depth``: the block attended and as many behind it as
    hold ``_IN_FLIGHT_BYTES`` (blocks of all pools; the look-ahead is
    counted over all slots, so a table one block wide is no reason for a
    shallower one), within ``_PIPELINE_BYTES`` of VMEM, from ``_MIN_DEPTH``
    to ``_MAX_DEPTH``: 3 buffers at the cells' blocks of 0.5 and 1.25 MiB,
    4 at Phi-4-mini-flash's 0.47."""
    pages = min(max(1, _BLOCK_ROWS // page_rows), max_blocks)
    block_bytes = n_pools * pages * page_rows * width * itemsize
    depth = min(1 + -(-_IN_FLIGHT_BYTES // block_bytes),
                _PIPELINE_BYTES // block_bytes, _MAX_DEPTH)
    return pages, max(depth, _MIN_DEPTH)


# Bytes a copy descriptor should move where a slot's blocks lie side by side
# in the pool, and the most pages it takes to get there.  Measured alone on a
# v5e at the cells' shapes (PERF.md section 6, PR 50; us a call at runs of
# 1 / 2 / 4 / 8 / 16 pages): 20 KB latent pages 234 / 200 / 189 / 189 / 187
# (GigaChat's call) and 154 / 138 / 131 / 136 / 132 (LongCat's), SmallThinker's
# 16 KB pages 191 / 150 / 145 / 143 / 144: flat from 64 KiB on, and a longer
# run only holds more blocks ahead of a slot.
_RUN_BYTES = 64 << 10
_MAX_RUN = 8
# slots a grid step: a step costs 0.4 us whether its slots hold anything
_SLOTS_A_STEP = 8


def page_run(page_rows: int, width: int, itemsize: int, pages: int) -> int:
    """Pages a copy descriptor where a slot's blocks are adjacent in the
    pool: the smallest power of two that makes ``_RUN_BYTES`` of a pool's
    page ``[page_rows, width]``, as far as powers of two divide ``pages``
    (a run never straddles two compute blocks) and never past
    ``_MAX_RUN``.  A static function of shapes, asked by ``_call`` for the
    kernel and by the serve loop's block manager, which hands a slot its
    blocks in aligned runs of this many (``llm/engine.py:_BlockManager``):
    4 at the latent pools' 20 KB pages and SmallThinker's 16, 2 at
    Mistral's 32 and Phi-4-mini-flash's 40."""
    run = 1
    while (run * page_rows * width * itemsize < _RUN_BYTES
           and run < _MAX_RUN and pages % (2 * run) == 0):
        run *= 2
    return run


def run_flags(block_tables, lengths, *, run: int, pages: int,
              block_size: int, window: int | None, xp=jnp):
    """What of a call's tables one descriptor a ``run`` of pages can copy.
    Returns ``(in_runs, whole, live)``: ``live[s]``, the pages slot s
    reads (under its length, past its window's start); ``whole[s, j, g]``,
    whether group g (its ``run`` table entries) of the slot's compute block
    j is live whole; ``in_runs[s, j]``, whether every such group of the
    block names ``run`` adjacent blocks of the pool in order, which is when
    the kernel copies the block's groups whole (a block with one group
    that is no run is copied a page a descriptor).  ``xp``: for the
    device's tables (``_call``) and, with numpy, the host's
    (``LLMEngine._live_pages``) alike."""
    b, MB = block_tables.shape
    n = MB // run
    groups = block_tables[:, :n * run].reshape(b, n, run)
    adjacent = (groups == groups[:, :, :1]
                + np.arange(run, dtype=np.int32)).all(axis=-1)
    first = np.arange(n, dtype=np.int32) * run  # a group's first page
    end = -(-lengths // block_size)
    start = (0 * end if window is None
             else xp.maximum(lengths - window, 0) // block_size)
    whole = (first >= start[:, None]) & (first + run <= end[:, None])
    # by compute block: the table's last block may be cut short
    shape = (b, -(-MB // pages), pages // run)
    pad = ((0, 0), (0, shape[1] * shape[2] - n))
    whole = xp.pad(whole, pad).reshape(shape)
    adjacent = xp.pad(adjacent, pad).reshape(shape)
    return (adjacent | ~whole).all(axis=-1), whole, end - start


def paged_attention(q, k_pool, v_pool, block_tables, lengths, *, layer,
                    window: int | None = None,
                    scale: float | None = None,
                    kv_heads: int | None = None,
                    pages_per_block: int | None = None,
                    interpret: bool | None = None):
    """The dense arm: attention of one query token a slot over its paged
    cache of keys and values.

    q ``[b, H, hd]``; k_pool / v_pool ``[L, NB, bs, KVH, hd]`` (the whole
    stacked pool; ``layer`` an int or int32 scalar, a run-time value of the
    one program all layers share); block_tables ``[b, MB]`` int32;
    lengths ``[b]`` int32, the number of cached positions the token sees
    (its own included), 0 for a slot that holds nothing.  ``window``: only
    the last ``window`` positions are visible (``ops.attention.
    sliding_window_mask``).  ``scale``: the scores' factor, ``hd ** -0.5``
    unless given.  ``kv_heads``: the pools come as pages already,
    ``[L, NB, bs * kv_heads, hd]`` (a number of KV heads that is no
    multiple of a tile's 8 sublanes has no unpadded 5-D layout on the chip,
    and XLA copies such a pool to reshape it).  Returns ``[b, H, hd]`` in
    q's dtype.

    Off-TPU this runs the Pallas interpreter (slow; tests use small
    shapes).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _paged_attention(q, k_pool, v_pool, block_tables, lengths,
                            jnp.asarray(layer, jnp.int32), window=window,
                            scale=scale, kv_heads=kv_heads,
                            pages_per_block=pages_per_block,
                            interpret=interpret)


def latent_paged_attention(q, pool, block_tables, lengths, *, layer,
                           value_width: int, scale: float,
                           pages_per_block: int | None = None,
                           interpret: bool | None = None):
    """The latent arm: H query heads of one token a slot against ONE shared
    row a cached token.

    q ``[b, H, W]`` (the absorbed query, as wide as a row); pool
    ``[L, NB, bs, W]`` (``layer`` as above: here the attention block's
    index in the stacked pool); block_tables / lengths as above.  Scores
    are ``q . row * scale`` over the whole width; the probabilities weight
    the row's first ``value_width`` columns, which are read out of the page
    that the keys came in.  Returns ``[b, H, value_width]`` in q's dtype.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _latent_paged_attention(
        q, pool, block_tables, lengths, jnp.asarray(layer, jnp.int32),
        value_width=value_width, scale=scale,
        pages_per_block=pages_per_block, interpret=interpret)


def _call(q, pools, block_tables, lengths, layer, *, window, block_size,
          kv_heads, out_width, pages_per_block, interpret, **arm):
    """The one ``pallas_call`` of both arms.  ``pools``: the stacked
    pool(s) with a page as a matrix ``[L, NB, bs * kv_heads, width]``;
    ``arm``: what else ``_kernel`` is told (scale, value width)."""
    b, H, _ = q.shape
    _, _, page_rows, width = pools[0].shape
    MB = block_tables.shape[1]
    n = len(pools)
    P, depth = pipeline_plan(page_rows, width, pools[0].dtype.itemsize, n, MB)
    if pages_per_block:  # the tests' lever
        P = min(pages_per_block, MB)
    R = page_run(page_rows, width, pools[0].dtype.itemsize, P)
    G = max(g for g in range(1, _SLOTS_A_STEP + 1) if b % g == 0)
    rows = P * page_rows
    # constants of the program: which rows belong to a head's KV group,
    # and a row's position inside its block
    r = np.arange(rows, dtype=np.int32)[None, :]
    group = np.where(
        r % kv_heads == np.arange(H, dtype=np.int32)[:, None]
        // (H // kv_heads), 0.0, _NEG_INF).astype(np.float32)  # [H, rows]
    tok = r // kv_heads  # [1, rows]
    in_runs, _, _ = run_flags(block_tables, lengths, run=R, pages=P,
                              block_size=block_size, window=window)
    return pl.pallas_call(
        functools.partial(_kernel, window=window, block_size=block_size,
                          pages=P, run=R, max_blocks=MB, **arm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b // G,),
            in_specs=[
                pl.BlockSpec((G, H, q.shape[2]), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec((H, rows), lambda s, *_: (0, 0)),
                pl.BlockSpec((1, rows), lambda s, *_: (0, 0)),
            ] + [pl.BlockSpec(memory_space=pl.ANY)] * n,
            out_specs=pl.BlockSpec((G, H, out_width),
                                   lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((depth, P, page_rows, width), pool.dtype)
                for pool in pools
            ] + [
                pltpu.SemaphoreType.DMA((n, depth)),
                pltpu.SMEM((_CURSOR + b + 1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, H, out_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer.reshape(1), lengths.astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32),
      in_runs.reshape(-1).astype(jnp.int32),
      q, group, tok, *pools)


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "kv_heads", "pages_per_block", "interpret"))
def _paged_attention(q, k_pool, v_pool, block_tables, lengths, layer, *,
                     window, scale, kv_heads, pages_per_block, interpret):
    hd = q.shape[2]
    if kv_heads is None:
        L, NB, bs, KVH, _ = k_pool.shape
        # a page as a matrix of (token, kv head) rows: a view, not a copy
        k_pages = k_pool.reshape(L, NB, bs * KVH, hd)
        v_pages = v_pool.reshape(L, NB, bs * KVH, hd)
    else:
        KVH, bs = kv_heads, k_pool.shape[2] // kv_heads
        k_pages, v_pages = k_pool, v_pool
    return _call(q, (k_pages, v_pages), block_tables, lengths, layer,
                 window=window, block_size=bs, kv_heads=KVH, out_width=hd,
                 pages_per_block=pages_per_block, interpret=interpret,
                 scale=float(hd) ** -0.5 if scale is None else float(scale))


@functools.partial(jax.jit, static_argnames=(
    "value_width", "scale", "pages_per_block", "interpret"))
def _latent_paged_attention(q, pool, block_tables, lengths, layer, *,
                            value_width, scale, pages_per_block, interpret):
    return _call(q, (pool,), block_tables, lengths, layer, window=None,
                 block_size=pool.shape[2], kv_heads=1, out_width=value_width,
                 pages_per_block=pages_per_block, interpret=interpret,
                 scale=float(scale), value_width=value_width)
