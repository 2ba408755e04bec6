"""Paged decode attention in Pallas for TPU: one query token a slot, the
slot's live blocks read in place out of the stacked KV pool.

The pool ``[L, NB, bs, KVH, hd]`` stays in HBM (``memory_space=ANY``) and is
never sliced as a value: the layer is a scalar the kernel is handed (so the
layers of a model share ONE traced and lowered kernel: lowering a Pallas
kernel is Python work no compile cache saves) and a page is addressed as
``pool[layer, block_tables[s, p]]``, ``bs * KVH * hd`` contiguous elements.
Per slot only pages ``p < ceil(length / bs)`` are copied (and, under a
sliding window, none that lie wholly before it), ``pages_per_block`` of them
a compute block, double-buffered: while a block is attended the next one
(the same slot's, or the first block of the next slot that holds anything)
is already in flight.  A slot with length 0 copies nothing and returns zeros.

A page's rows are ``(token, kv head)`` pairs, so a block of P pages is a
``[P * bs * KVH, hd]`` key matrix.  All H query heads are multiplied against
all of it in ONE matmul and the pairs of another KV group are masked out
with the positions past ``length``: the MXU is bound by the key tiles it
loads, which are the same either way, and no per-head slice of a page (a
strided sublane read of packed bf16) is ever taken.  Keys, values and
probabilities enter the matmuls in the pool's dtype, scores, softmax and the
accumulator are float32: what ``models/generation._gqa_attend`` does on the
gathered cache, which stays the reference this kernel is tested against.
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


def _kernel(layer_ref, len_ref, tab_ref, q_ref, group_ref, tok_ref, k_hbm,
            v_hbm, o_ref, kbuf, vbuf, sems, state, *, window, pages,
            block_size, max_blocks, scale):
    s = pl.program_id(0)
    layer = layer_ref[0]
    nslots = pl.num_programs(0)
    rows = kbuf.shape[1] * kbuf.shape[2]  # (token, kv head) pairs a block
    tokens = pages * block_size

    @pl.when(s == 0)
    def _init():
        state[0] = 0  # buffer of the next slot's first block
        state[1] = 0  # 1: that block's copies are already in flight
        # pages a block does not copy keep what the buffer held before:
        # finite (zeros, then older pages), so a masked 0 x stale stays 0
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def first_token(length):
        return 0 if window is None else jnp.maximum(length - window, 0)

    def first_block(length):
        return 0 if window is None else lax.div(first_token(length), tokens)

    def copies(slot, j, buf, act):
        """start / wait the live pages of compute block j of ``slot``."""
        length = len_ref[slot]
        n_pages = lax.div(length + block_size - 1, block_size)
        lo_page = (0 if window is None
                   else lax.div(first_token(length), block_size))
        for p in range(pages):
            page = j * pages + p

            @pl.when((page >= lo_page) & (page < n_pages))
            def _():
                blk = tab_ref[slot * max_blocks + page]
                for w, (hbm, buffer) in enumerate(((k_hbm, kbuf),
                                                   (v_hbm, vbuf))):
                    dma = pltpu.make_async_copy(
                        hbm.at[layer, blk], buffer.at[buf, p],
                        sems.at[w, buf])
                    if act == "start":
                        dma.start()
                    else:
                        dma.wait()

    length = len_ref[s]

    @pl.when(length == 0)
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _attend():
        j0 = first_block(length)
        j1 = lax.div(length + tokens - 1, tokens)
        buf0 = state[0]

        @pl.when(state[1] == 0)
        def _():
            copies(s, j0, buf0, "start")

        q = q_ref[...]
        lo = first_token(length)

        def body(j, carry):
            m, l, acc = carry
            buf = jnp.bitwise_and(buf0 + (j - j0), 1)
            nxt = 1 - buf

            @pl.when(j + 1 < j1)
            def _():
                copies(s, j + 1, nxt, "start")

            @pl.when(j + 1 == j1)
            def _():
                # the next slot that holds anything: its first block
                # travels while this slot's last one is attended
                ns = lax.fori_loop(
                    s + 1, nslots,
                    lambda i, n: jnp.where(
                        (n == nslots) & (len_ref[i] > 0), i, n), nslots)

                @pl.when(ns < nslots)
                def _():
                    copies(ns, first_block(len_ref[ns]), nxt, "start")

                state[0] = nxt
                state[1] = (ns < nslots).astype(jnp.int32)

            copies(s, j, buf, "wait")
            k = kbuf[buf].reshape(rows, kbuf.shape[-1])
            v = vbuf[buf].reshape(rows, vbuf.shape[-1])
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
            return m_new, l, acc

        H, hd = q_ref.shape
        m, l, acc = lax.fori_loop(
            j0, j1, body,
            (jnp.full((H, 1), _NEG_INF, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, hd), jnp.float32)))
        o_ref[...] = (acc / l).astype(o_ref.dtype)


# (token, kv head) rows a compute block holds unless the caller says
# otherwise: 8 pages of 16 tokens x 8 KV heads.  [H, rows] float32 scores
# are the kernel's largest value.
_BLOCK_ROWS = 1024


def paged_attention(q, k_pool, v_pool, block_tables, lengths, *, layer,
                    window: int | None = None,
                    pages_per_block: int | None = None,
                    interpret: bool | None = None):
    """Attention of one query token a slot over its paged cache.

    q ``[b, H, hd]``; k_pool / v_pool ``[L, NB, bs, KVH, hd]`` (the whole
    stacked pool; ``layer`` an int or int32 scalar, a run-time value of the
    one program all layers share); block_tables ``[b, MB]`` int32;
    lengths ``[b]`` int32, the number of cached positions the token sees
    (its own included), 0 for a slot that holds nothing.  ``window``: only
    the last ``window`` positions are visible (``ops.attention.
    sliding_window_mask``).  Returns ``[b, H, hd]`` in q's dtype.

    Off-TPU this runs the Pallas interpreter (slow; tests use small
    shapes).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _paged_attention(q, k_pool, v_pool, block_tables, lengths,
                            jnp.asarray(layer, jnp.int32), window=window,
                            pages_per_block=pages_per_block,
                            interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("window", "pages_per_block", "interpret"))
def _paged_attention(q, k_pool, v_pool, block_tables, lengths, layer, *,
                     window, pages_per_block, interpret):
    b, H, hd = q.shape
    L, NB, bs, KVH, _ = k_pool.shape
    MB = block_tables.shape[1]
    page_rows = bs * KVH
    P = pages_per_block or max(1, _BLOCK_ROWS // page_rows)
    P = min(P, MB)
    rows = P * page_rows
    # a page as a matrix of (token, kv head) rows: a view, not a copy
    k_pages = k_pool.reshape(L, NB, page_rows, hd)
    v_pages = v_pool.reshape(L, NB, page_rows, hd)
    # constants of the program: which rows belong to a head's KV group,
    # and a row's position inside its block
    r = np.arange(rows, dtype=np.int32)[None, :]
    group = np.where(
        r % KVH == np.arange(H, dtype=np.int32)[:, None] // (H // KVH),
        0.0, _NEG_INF).astype(np.float32)  # [H, rows]
    tok = r // KVH  # [1, rows]

    kernel = functools.partial(
        _kernel, window=window, pages=P, block_size=bs, max_blocks=MB,
        scale=float(hd) ** -0.5)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, H, hd), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec((H, rows), lambda s, *_: (0, 0)),
                pl.BlockSpec((1, rows), lambda s, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, H, hd), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, P, page_rows, hd), k_pool.dtype),
                pltpu.VMEM((2, P, page_rows, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer.reshape(1), lengths.astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32), q, group, tok, k_pages,
      v_pages)
