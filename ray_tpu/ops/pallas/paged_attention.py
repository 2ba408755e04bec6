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


def _kernel(layer_ref, len_ref, tab_ref, q_ref, group_ref, tok_ref, *refs,
            window, pages, block_size, max_blocks, scale, value_width=None):
    if value_width is None:  # dense arm: a pool of keys and one of values
        k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, state = refs
        pools = ((k_hbm, kbuf), (v_hbm, vbuf))
    else:  # latent arm: the values are columns of the key page
        k_hbm, o_ref, kbuf, sems, state = refs
        pools = ((k_hbm, kbuf),)
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
        for _, buffer in pools:
            buffer[...] = jnp.zeros_like(buffer)

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
                for w, (hbm, buffer) in enumerate(pools):
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
            return m_new, l, acc

        H = q_ref.shape[0]
        m, l, acc = lax.fori_loop(
            j0, j1, body,
            (jnp.full((H, 1), _NEG_INF, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros(o_ref.shape, jnp.float32)))
        o_ref[...] = (acc / l).astype(o_ref.dtype)


# (token, kv head) rows a compute block holds unless the caller says
# otherwise: 8 pages of 16 tokens x 8 KV heads.  [H, rows] float32 scores
# are the kernel's largest value.
_BLOCK_ROWS = 1024


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


def _call(kernel, q, pools, block_tables, lengths, layer, *, kv_heads,
          out_width, pages_per_block, interpret):
    """The one ``pallas_call`` of both arms.  ``pools``: the stacked
    pool(s) with a page as a matrix ``[L, NB, bs * kv_heads, width]``."""
    b, H, _ = q.shape
    _, _, page_rows, width = pools[0].shape
    MB = block_tables.shape[1]
    P = pages_per_block or max(1, _BLOCK_ROWS // page_rows)
    P = min(P, MB)
    rows = P * page_rows
    # constants of the program: which rows belong to a head's KV group,
    # and a row's position inside its block
    r = np.arange(rows, dtype=np.int32)[None, :]
    group = np.where(
        r % kv_heads == np.arange(H, dtype=np.int32)[:, None]
        // (H // kv_heads), 0.0, _NEG_INF).astype(np.float32)  # [H, rows]
    tok = r // kv_heads  # [1, rows]
    n = len(pools)
    return pl.pallas_call(
        functools.partial(kernel, pages=P, max_blocks=MB),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, H, q.shape[2]), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec((H, rows), lambda s, *_: (0, 0)),
                pl.BlockSpec((1, rows), lambda s, *_: (0, 0)),
            ] + [pl.BlockSpec(memory_space=pl.ANY)] * n,
            out_specs=pl.BlockSpec((None, H, out_width),
                                   lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, P, page_rows, width), pool.dtype)
                for pool in pools
            ] + [
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, H, out_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer.reshape(1), lengths.astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32), q, group, tok, *pools)


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
    kernel = functools.partial(
        _kernel, window=window, block_size=bs,
        scale=float(hd) ** -0.5 if scale is None else float(scale))
    return _call(kernel, q, (k_pages, v_pages), block_tables, lengths, layer,
                 kv_heads=KVH, out_width=hd,
                 pages_per_block=pages_per_block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "value_width", "scale", "pages_per_block", "interpret"))
def _latent_paged_attention(q, pool, block_tables, lengths, layer, *,
                            value_width, scale, pages_per_block, interpret):
    kernel = functools.partial(_kernel, window=None, block_size=pool.shape[2],
                               scale=float(scale), value_width=value_width)
    return _call(kernel, q, (pool,), block_tables, lengths, layer,
                 kv_heads=1, out_width=value_width,
                 pages_per_block=pages_per_block, interpret=interpret)
