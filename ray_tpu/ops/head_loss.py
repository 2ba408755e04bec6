"""The vocabulary product and the cross-entropy as ONE function with its own
differentiation rule: three products of the head's shape (logits, dX, dW),
a sequence chunk at a time, and no ``[tokens, vocab]`` float32 array.

Autodiff of ``log_softmax(x @ W)`` keeps the float32 logits (or ``logp``) for
the backward, 2.15 GB at 16 384 tokens × 32 768 words, and XLA:TPU then runs
the product a second time rather than hold them (``fusion.284.remat``: 27.0 of
the train cell's 552 ms step, ledger PR 37).  Nothing in the mathematics needs
either: with a chunk's logits in hand, ``(softmax − onehot) × weight`` IS the
cotangent of the logits, so the rule's forward forms it once, in the type the
two backward products multiply it at, and the backward is two products.

Plain ``jax.numpy``: the products already run at the MXU's pace, what goes is
a fourth product and round trips through HBM.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ray_tpu._private import tracing

# A chunk's size, two readings on the v5e (PERF.md §6, PR 38).  Float32
# logits of at most 64 MiB stay in the chip's fast memory between the product
# and the two passes that read them (the passes 2.4 ms a step of the train
# cell against 7.5 at 256 MiB, the product 23.9 against 25.2).  A product of
# fewer than 512 rows leaves the MXU's pace (31 ms at 256 rows, 46 at 128), so
# a large vocabulary's chunk goes through HBM rather than under that.
CHUNK_LOGITS_BYTES = 64 * 2**20
CHUNK_MIN_ROWS = 512


def head_loss_chunks(rows: int, seq: int, vocab: int) -> int:
    """The fewest sequence chunks that keep a chunk's float32 logits
    (``rows`` batch rows a device holds × chunk × ``vocab``) under
    ``CHUNK_LOGITS_BYTES`` without cutting a chunk under ``CHUNK_MIN_ROWS``
    rows: static shapes in, 1 for every small model."""
    fits = max(CHUNK_LOGITS_BYTES // (4 * rows * vocab),
               -(-CHUNK_MIN_ROWS // rows))
    return -(-seq // fits)


def _chunk(x_c, head, targets_c, weights_c, constrain, with_cotangent):
    """One chunk: Σ weight × nll in float32 and, if asked, the logits'
    cotangent ``(softmax − onehot) × weight`` rounded to ``x``'s type."""
    with tracing.scope("head"):
        logits = jnp.einsum("bsh,hv->bsv", x_c, head,
                            preferred_element_type=jnp.float32)
        if constrain is not None:
            logits = constrain(logits)
    with tracing.scope("loss"):
        # every pass reads the logits themselves: a shared ``logits - top``
        # would be written out beside them; the target's logit comes out of
        # the pass that sums the exponentials, not out of a gather
        hit = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
               == targets_c[..., None])
        top = jnp.max(logits, axis=-1, keepdims=True)
        lse = top + jnp.log(
            jnp.sum(jnp.exp(logits - top), axis=-1, keepdims=True))
        picked = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        loss = jnp.sum((lse[..., 0] - picked) * weights_c)
        if not with_cotangent:
            return loss, None
        d = (jnp.exp(logits - lse) - hit) * weights_c[..., None]
        return loss, d.astype(x_c.dtype)


def _over_chunks(x, head, targets, weights, chunks, constrain,
                 with_cotangent):
    """(loss, cotangent of the logits | None) over ``chunks`` pieces of the
    sequence axis; a chunk's float32 logits die with the chunk."""
    b, s, _ = x.shape
    size = -(-s // chunks)
    whole, tail = divmod(s, size)
    if whole == 1 and not tail:
        return _chunk(x, head, targets, weights, constrain, with_cotangent)

    def piece(start, length):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, start, length, axis=1)
        with tracing.scope("head"):
            x_c = cut(x)
        with tracing.scope("loss"):
            targets_c, weights_c = cut(targets), cut(weights)
        return _chunk(x_c, head, targets_c, weights_c, constrain,
                      with_cotangent)

    def put(d, d_c, start):
        with tracing.scope("loss"):  # the fusion that forms d_c writes here
            return jax.lax.dynamic_update_slice_in_dim(d, d_c, start, axis=1)

    def body(i, carry):
        loss, d = carry
        loss_c, d_c = piece(i * size, size)
        return loss + loss_c, (put(d, d_c, i * size) if with_cotangent
                               else d)

    d = None
    if with_cotangent:
        with tracing.scope("loss"):
            d = jnp.zeros((b, s, head.shape[1]), x.dtype)
            if constrain is not None:
                d = constrain(d)
    loss, d = jax.lax.fori_loop(
        0, whole, body, (jnp.zeros((), jnp.float32), d))
    if tail:
        loss_c, d_c = piece(whole * size, tail)
        loss = loss + loss_c
        if with_cotangent:
            d = put(d, d_c, whole * size)
    return loss, d


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def head_loss(x, head, targets, weights, chunks: int = 1,
              constrain: Optional[Callable] = None):
    """Σ over tokens of ``weights`` × the cross-entropy of
    ``softmax(x @ head)`` against ``targets``.

    ``x`` [b, s, h] are the final-normed hidden states and ``head`` [h, v]
    the vocabulary matrix, both in the type the products multiply at
    (``cfg.dtype``); ``targets`` [b, s] integers; ``weights`` [b, s] float32,
    a token's share of the mean (``mask ÷ max(Σ mask, 1)``, or ``1 ÷
    tokens``) and no input of the differentiation.  ``chunks`` is static
    (:func:`head_loss_chunks`); ``constrain`` pins a chunk's logits' layout
    on a mesh.  Logits, softmax and the sum are float32.  Called without
    differentiation it forms no cotangent.
    """
    return _over_chunks(x, head, targets, weights, chunks, constrain,
                        False)[0]


def _fwd(x, head, targets, weights, chunks, constrain):
    loss, d = _over_chunks(x, head, targets, weights, chunks, constrain,
                           True)
    return loss, (x, head, d)


def _bwd(chunks, constrain, saved, g):
    x, head, d = saved
    with tracing.scope("head"):
        dx = jnp.einsum("bsv,hv->bsh", d, head,
                        preferred_element_type=jnp.float32)
        dhead = jnp.einsum("bsh,bsv->hv", x, d,
                           preferred_element_type=jnp.float32)
        # targets and weights are no inputs of the differentiation
        return ((dx * g).astype(x.dtype), (dhead * g).astype(head.dtype),
                None, None)


head_loss.defvjp(_fwd, _bwd)
