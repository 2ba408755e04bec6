"""The expert layer at decode shape, in Pallas for TPU: a hit expert's
three weights streamed through VMEM once, against the handful of rows that
landed on it.

When an expert gets one to three rows, the expert layer is no grouped
matmul but a weight-streaming problem: its arithmetic is nothing, and what
bounds it is how fast ``w_gate``, ``w_up`` ``[H, F]`` and ``w_down``
``[F, H]`` of the experts that got a row come out of HBM.  So the kernel
walks the HIT experts only, in the sorted order ``ops/experts.py`` made, and
for each of them

* copies its weights HBM -> VMEM in a few large DMAs (an expert whose three
  matrices pass ``_STAGE_BYTES`` is cut along F into column slices of gate /
  up and the matching rows of down, whose partial products add: a *unit* is
  one (hit expert, F slice)), double-buffered: unit u + 1 is in flight while
  unit u is multiplied;
* gathers that expert's rows of ``y`` by their token (``y`` ``[T, H]`` stays
  whole in VMEM) in tiles of ``_ROWS`` (16) rows; an expert that got more
  loops over its tiles with the unit's weights resident;
* runs gate and up (float32 accumulators), ``activation(gate, up)`` in
  float32, rounds it ONCE to the operand type for the down product
  (float32 accumulator), weighs each row in float32 and adds it to its
  token's row of the float32 result, which stays in VMEM for the whole
  call: the rows meet their tokens in sorted order, as a scatter-add of the
  live rows would add them.

An expert no pair landed on is never read; rows past an expert's count are
neither gathered nor added (a stale row of the tile's buffer is multiplied
and dropped: rows of a matmul do not mix).  No capacity, no dropped token:
all ``T * k`` pairs on one expert is ``ceil(T * k / 16)`` tiles against
one resident set of weights.

The hit list, the experts' offsets and counts and the sorted pairs' tokens
are scalar prefetch (``paged_attention`` takes its tables the same way);
the weights stay in HBM (``memory_space=ANY``) and are only ever addressed
as ``w[expert, :, slice]``.  One ``pallas_call`` an expert layer; the layers
of a model hand it their own weights of one shape, so they share ONE trace
and one Mosaic lowering (the ``jax.jit`` below).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM one stage of an expert's three weight slices may take (there are two
# stages): SmallThinker's expert (3 x 2560 x 768 bf16 = 11.8 MB) travels
# whole, LongCat's (75.5 MB) in eight slices of 256 columns (9.4 MB).
_STAGE_BYTES = 12 << 20
# rows of a tile: bf16's sublane count.  On the chip 8 and 16 read the same
# while an expert gets a handful of rows (0.518 | 0.519 ms a layer at
# SmallThinker's decode shape) and 16 reads better once it gets dozens
# (1.93 | 1.18 ms at 96 rows an expert): my chip runs, PR 32, PERF.md §6.
_ROWS = 16


def ffn_slice(H: int, F: int, itemsize: int) -> int:
    """Columns of gate / up (rows of down) a unit holds: the largest divisor
    of F in whole 128-lane tiles whose three slices fit a stage; F itself
    where F is no multiple of 128 (toy widths, through the interpreter)."""
    if F % 128:
        return F
    fits = [c for c in range(128, F + 1, 128)
            if F % c == 0 and 3 * H * c * itemsize <= _STAGE_BYTES]
    return max(fits, default=128)


def vmem_bytes(T: int, H: int, F: int, itemsize: int) -> int:
    """What the call asks of VMEM: two stages of weights, y and the result
    in float32, the tile's buffers, and room for the products' values."""
    fc = ffn_slice(H, F, itemsize)
    return (2 * 3 * H * fc * itemsize + 2 * T * H * 4 + 2 * _ROWS * H * 4
            + 6 * _ROWS * max(H, fc) * 4 + (4 << 20))


def _kernel(hit_ref, nhit_ref, start_ref, count_ref, tok_ref,  # prefetch
            wt_ref, y_ref, wg_hbm, wu_hbm, wd_hbm, out_ref,
            gbuf, ubuf, dbuf, xbuf, obuf, sems, *, activation, fc, slices,
            dtype):
    rows = _ROWS
    units = nhit_ref[0] * slices

    def copies(u, slot):
        e = hit_ref[lax.div(u, slices)]
        lo = pl.multiple_of(lax.rem(u, slices) * fc, fc)
        return (
            pltpu.make_async_copy(wg_hbm.at[e, :, pl.ds(lo, fc)],
                                  gbuf.at[slot], sems.at[0, slot]),
            pltpu.make_async_copy(wu_hbm.at[e, :, pl.ds(lo, fc)],
                                  ubuf.at[slot], sems.at[1, slot]),
            pltpu.make_async_copy(wd_hbm.at[e, pl.ds(lo, fc), :],
                                  dbuf.at[slot], sems.at[2, slot]))

    out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(units > 0)
    def _():
        for dma in copies(0, 0):
            dma.start()

    def unit(u, carry):
        slot = jnp.bitwise_and(u, 1)

        @pl.when(u + 1 < units)
        def _():
            for dma in copies(u + 1, 1 - slot):
                dma.start()

        for dma in copies(u, slot):
            dma.wait()
        e = hit_ref[lax.div(u, slices)]
        first, count = start_ref[e], count_ref[e]

        def tile(t, carry):
            base = first + t * rows
            n = jnp.minimum(count - t * rows, rows)
            for r in range(rows):
                @pl.when(r < n)
                def _():
                    xbuf[r:r + 1, :] = y_ref[pl.ds(tok_ref[base + r], 1), :]
            x = xbuf[...].astype(dtype)
            gate = jnp.dot(x, gbuf[slot], preferred_element_type=jnp.float32)
            up = jnp.dot(x, ubuf[slot], preferred_element_type=jnp.float32)
            act = activation(gate, up).astype(dtype)
            obuf[...] = jnp.dot(act, dbuf[slot],
                                preferred_element_type=jnp.float32)
            for r in range(rows):
                @pl.when(r < n)
                def _():
                    at = pl.ds(tok_ref[base + r], 1)
                    out_ref[at, :] = (out_ref[at, :]
                                      + obuf[r:r + 1, :] * wt_ref[base + r])
            return carry

        lax.fori_loop(0, lax.div(count + rows - 1, rows), tile, 0)
        return carry

    lax.fori_loop(0, units, unit, 0)


@functools.partial(jax.jit, static_argnames=("activation", "interpret"))
def _expert_decode(y, token_of, w_sorted, starts, counts, w_gate, w_up,
                   w_down, *, activation, interpret):
    T, H = y.shape
    E, _, F = w_gate.shape
    dtype = y.dtype
    fc = ffn_slice(H, F, dtype.itemsize)
    # the experts that got a row, in order, then the rest (never read)
    hit = jnp.argsort(counts == 0, stable=True).astype(jnp.int32)
    nhit = jnp.sum(counts > 0, dtype=jnp.int32).reshape(1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, activation=activation, fc=fc,
                          slices=F // fc, dtype=dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[smem, vmem, hbm, hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, H, fc), dtype),
                pltpu.VMEM((2, H, fc), dtype),
                pltpu.VMEM((2, fc, H), dtype),
                pltpu.VMEM((_ROWS, H), jnp.float32),
                pltpu.VMEM((_ROWS, H), jnp.float32),
                pltpu.SemaphoreType.DMA((3, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((T, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem_bytes(T, H, F, dtype.itemsize)),
        interpret=interpret,
    )(hit, nhit, starts.astype(jnp.int32), counts.astype(jnp.int32),
      token_of.astype(jnp.int32), w_sorted.astype(jnp.float32),
      y.astype(jnp.float32), w_gate.astype(dtype), w_up.astype(dtype),
      w_down.astype(dtype))


def expert_decode_ffn(y, token_of, w_sorted, starts, counts, w_gate, w_up,
                      w_down, *, activation, interpret: bool | None = None):
    """What the held experts add to each token, from the sorted pairs.

    y ``[T, H]``; token_of / w_sorted ``[>= T * k]``: the token and the
    float32 weight of each (token, pick) pair, the held pairs first, grouped
    by expert; starts / counts ``[E]`` int32: where each held expert's pairs
    begin and how many it got (0: its weights are not read); w_gate / w_up
    ``[E, H, F]``, w_down ``[E, F, H]``.  Products in y's dtype with float32
    accumulators, ``activation(gate, up)`` on float32.  Returns ``[T, H]``
    float32.

    Off-TPU this runs the Pallas interpreter (slow; tests use small
    shapes).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _expert_decode(y, token_of, w_sorted, starts, counts, w_gate,
                          w_up, w_down, activation=activation,
                          interpret=interpret)
