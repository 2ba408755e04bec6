"""The gated delta rule (Yang et al., arXiv:2412.06464; Gated DeltaNet): a
linear-attention layer whose state is a MATRIX a head, ``S [dk, dv]`` in
float32, that does not grow with the position.  Beside ``ops/ssm.py``, whose
state is a diagonal one; the causal convolution in front of both is
``ops/ssm.py:causal_conv1d``.

The recurrence, one value head (``k`` of unit length, ``alpha`` in (0, 1],
``beta`` in (0, 1))::

    S'_t = alpha_t S_{t-1}
    S_t  = S'_t + k_t (beta_t (v_t - S'_t^T k_t))^T
    o_t  = S_t^T q_t

**At decode** (``delta_update_records``) a layer's whole update is ONE
operation from what the projections made to the two pools: the slot's row of
``qkv``, its ``alpha`` and ``beta``, the convolution's taps, the records
``[L, R, Hv, dk, dv]`` float32 and the convolution's tails ``[L, R, (K - 1)
P, d]`` (``P = C / d`` rows of one head's width: a record's tail is whole
tiles, so a block can name it).  A request's record and tail are read once
and written once, where they lie.  Both reductions are taken of the record as
it was read, because the update is of rank one; key head ``j`` serves the
value heads ``h`` with ``h // (Hv / Hk) = j`` and is never repeated::

    u_t = S_{t-1}^T k_t
    d_t = beta_t v_t - alpha_t beta_t u_t
    o_t = alpha_t S_{t-1}^T q_t + d_t (k_t . q_t)
    S_t = alpha_t S_{t-1} + k_t d_t^T

On one TPU device that is a Pallas kernel (``delta_update_path``): a grid
step a slot, the slot's record ``[Hv, dk, dv]`` and tail ``[(K - 1) P, d]``
of one layer its blocks, both found through the slot's record number (a
prefetched scalar) and both aliased to their pools, so that only the live
slots' records and tails move and XLA never copies a pool.  The slots are
visited live ones first: the idle ones all name the scratch record 0, and a
block whose index does not change is neither fetched nor written again.
Before the head loop the step makes the token's vectors itself, from the
slot's ``qkv`` viewed ``[P, d]`` (a row a head): the convolution's one
position (the sum in float32, rounded to the model's dtype as
``ops/ssm.py:causal_conv1d_step`` rounds it), ``delta_heads`` and ``k . q``.
``S`` is held ``[dk, dv]`` with ``dv`` on the lanes, so ``k`` and ``q``
multiply it as COLUMNS: ``[k | q | alpha, beta]`` is transposed once,
``[d, d]``, a key head's column is a lane slice broadcast over the lanes for
both value heads it serves, and ``alpha``, ``beta`` come out of the same
transpose as columns over the heads.  Elsewhere (the CPU, the tests'
reference, a width off the tiles) the live slots' tails and records are
gathered, taken through ``causal_conv1d_step``, ``delta_heads_of`` and
``delta_step`` as they stand, and scattered back.

**At prefill** (``chunked_delta_scan``) the recurrence over a prompt is cut
into chunks of ``SCAN_CHUNK`` positions.  Inside a chunk, with ``G_i`` the
running sum of ``log alpha`` and ``d_i`` as above, the ``d`` of a chunk
solve a unit lower-triangular system (the WY / UT transform)::

    (I + A) D = beta V - (beta e^G K) S_0,   A_ij = beta_i e^{G_i - G_j} k_i.k_j  (j < i)
    O   = (e^G Q) S_0 + ((Q K^T) * e^{G_i - G_j})_{j <= i} D
    S_C = e^{G_C} S_0 + (e^{G_C - G} K)^T D

so a chunk is a handful of matrix products and the chunks are walked in
order.  It is a reordering of the same sums: no term is dropped, and
``exp`` is only ever taken of ``G_i - G_j`` with ``j <= i``, which is not
positive.  Positions at or past ``length`` take ``alpha = 1`` and ``beta =
0`` and leave the state as it is, so a prompt leaves the same state
whatever bucket it was padded to (as ``ops/ssm.py``).  The state's products
run at ``"highest"`` precision: the state is float32 at decode, where the
update is exact, and a prompt's state has to be the one decode would have
made.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu._private import tracing
from ray_tpu.ops.ssm import causal_conv1d_step, silu

SCAN_CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def l2_normalise(x, eps: float = 1e-6):
    """``x / ||x||`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def log_decay(a, A_log, dt_bias):
    """``log alpha = -exp(A_log) softplus(a + dt_bias)`` (never positive):
    a ``[..., Hv]``, the two parameters ``[Hv]`` float32."""
    return -jnp.exp(A_log.astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + dt_bias.astype(jnp.float32))


# ---------------------------------------------------------- one position

def delta_step(q, k, v, alpha, beta, state):
    """One position, the recurrence as it is written: q, k ``[r, Hv, dk]``;
    v ``[r, Hv, dv]``; alpha, beta ``[r, Hv]``; state ``[r, Hv, dk, dv]``
    float32.  Returns ``(o [r, Hv, dv] float32, state')``."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    s = alpha.astype(jnp.float32)[..., None, None] * state
    u = jnp.sum(s * k[..., None], axis=-2)
    d = beta.astype(jnp.float32)[..., None] * (v - u)
    s = s + k[..., None] * d[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def delta_heads(q, k, v):
    """The convolution's output by head -> what the rule takes: ``silu``
    (rounded to the model's dtype, as ``ops/ssm.py:silu`` rounds it), then
    ``q`` and ``k`` of unit length over ``dk`` (``q`` also ``/ sqrt(dk)``)
    and ``v``, all float32.  q, k ``[..., Hk, dk]``; v ``[..., Hv, dv]``."""
    q, k, v = silu(q), silu(k), silu(v)
    return (l2_normalise(q) * q.shape[-1] ** -0.5, l2_normalise(k),
            v.astype(jnp.float32))


def delta_heads_of(x, key_heads: int, value_heads: int, dk: int):
    """``delta_heads`` of the convolution's output ``[..., C]`` (``[q | k |
    v]``, the heads leading): q, k ``[..., Hv, dk]`` (a key head repeated
    for the value heads it serves) and v ``[..., Hv, dv]``."""
    lead = x.shape[:-1]
    q, k, v = jnp.split(x, [key_heads * dk, 2 * key_heads * dk], axis=-1)
    q, k, v = delta_heads(q.reshape(*lead, key_heads, dk),
                          k.reshape(*lead, key_heads, dk),
                          v.reshape(*lead, value_heads, -1))
    q, k = (jnp.repeat(a, value_heads // key_heads, axis=-2) for a in (q, k))
    return q, k, v


def delta_update_path(state) -> str:
    """Which update a decode step runs, from what it sees: ``"kernel"`` on
    a TPU where a head's state is whole square tiles, as wide as the tails'
    rows, and the heads fit one transpose (an engine with a mesh is refused
    before it gets here), ``"gather"`` elsewhere."""
    Hv, dk, dv = state["s"].shape[-3:]
    if (jax.default_backend() != "tpu" or dk != dv or dv % 128 or Hv > dk
            or state["conv"].shape[-1] != dv):
        return "gather"
    return "kernel"


def delta_update_records(qkv, alpha, beta, conv_w, state, layer: int, rec,
                         path: str | None = None,
                         interpret: bool | None = None):
    """One position of a Gated DeltaNet layer for every slot, on the slots'
    own records and tails.

    qkv ``[b, C]`` (the convolution's input, ``[q | k | v]``, the model's
    dtype); alpha, beta ``[b, Hv]``; conv_w ``[K, C]``; state ``{"s": [L, R,
    Hv, dk, dv] float32, "conv": [L, R, (K - 1) P, d]}`` (``P d = C``;
    record 0 the scratch one); ``layer`` static; rec ``[b]`` int32: each
    slot's record, 0 for a slot that holds no request.  Returns ``(o [b, Hv,
    dv] float32, state)``; an idle slot's ``o`` is of no use (zero from the
    kernel), the scratch record and its tail are garbage, and a record no
    slot holds is not touched."""
    if path is None:
        path = delta_update_path(state)
    if path == "kernel":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        with tracing.scope("gdn.update"):
            return _kernel_update(qkv, alpha, beta, conv_w, state, rec,
                                  layer=layer, interpret=interpret)
    b, C = qkv.shape
    Hv, dk, dv = state["s"].shape[-3:]
    tails = state["conv"]
    with tracing.scope("gdn.conv"):
        y, tail = causal_conv1d_step(qkv, conv_w,
                                     tails[layer, rec].reshape(b, -1))
        q, k, v = delta_heads_of(y, (C - Hv * dv) // (2 * dk), Hv, dk)
    with tracing.scope("gdn.update"):
        o, s = delta_step(q, k, v, alpha, beta, state["s"][layer, rec])
        return o, {"s": state["s"].at[layer, rec].set(s),
                   "conv": tails.at[layer, rec].set(
                       tail.reshape(b, *tails.shape[2:]))}


def _kernel(rec_ref, slot_ref, x_ref, gate_ref, w_ref, s_ref, t_ref, o_ref,
            s_out, t_out, cols_ref, rows_ref, kq_ref, *, key_heads: int):
    """A slot's grid step on one layer.  x ``[P, d]``: the slot's row of
    ``qkv``, a row a head; gate ``[8, d]``: ``alpha`` and ``beta`` its first
    two rows; w ``[K, P, d]`` float32; s / s_out ``[Hv, dk, dv]``; t / t_out
    ``[(K - 1) P, d]``; o ``[Hv, dv]``.  Scratch: cols ``[d, n]`` (``k | q |
    gate`` transposed), rows ``[3, Hv, dv]`` (``alpha``, ``beta v``, ``alpha
    beta`` over the lanes), kq ``[Hk, dv]``."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    Hk, (Hv, dk, dv) = key_heads, s_ref.shape
    (K, P, _), n = w_ref.shape, cols_ref.shape[1]

    @pl.when(rec_ref[i] == 0)
    def _():  # no request: nothing moves, and the output is defined
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(rec_ref[i] != 0)
    def _():
        # the convolution's one position, as causal_conv1d_step sums it
        taps = [t_ref[j * P:(j + 1) * P, :] for j in range(K - 1)]
        taps.append(x_ref[...])
        y = sum(t.astype(jnp.float32) * w_ref[j]
                for j, t in enumerate(taps)).astype(x_ref.dtype)
        for j in range(1, K):
            t_out[(j - 1) * P:j * P, :] = taps[j]
        q, k, v = delta_heads(y[:Hk], y[Hk:2 * Hk], y[2 * Hk:])
        cols_ref[...] = jnp.concatenate(
            [k, q, gate_ref[...],
             jnp.zeros((n - 2 * Hk - 8, dk), jnp.float32)], axis=0).T
        alpha, beta = (
            jnp.broadcast_to(cols_ref[:Hv, 2 * Hk + j:2 * Hk + j + 1],
                             (Hv, dv)) for j in (0, 1))
        rows_ref[0], rows_ref[1], rows_ref[2] = alpha, beta * v, alpha * beta
        kq_ref[...] = jnp.broadcast_to(
            jnp.sum(k * q, axis=-1, keepdims=True), (Hk, dv))
        for j in range(Hk):  # a key head's columns serve Hv / Hk heads
            kc = jnp.broadcast_to(cols_ref[:, j:j + 1], (dk, dv))
            qc = jnp.broadcast_to(cols_ref[:, Hk + j:Hk + j + 1], (dk, dv))
            for h in range(j * (Hv // Hk), (j + 1) * (Hv // Hk)):
                s, a = s_ref[h], rows_ref[0, h:h + 1, :]
                d = (rows_ref[1, h:h + 1, :] - rows_ref[2, h:h + 1, :]
                     * jnp.sum(s * kc, axis=0, keepdims=True))
                o_ref[h:h + 1, :] = (
                    a * jnp.sum(s * qc, axis=0, keepdims=True)
                    + d * kq_ref[j:j + 1, :])
                s_out[h] = s * a + kc * d


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def _kernel_update(qkv, alpha, beta, conv_w, state, rec, *, layer: int,
                   interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    records, tails = state["s"], state["conv"]
    b, K = rec.shape[0], conv_w.shape[0]
    _, _, Hv, dk, dv = records.shape
    P = qkv.shape[1] // dk
    Hk = (P - Hv) // 2
    n = -(-(2 * Hk + 8) // dk) * dk  # the transposed block's columns
    # live slots first, then the idle ones, which all name record 0
    order = jnp.argsort(rec == 0, stable=True).astype(jnp.int32)
    gate = jnp.pad(jnp.stack([alpha, beta], 1).astype(jnp.float32),
                   ((0, 0), (0, 6), (0, dk - Hv)))
    # the barrier keeps the product that made qkv apart from its view by
    # heads (ops/layers.py:heads_projection: folded, XLA:TPU transposes the
    # whole W_qkv every step); the relayout falls on 128 rows
    x = jax.lax.optimization_barrier(qkv).reshape(b, P, dk)
    by_slot = lambda i, rec, slot: (slot[i], 0, 0)  # noqa: E731
    record = pl.BlockSpec((None, None, Hv, dk, dv),
                          lambda i, rec, slot: (layer, rec[i], 0, 0, 0))
    tail = pl.BlockSpec((None, None, (K - 1) * P, dk),
                        lambda i, rec, slot: (layer, rec[i], 0, 0))
    block = Hv * dk * dv * 4
    small = (K + 2) * P * dk * 4  # the taps, qkv, the tail in and out
    o, records, tails = pl.pallas_call(
        functools.partial(_kernel, key_heads=Hk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((None, P, dk), by_slot),
                      pl.BlockSpec((None, 8, dk), by_slot),
                      pl.BlockSpec((K, P, dk), lambda i, rec, slot: (0, 0, 0)),
                      record, tail],
            out_specs=[pl.BlockSpec((None, Hv, dv), by_slot), record, tail],
            scratch_shapes=[pltpu.VMEM((dk, n), jnp.float32),
                            pltpu.VMEM((3, Hv, dv), jnp.float32),
                            pltpu.VMEM((Hk, dv), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, Hv, dv), jnp.float32),
                   jax.ShapeDtypeStruct(records.shape, records.dtype),
                   jax.ShapeDtypeStruct(tails.shape, tails.dtype)],
        # the records and the tails are rewritten where they lie (operands
        # 5 and 6 count the two prefetched scalars)
        input_output_aliases={5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a record in and one out, each double-buffered, and the rest
            vmem_limit_bytes=4 * block + 4 * small + (8 << 20)),
        interpret=interpret,
        name="gated_delta_update",
    )(rec[order], order, x, gate,
      conv_w.astype(jnp.float32).reshape(K, P, dk), records, tails)
    return o, {"s": records, "conv": tails}


# ------------------------------------------------------------ a sequence

def sequential_delta_scan(q, k, v, g, beta, state0, length=None):
    """The recurrence position by position (``delta_step`` under a scan):
    what ``chunked_delta_scan`` reorders, kept for the tests.  Shapes as
    there."""
    s = q.shape[1]
    live = jnp.ones((s,), bool) if length is None else jnp.arange(s) < length

    def one(state, args):
        qt, kt, vt, gt, bt, on = args
        o, new = delta_step(qt, kt, vt, jnp.exp(gt), bt, state)
        return jnp.where(on, new, state), o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)) + (live,)
    state, o = jax.lax.scan(one, state0, xs)
    return jnp.moveaxis(o, 0, 1), state


def chunked_delta_scan(q, k, v, g, beta, state0, length=None,
                       chunk: int = SCAN_CHUNK):
    """The recurrence over a sequence, a chunk at a time (the module's
    docstring): q, k ``[b, s, Hv, dk]``; v ``[b, s, Hv, dv]``; g (``log
    alpha``, never positive), beta ``[b, s, Hv]``; state0 ``[b, Hv, dk,
    dv]`` float32; ``length``: positions at or past it do not move the
    state (their ``o`` is of no use).  Returns ``(o [b, s, Hv, dv] float32,
    state [b, Hv, dk, dv])``."""
    b, s, Hv, dk = q.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    if length is not None:
        on = (jnp.arange(s) < length)[None, :, None]
        g, beta = jnp.where(on, g, 0.0), jnp.where(on, beta, 0.0)
    pad = (-s) % chunk
    if pad:  # alpha = 1 and beta = 0 there: the state stays
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
    n, C = (s + pad) // chunk, chunk

    def chunks(a):  # [b, n C, Hv, ...] -> [n, b, Hv, C, ...]
        a = a.reshape(b, n, C, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)  # [n, b, Hv, C]
    lower = jnp.tril(jnp.ones((C, C), bool))
    # e^{G_i - G_j} where j <= i; the argument is masked BEFORE the exp
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                              -jnp.inf))
    mm = functools.partial(jnp.einsum, precision=_HIGHEST)
    kk = mm("...id,...jd->...ij", k, k)
    A = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1),
                  beta[..., None] * decay * kk, 0.0)
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * jnp.exp(G))[..., None] * k], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(C, dtype=A.dtype), rhs, lower=True, unit_diagonal=True)
    d_own, w = solved[..., :dv], solved[..., dv:]  # T beta V, T beta e^G K
    p = mm("...id,...jd->...ij", q, k) * decay  # masked: decay is 0 above
    q_in = jnp.exp(G)[..., None] * q
    k_out = jnp.exp(G[..., -1:] - G)[..., None] * k
    last = jnp.exp(G[..., -1])[..., None, None]

    def one_chunk(state, args):
        d_own, w, p, q_in, k_out, last = args
        d = d_own - mm("...ck,...kv->...cv", w, state)
        o = (mm("...ck,...kv->...cv", q_in, state)
             + mm("...ij,...jv->...iv", p, d))
        return last * state + mm("...ck,...cv->...kv", k_out, d), o

    state, o = jax.lax.scan(one_chunk, state0.astype(jnp.float32),
                            (d_own, w, p, q_in, k_out, last))
    # [n, b, Hv, C, dv] -> [b, n C, Hv, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(b, n * C, Hv, dv)
    return o[:, :s], state
