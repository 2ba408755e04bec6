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

**At decode** (``delta_update_records``) a request's record is read once
and written once, where it lies.  Both reductions are taken of the record
as it was read, because the update is of rank one::

    d_t = beta_t v_t - S_{t-1}^T (alpha_t beta_t k_t)
    o_t = S_{t-1}^T (alpha_t q_t) + d_t (k_t . q_t)
    S_t = alpha_t S_{t-1} + k_t d_t^T

On one TPU device that pass is a Pallas kernel (``delta_update_path``): a
grid step a slot, the slot's whole record ``[Hv, dk, dv]`` of one layer
its block, found through the slot's record number (a prefetched scalar) and
aliased to the output, so that only the live slots' records move.  The
slots are visited live ones first: the idle ones all name the scratch
record 0, and a block whose index does not change is neither fetched nor
written again.  ``S`` is held ``[dk, dv]`` with ``dv`` on the lanes, so the
vectors that multiply along ``dk`` (``k``, ``alpha beta k``, ``alpha q``)
come as COLUMNS (``[dk, heads]``: a head's is a lane slice, broadcast over
the lanes) and those along ``dv`` (``beta v``, ``alpha``, ``k . q``) as
rows; all of them are made outside, in one small fusion.  Elsewhere (the
CPU, the tests' reference) the live records are gathered, updated by
``delta_step`` and scattered back.

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


def delta_update_path(records) -> str:
    """Which update a decode step runs, from what it sees: ``"kernel"`` on
    a TPU where a head's state is whole tiles (an engine with a mesh is
    refused before it gets here), ``"gather"`` elsewhere."""
    dk, dv = records.shape[-2:]
    if jax.default_backend() != "tpu" or dk % 8 or dv % 128:
        return "gather"
    return "kernel"


def delta_update_records(q, k, v, alpha, beta, records, layer: int, rec,
                         path: str | None = None,
                         interpret: bool | None = None):
    """One position for every slot, on the slots' own records.

    q, k ``[b, Hv, dk]``; v ``[b, Hv, dv]``; alpha, beta ``[b, Hv]``;
    records ``[L, R, Hv, dk, dv]`` float32 (record 0 the scratch one);
    ``layer`` static; rec ``[b]`` int32: each slot's record, 0 for a slot
    that holds no request.  Returns ``(o [b, Hv, dv] float32, records)``;
    an idle slot's ``o`` is of no use, and the scratch record is garbage."""
    if path is None:
        path = delta_update_path(records)
    if path == "kernel":
        return _kernel_update(q, k, v, alpha, beta, records, layer, rec,
                              interpret)
    o, s = delta_step(q, k, v, alpha, beta, records[layer, rec])
    return o, records.at[layer, rec].set(s)


def _kernel(rec_ref, slot_ref, cols_ref, rows_ref, s_ref, o_ref, out_ref, *,
            heads: int):
    """A slot's record of one layer.  cols ``[dk, 3 Hv]``: ``k | alpha beta
    k | alpha q`` as columns; rows ``[3, Hv, dv]``: ``beta v``, ``alpha``,
    ``k . q``; s / out ``[Hv, dk, dv]``; o ``[Hv, dv]``."""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(rec_ref[i] == 0)
    def _():  # no request: nothing moves, and the output is defined
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(rec_ref[i] != 0)
    def _():
        cols = cols_ref[...]
        for h in range(heads):
            s = s_ref[h]
            kc = cols[:, h:h + 1]
            kb = cols[:, heads + h:heads + h + 1]
            qa = cols[:, 2 * heads + h:2 * heads + h + 1]
            d = rows_ref[0, h:h + 1, :] - jnp.sum(s * kb, axis=0,
                                                  keepdims=True)
            o_ref[h:h + 1, :] = (jnp.sum(s * qa, axis=0, keepdims=True)
                                 + d * rows_ref[2, h:h + 1, :])
            out_ref[h] = s * rows_ref[1, h:h + 1, :] + kc * d


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def _kernel_call(cols, rows, records, rec, *, layer: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = rec.shape[0]
    _, _, Hv, dk, dv = records.shape
    # live slots first, then the idle ones, which all name record 0
    order = jnp.argsort(rec == 0, stable=True).astype(jnp.int32)
    block = Hv * dk * dv * 4
    small = (dk * max(3 * Hv, 128) + 3 * Hv * dv + Hv * dv) * 4
    o, records = pl.pallas_call(
        functools.partial(_kernel, heads=Hv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[
                pl.BlockSpec((None, dk, 3 * Hv),
                             lambda i, rec, slot: (slot[i], 0, 0)),
                pl.BlockSpec((None, 3, Hv, dv),
                             lambda i, rec, slot: (slot[i], 0, 0, 0)),
                pl.BlockSpec((None, None, Hv, dk, dv),
                             lambda i, rec, slot: (layer, rec[i], 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, Hv, dv),
                             lambda i, rec, slot: (slot[i], 0, 0)),
                pl.BlockSpec((None, None, Hv, dk, dv),
                             lambda i, rec, slot: (layer, rec[i], 0, 0, 0)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((b, Hv, dv), jnp.float32),
                   jax.ShapeDtypeStruct(records.shape, records.dtype)],
        # the records are rewritten where they lie (operand 4 counts the
        # two prefetched scalars)
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a record in and one out, each double-buffered, and the rest
            vmem_limit_bytes=4 * block + 4 * small + (8 << 20)),
        interpret=interpret,
        name="gated_delta_update",
    )(rec[order], order, cols, rows, records)
    return o, records


def _kernel_update(q, k, v, alpha, beta, records, layer, rec, interpret):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    alpha = alpha.astype(jnp.float32)[..., None]
    beta = beta.astype(jnp.float32)[..., None]
    cols = jnp.swapaxes(
        jnp.concatenate([k, alpha * beta * k, alpha * q], axis=1), 1, 2)
    rows = jnp.stack(
        [beta * v, jnp.broadcast_to(alpha, v.shape),
         jnp.broadcast_to(jnp.sum(k * q, -1, keepdims=True), v.shape)], 1)
    return _kernel_call(cols, rows, records, rec, layer=layer,
                        interpret=interpret)


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
