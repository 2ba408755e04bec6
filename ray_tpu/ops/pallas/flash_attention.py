"""Flash attention (forward + backward) in Pallas for TPU: two kernels.

Forward: blockwise online-softmax attention.  For each (batch*head, q-block)
grid cell the kernel streams K/V blocks through VMEM, keeping running
max/normalizer in VMEM scratch that persists across the innermost (k-block)
grid dimension — the TPU grid executes sequentially per core, so scratch is
the accumulator carry.  QK^T and PV ride the MXU with fp32 accumulation; the
log-sum-exp is written out for the backward pass.

Backward: one kernel, grid (b*kv_h, n_rep, nq, nk).  For a (Q block, K block)
pair it forms P = exp(S - lse) and dP = dO V^T once and accumulates all of
dV += P^T dO, dK += dS^T Q and dQ += dS K in fp32 VMEM scratch, each cast to
the operands' dtype once, when its reduction ends.  dQ reduces over the
innermost (k-block) dimension in a block-sized scratch; dK/dV reduce over
the Q blocks and the q-heads grouped on the kv head (GQA), so they are kept
whole, [sk, d] each, across the three inner dimensions: 2 * sk * d * 4 bytes
(4 MiB at 4096 x 128, 32 MiB at 32768), which is why the call sets its own
``vmem_limit_bytes`` from the shapes.  The scores are held transposed,
[keys, queries]: lse and D = rowsum(dO * O) (precomputed outside, on the
VPU) broadcast along sublanes as they are stored, and only dQ's product
contracts over its operands' leading dimension.

Work the causal mask does not ask for is not executed, in both kernels
(``_visit_block``): a block wholly above the diagonal is skipped, and its
K/V block not fetched (the index map stays on the last block needed); a
block wholly below it is one pass with no mask built; the block the
diagonal starts in is cut into strips of queries (two forward, four
backward, of a 1024 block), each run against the keys up to its own end
only, so 3/4 and 5/8 of the block's products are executed; a block that
something else cuts (the pad of the last K block, a diagonal through
unequal blocks when sq != sk) runs whole under its mask.  The strips of a
block share one basic block: tiles in regions of their own ran at half
the MXU's rate on a v5e, and ``lax.cond`` around a mask cost more than
the mask.

The forward kernel takes a sliding ``window`` (a query sees the last
``window`` positions, ``ops.attention.sliding_window_mask``): a K block that
lies wholly before every query's window is skipped and not fetched, as one
above the diagonal is (the index map holds the first block needed until the
grid reaches it), and only a block the window's edge cuts builds its mask.
Forward only: the backward kernel has no window yet and says so.  With
``window=None`` nothing of this is traced.

The forward takes values of another width than the keys: q, k
``[.., d]`` beside v ``[.., dv]`` give ``[.., dv]`` (latent attention's
non-absorbed form: keys of the nope and rope widths together, 192, beside
128-wide values).  The width of v's block, of the output's and of the
accumulator is read off v; with ``dv == d`` the call is what it was.  The
default scale stays ``d ** -0.5`` of the keys' width.  Forward only, as a
``scale`` of the caller's own is: the backward kernel has one width, and a
gradient through either raises by name.  These forward-only calls go
through one jitted function, so that a program's calls at the same shapes
share one traced and lowered kernel.

Sequences are padded to the block size and pad K positions masked, so any
length works.  GQA is handled by index-mapping q-heads onto kv heads — no
materialized KV expansion.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))  # A B^T
_NN = (((1,), (0,)), ((), ()))  # A B
_TN = (((0,), (0,)), ((), ()))  # A^T B


def _strip(block, parts):
    """Rows of the strips a block on the diagonal is cut into: the block
    in up to ``parts``, each a whole number of 128-lane vector tiles."""
    for n in range(parts, 1, -1):
        if block % (n * 128) == 0:
            return block // n
    return block


def _masked(scores, causal, k_padded, r0, c0, q_axis, seq_k, window=None):
    """``scores`` of queries from r0 (along ``q_axis``) against keys from
    c0, with what the mask forbids set to _NEG_INF."""
    qpos = r0 + jax.lax.broadcasted_iota(jnp.int32, scores.shape, q_axis)
    kpos = c0 + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1 - q_axis)
    mask = None
    if causal:
        mask = qpos >= kpos
    if window is not None:  # causal too: the wrapper insists
        mask = jnp.logical_and(mask, qpos - kpos < window)
    if k_padded:  # pad K positions contribute nothing
        pad = kpos < seq_k
        mask = pad if mask is None else jnp.logical_and(mask, pad)
    return jnp.where(mask, scores, _NEG_INF)


def _visit_block(tile_fn, *, causal, k_padded, qi, ki, last_k, block_q,
                 block_k, strip, seq_k, window=None):
    """Run ``tile_fn(i0, rows, cols, mask)``, queries i0.. of block qi
    against the first ``cols`` keys of block ki, over what the block needs:

    - nothing, above the diagonal or wholly before the ``window``;
    - the whole block with no mask built (``mask`` None), below the
      diagonal and inside every query's window;
    - on it (equal blocks: where it starts), strip by strip, each strip's
      queries against the keys up to them, all in one basic block;
    - the whole block masked where something else cuts through it: the
      pad of the last K block, a diagonal through unequal blocks, the
      window's edge.

    ``mask(scores, q_axis)`` returns the scores masked."""
    r0, c0 = qi * block_q, ki * block_k
    whole = functools.partial(tile_fn, 0, block_q, block_k)

    def mask(i0):
        return lambda scores, q_axis: _masked(
            scores, causal, k_padded, r0 + i0, c0, q_axis, seq_k, window)

    if not causal and not k_padded:
        whole(None)
        return
    run, cut = True, False  # any of it asked for; anything cuts through it
    if causal:
        run = r0 + block_q - 1 >= c0
        cut = r0 < c0 + block_k - 1
    if window is not None:
        # the nearest pair is (first query, last key), the farthest (last
        # query, first key)
        run = jnp.logical_and(run, r0 - (c0 + block_k - 1) < window)
        cut = jnp.logical_or(cut, r0 + block_q - 1 - c0 >= window)
    if k_padded:
        cut = jnp.logical_or(cut, last_k)
    pl.when(jnp.logical_and(run, jnp.logical_not(cut)))(
        functools.partial(whole, None))
    strips = causal and block_q == block_k and strip < block_q
    if strips:
        on_diag = r0 == c0  # equal blocks: no other block is crossed

        @pl.when(on_diag)
        def _diagonal():
            for i0 in range(0, block_q, strip):
                tile_fn(i0, strip, i0 + strip, mask(i0))

        cut = jnp.logical_and(cut, jnp.logical_not(on_diag))
    if k_padded or not strips or window is not None:
        pl.when(jnp.logical_and(run, cut))(
            functools.partial(whole, mask(0)))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale,
    causal, k_padded, block_q, block_k, seq_k, window=None
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def tile(i0, rows, cols, mask):
        qs, ks = pl.ds(i0, rows), pl.ds(0, cols)
        q = q_ref[0, qs, :]  # [rows, d]
        k = k_ref[0, ks, :]  # [cols, d]
        logits = jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32) * scale
        if mask is not None:
            logits = mask(logits, 0)
        m_prev = m_scr[qs, :]
        m_blk = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[qs, :] = l_scr[qs, :] * alpha + jnp.sum(
            p, axis=-1, keepdims=True)
        v = v_ref[0, ks, :]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        acc_scr[qs, :] = acc_scr[qs, :] * alpha + pv
        m_scr[qs, :] = m_new

    last_k = ki == pl.num_programs(2) - 1
    # two strips: the chain QK^T -> softmax -> PV of a strip does not
    # overlap, so finer strips cost more in latency than they skip
    _visit_block(
        tile, causal=causal, k_padded=k_padded, qi=qi, ki=ki, last_k=last_k,
        block_q=block_q, block_k=block_k, strip=_strip(block_q, 2),
        seq_k=seq_k, window=window)

    @pl.when(last_k)
    def _finalize():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:] + jnp.log(l))[:, 0]


def _pad_seq(x, block, axis=1):
    s = x.shape[axis]
    pad = (-s) % block
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x


def _fold_heads(x):
    """[b, s, h, d] -> [b*h, s, d]."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _last_k_block(causal, qi, ki, block_q, block_k, window=None):
    """The K block to hold at grid step (qi, ki): ki, but no further than
    the last one a causal Q block needs and no sooner than the first one
    its ``window`` reaches, so that a skipped step fetches nothing (an
    unchanged block index is not copied again)."""
    if not causal:
        return ki
    ki = jnp.minimum(ki, (qi * block_q + block_q - 1) // block_k)
    if window is not None:
        ki = jnp.maximum(ki, jnp.maximum(qi * block_q - window + 1, 0)
                         // block_k)
    return ki


def _flash_fwd_impl(q, k, v, *, causal, block_q, block_k, interpret,
                    window=None, scale=None):
    b, sq, h, d = q.shape
    sk, kv_h = k.shape[1], k.shape[2]
    dv = v.shape[-1]  # the values' (and the output's) width: need not be d
    n_rep = h // kv_h
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    q = _pad_seq(q, block_q)
    k = _pad_seq(k, block_k)
    v = _pad_seq(v, block_k)
    sq_p, sk_p = q.shape[1], k.shape[1]
    qt, kt, vt = _fold_heads(q), _fold_heads(k), _fold_heads(v)
    nq, nk = sq_p // block_q, sk_p // block_k
    grid = (b * h, nq, nk)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        return ((bh // h) * kv_h + (bh % h) // n_rep,
                _last_k_block(causal, qi, ki, block_q, block_k, window), 0)

    def lse_map(bh, qi, ki):
        return (bh, 0, qi)

    kernel = functools.partial(
        _fwd_kernel, scale=d ** -0.5 if scale is None else float(scale),
        causal=causal, k_padded=sk_p != sk,
        block_q=block_q, block_k=block_k, seq_k=sk, window=window,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), q_map),
            pl.BlockSpec((1, 1, block_q), lse_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sq_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out.reshape(b, h, sq_p, dv).transpose(0, 2, 1, 3)[:, :sq]
    return out, lse  # lse stays padded/folded for the backward kernel


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref, dk_ref, dv_ref,
    dq_scr, dk_scr, dv_scr, *, scale, causal, k_padded, block_q, block_k,
    seq_k
):
    rep, qi, ki = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    first_q = jnp.logical_and(rep == 0, qi == 0)
    last_q = jnp.logical_and(rep == pl.num_programs(1) - 1,
                             qi == pl.num_programs(2) - 1)
    last_k = ki == pl.num_programs(3) - 1

    @pl.when(jnp.logical_and(first_q, ki == 0))
    def _init_kv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(ki == 0)
    def _init_q():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def tile(i0, rows, cols, mask):
        qs, ks = pl.ds(i0, rows), pl.ds(0, cols)
        q, do = q_ref[0, qs, :], do_ref[0, qs, :]  # [rows, d]
        k, v = k_ref[0, ks, :], v_ref[0, ks, :]  # [cols, d]
        # rows of the whole-sequence dK/dV scratch this tile adds to
        kv = pl.ds(pl.multiple_of(ki * block_k, block_k), cols)
        # scores transposed, [cols, rows]: lse and D broadcast as stored
        s_t = jax.lax.dot_general(
            k, q, _NT, preferred_element_type=jnp.float32) * scale
        if mask is not None:
            s_t = mask(s_t, 1)
        p_t = jnp.exp(s_t - lse_ref[0, :, qs])
        dv_scr[kv, :] += jax.lax.dot_general(  # P^T dO: [cols, d]
            p_t.astype(do.dtype), do, _NN,
            preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(  # V dO^T: [cols, rows]
            v, do, _NT, preferred_element_type=jnp.float32)
        ds_t = (p_t * (dp_t - dd_ref[0, :, qs])).astype(q.dtype)
        dk_scr[kv, :] += scale * jax.lax.dot_general(  # dS^T Q: [cols, d]
            ds_t, q, _NN, preferred_element_type=jnp.float32)
        dq_scr[qs, :] += scale * jax.lax.dot_general(  # dS K: [rows, d]
            ds_t, k, _TN, preferred_element_type=jnp.float32)

    # four strips: the five products keep the MXU busy, so skipped work
    # is saved time
    _visit_block(
        tile, causal=causal, k_padded=k_padded, qi=qi, ki=ki, last_k=last_k,
        block_q=block_q, block_k=block_k, strip=_strip(block_q, 4),
        seq_k=seq_k)

    @pl.when(last_k)
    def _emit_q():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(last_q, last_k))
    def _emit_kv():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_vmem_bytes(block_q, block_k, sk_p, d, itemsize):
    """What the backward call may use of VMEM: the whole-sequence fp32
    dK/dV scratch and their output blocks (both buffers), the streamed
    blocks, and a whole block's fp32 scores and their siblings."""
    whole = 2 * sk_p * d * (4 + 2 * itemsize)
    streamed = 2 * (3 * block_q + 2 * block_k) * d * itemsize
    scores = 6 * block_q * block_k * 4
    return whole + streamed + scores + block_q * d * 4 + (8 << 20)


def _flash_bwd_impl(res, g, *, causal, block_q, block_k, interpret):
    q, k, v, out, lse = res
    b, sq, h, d = q.shape
    sk, kv_h = k.shape[1], k.shape[2]
    n_rep = h // kv_h
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    qp = _pad_seq(q, block_q)
    op = _pad_seq(out, block_q)
    gp = _pad_seq(g, block_q)
    kp = _pad_seq(k, block_k)
    vp = _pad_seq(v, block_k)
    sq_p, sk_p = qp.shape[1], kp.shape[1]
    nq, nk = sq_p // block_q, sk_p // block_k

    qt, kt, vt = _fold_heads(qp), _fold_heads(kp), _fold_heads(vp)
    dot, got = _fold_heads(op), _fold_heads(gp)
    # D = rowsum(dO * O): cheap VPU work, done outside the kernel.
    dd = jnp.sum(
        got.astype(jnp.float32) * dot.astype(jnp.float32), axis=-1
    )[:, None, :]  # [b*h, 1, sq_p]

    # Grid (b*kv_h, n_rep, nq, nk): dQ's reduction is the innermost
    # dimension; dK/dV's, over grouped q-heads and Q blocks, spans the
    # three inner ones, all sequential, so no cross-cell races.
    def head(bkv, rep):
        return (bkv // kv_h) * h + (bkv % kv_h) * n_rep + rep

    def q_map(bkv, rep, qi, ki):
        return (head(bkv, rep), qi, 0)

    def kv_map(bkv, rep, qi, ki):
        return (bkv, _last_k_block(causal, qi, ki, block_q, block_k), 0)

    def lse_map(bkv, rep, qi, ki):
        return (head(bkv, rep), 0, qi)

    def whole_kv_map(bkv, rep, qi, ki):
        return (bkv, 0, 0)

    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=d ** -0.5, causal=causal,
            k_padded=sk_p != sk, block_q=block_q, block_k=block_k, seq_k=sk,
        ),
        grid=(b * kv_h, n_rep, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_q), lse_map),
            pl.BlockSpec((1, 1, block_q), lse_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, sk_p, d), whole_kv_map),
            pl.BlockSpec((1, sk_p, d), whole_kv_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b * kv_h, sk_p, d), k.dtype),
            jax.ShapeDtypeStruct((b * kv_h, sk_p, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((sk_p, d), jnp.float32),
            pltpu.VMEM((sk_p, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_bwd_vmem_bytes(
                block_q, block_k, sk_p, d, q.dtype.itemsize)),
        interpret=interpret,
    )(qt, kt, vt, got, lse, dd)
    dq = dq.reshape(b, h, sq_p, d).transpose(0, 2, 1, 3)[:, :sq]
    dk = dk.reshape(b, kv_h, sk_p, d).transpose(0, 2, 1, 3)[:, :sk]
    dv = dv.reshape(b, kv_h, sk_p, d).transpose(0, 2, 1, 3)[:, :sk]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, window=None):
    out, _ = _flash_fwd_impl(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window,
    )
    return out


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret, window):
    if window is not None:
        raise NotImplementedError(
            "flash_attention's backward kernel has no sliding window yet: "
            "differentiate reference_attention(window=...) or ring_attention")
    out, lse = _flash_fwd_impl(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    # Name the residuals so remat policies (save_only_these_names) can keep
    # them instead of replaying the forward kernel in the backward pass.
    from jax.ad_checkpoint import checkpoint_name

    out_res = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out_res, lse)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, window, res, g):
    return _flash_bwd_impl(
        res, g, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)

_NO_BACKWARD = (
    "flash_attention's backward kernel takes q, k and v of one width and "
    "the default scale: a call with values narrower or wider than the keys, "
    "or with a scale of its own, is forward only (differentiate "
    "reference_attention)")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_forward_only(q, k, v, causal, block_q, block_k, interpret, window,
                        scale):
    """The forward where there is no backward for it: a ``scale``, unequal
    widths."""
    return _flash_fwd_impl(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window, scale=scale)[0]


def _no_backward(*args):
    raise NotImplementedError(_NO_BACKWARD)


_flash_forward_only.defvjp(_no_backward, _no_backward)
# ONE jitted function: the calls of a program at the same shapes (a model's
# attention blocks) share one traced and lowered kernel, as
# ``paged_attention.py``'s layers do (lowering a Pallas kernel is Python
# work no compile cache saves)
_flash_forward_only = jax.jit(_flash_forward_only,
                              static_argnums=(3, 4, 5, 6, 7, 8))


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool = False,
    window: int | None = None,
    scale: float | None = None,
) -> jnp.ndarray:
    """Flash attention. q: [b, s, h, d]; k: [b, s, kv_h, d]; v: [b, s, kv_h,
    dv] -> [b, s, h, dv].

    ``window``: a query at position p sees the keys in (p - window, p],
    positions counted from 0 in both operands; causal only, forward only.
    ``scale``: the scores' factor where it is not ``d ** -0.5`` (a query
    zero-padded to twice its head's width); forward only.
    ``dv != d`` (latent attention's non-absorbed form: keys of the nope and
    rope widths together, values of their own): forward only; the default
    scale stays that of the keys' width.

    Off-TPU this runs the Pallas interpreter (slow; tests use small
    shapes).
    """
    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")
    if jax.default_backend() != "tpu":
        interpret = True
    if scale is not None or v.shape[-1] != q.shape[-1]:  # forward only
        return _flash_forward_only(
            q, k, v, causal, block_q, block_k, interpret, window, scale)
    return _flash(q, k, v, causal, block_q, block_k, interpret, window)
