"""Flash attention (forward + backward) in Pallas for TPU.

Forward: blockwise online-softmax attention.  For each (batch*head, q-block)
grid cell the kernel streams K/V blocks through VMEM, keeping running
max/normalizer in VMEM scratch that persists across the innermost (k-block)
grid dimension — the TPU grid executes sequentially per core, so scratch is
the accumulator carry.  QK^T and PV ride the MXU with fp32 accumulation;
causal blocks fully above the diagonal are skipped via ``pl.when``; the
log-sum-exp is written out for the backward pass.

Backward: the standard two-kernel flash decomposition with recomputed
probabilities P = exp(S - lse):
  - dQ kernel, grid (b*h, nq, nk): accumulates dQ over K blocks;
  - dK/dV kernel, grid (b*kv_h, nk, n_rep*nq): accumulates dK/dV over all
    q-heads mapped to the kv head (GQA) and all Q blocks — the reduction
    over the grouped q-heads lives in the sequential grid, so no cross-cell
    races.
Both use D = rowsum(dO * O) precomputed on the VPU outside the kernels.

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


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale,
    causal, block_q, block_k, num_kblocks, seq_k
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0]  # [block_q, d]
        k = k_ref[0]  # [block_k, d]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        mask = kpos < seq_k  # pad K positions contribute nothing
        if causal:
            mask = jnp.logical_and(mask, qpos >= kpos)
        logits = jnp.where(mask, logits, _NEG_INF)
        m_prev = m_scr[:]
        m_blk = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = m_new

    if causal:
        # Skip k-blocks strictly above the causal diagonal.
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == num_kblocks - 1)
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


def _flash_fwd_impl(q, k, v, *, causal, block_q, block_k, interpret):
    b, sq, h, d = q.shape
    sk, kv_h = k.shape[1], k.shape[2]
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
        return ((bh // h) * kv_h + (bh % h) // n_rep, ki, 0)

    def lse_map(bh, qi, ki):
        return (bh, 0, qi)

    kernel = functools.partial(
        _fwd_kernel, scale=d ** -0.5, causal=causal, block_q=block_q,
        block_k=block_k, num_kblocks=nk, seq_k=sk,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_q), lse_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sq_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    out = out.reshape(b, h, sq_p, d).transpose(0, 2, 1, 3)[:, :sq]
    return out, lse  # lse stays padded/folded for the backward kernels


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _recompute_p(q, k, lse, *, scale, causal, block_q, block_k, qi, ki,
                 seq_k):
    """P block = exp(S - lse), with pad/causal masking. fp32 [bq, bk]."""
    s_blk = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = kpos < seq_k
    if causal:
        mask = jnp.logical_and(mask, qpos >= kpos)
    s_blk = jnp.where(mask, s_blk, _NEG_INF)
    return jnp.exp(s_blk - lse[:, None])


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref, dq_scr, *,
    scale, causal, block_q, block_k, num_kblocks, seq_k
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        p = _recompute_p(
            q, k, lse_ref[0, 0], scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, qi=qi, ki=ki, seq_k=seq_k,
        )
        dp = jax.lax.dot_general(  # dO V^T: [bq, bk]
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dd_ref[0, 0][:, None])
        dq_scr[:] += scale * jax.lax.dot_general(  # dS K: [bq, d]
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(ki * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ki == num_kblocks - 1)
    def _emit():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, scale, causal, block_q, block_k, num_inner, nq, seq_k
):
    ki = pl.program_id(1)
    j = pl.program_id(2)  # j = rep * nq + qi
    qi = j % nq

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        p = _recompute_p(
            q, k, lse_ref[0, 0], scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, qi=qi, ki=ki, seq_k=seq_k,
        )
        dv_scr[:] += jax.lax.dot_general(  # P^T dO: [bk, d]
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dd_ref[0, 0][:, None])
        dk_scr[:] += scale * jax.lax.dot_general(  # dS^T Q: [bk, d]
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_compute)
    else:
        _compute()

    @pl.when(j == num_inner - 1)
    def _emit():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


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
    # D = rowsum(dO * O): cheap VPU work, done outside the kernels.
    dd = jnp.sum(
        got.astype(jnp.float32) * dot.astype(jnp.float32), axis=-1
    )[:, None, :]  # [b*h, 1, sq_p]

    scale = d ** -0.5

    # --- dQ ----------------------------------------------------------------
    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        return ((bh // h) * kv_h + (bh % h) // n_rep, ki, 0)

    def lse_map(bh, qi, ki):
        return (bh, 0, qi)

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, num_kblocks=nk, seq_k=sk,
        ),
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, 1, block_q), lse_map),
            pl.BlockSpec((1, 1, block_q), lse_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, got, lse, dd)
    dq = dq.reshape(b, h, sq_p, d).transpose(0, 2, 1, 3)[:, :sq]

    # --- dK/dV -------------------------------------------------------------
    # Grid (b*kv_h, nk, n_rep*nq): the reduction over grouped q-heads and
    # q-blocks runs inside the sequential inner grid dimension.
    num_inner = n_rep * nq

    def q_map2(bkv, ki, j):
        batch, kvh_idx = bkv // kv_h, bkv % kv_h
        rep, qi = j // nq, j % nq
        return (batch * h + kvh_idx * n_rep + rep, qi, 0)

    def kv_map2(bkv, ki, j):
        return (bkv, ki, 0)

    def lse_map2(bkv, ki, j):
        batch, kvh_idx = bkv // kv_h, bkv % kv_h
        rep, qi = j // nq, j % nq
        return (batch * h + kvh_idx * n_rep + rep, 0, qi)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, num_inner=num_inner, nq=nq, seq_k=sk,
        ),
        grid=(b * kv_h, nk, num_inner),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map2),
            pl.BlockSpec((1, block_k, d), kv_map2),
            pl.BlockSpec((1, block_k, d), kv_map2),
            pl.BlockSpec((1, block_q, d), q_map2),
            pl.BlockSpec((1, 1, block_q), lse_map2),
            pl.BlockSpec((1, 1, block_q), lse_map2),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), kv_map2),
            pl.BlockSpec((1, block_k, d), kv_map2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * kv_h, sk_p, d), k.dtype),
            jax.ShapeDtypeStruct((b * kv_h, sk_p, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, got, lse, dd)
    dk = dk.reshape(b, kv_h, sk_p, d).transpose(0, 2, 1, 3)[:, :sk]
    dv = dv.reshape(b, kv_h, sk_p, d).transpose(0, 2, 1, 3)[:, :sk]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd_impl(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret):
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


def _flash_vjp_bwd(causal, block_q, block_k, interpret, res, g):
    return _flash_bwd_impl(
        res, g, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = 1024,
    block_k: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    """Flash attention. q: [b, s, h, d]; k, v: [b, s, kv_h, d].

    Off-TPU this runs the Pallas interpreter (slow; tests use small
    shapes).
    """
    if jax.default_backend() != "tpu":
        interpret = True
    return _flash(q, k, v, causal, block_q, block_k, interpret)
