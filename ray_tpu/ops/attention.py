"""Attention: reference, Pallas flash (TPU), and ring attention (sp axis).

Ring attention (context parallelism) is absent from the reference
(SURVEY.md §2.4 — "EP/SP/CP/ring attention: Absent") and is a headline
TPU-native feature here: K/V blocks rotate around the ``sp`` mesh axis via
``lax.ppermute`` (ICI neighbor exchanges) while each device computes
blockwise online-softmax attention for its local Q shard — memory per device
is O(seq/sp), enabling contexts sp× longer than a single chip's HBM allows.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def sliding_window_mask(q_pos, k_pos, window):
    """Sliding-window visibility clause: query at ``q_pos`` sees keys in
    ``(q_pos - window, q_pos]`` — the SINGLE home of the off-by-one
    convention, shared by the attention ops and every model cache path
    (dense + paged).  Args broadcast."""
    return q_pos - k_pos < window


def _repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """[b, s, kv_heads, d] -> [b, s, kv_heads * n_rep, d] (GQA expansion)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def reference_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    positions_q: Optional[jnp.ndarray] = None,
    positions_k: Optional[jnp.ndarray] = None,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Plain softmax attention, fp32 accumulation.

    q: [b, sq, h, d]; k: [b, sk, kv_h, d]; v: [b, sk, kv_h, dv] (``dv``
    need not be ``d``: the output is ``[b, sq, h, dv]``) with h % kv_h == 0.
    ``window``: sliding-window (Mistral-style) — query p attends keys in
    (p - window, p].  Requires causal.  ``scale``: the scores' factor,
    ``d ** -0.5`` unless given.
    """
    b, sq, h, d = q.shape
    kv_h = k.shape[2]
    k = _repeat_kv(k, h // kv_h)
    v = _repeat_kv(v, h // kv_h)
    if scale is None:
        scale = d ** -0.5
    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        if positions_q is None:
            positions_q = jnp.arange(sq)
        if positions_k is None:
            positions_k = jnp.arange(k.shape[1])
        mask = positions_q[:, None] >= positions_k[None, :]
        if window is not None:
            mask &= sliding_window_mask(positions_q[:, None],
                                        positions_k[None, :], window)
        logits = jnp.where(mask[None, None, :, :], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)


def _blockwise_step(q, k, v, m, l, o, *, qpos, kpos, scale, window=None):
    """One online-softmax accumulation step against a K/V block.

    q: [b, sq, h, d]; k, v: [b, sk, h, d] (kv already GQA-expanded);
    m, l: [b, h, sq] running max / normalizer; o: [b, sq, h, d] fp32 accum.
    """
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    mask = qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= sliding_window_mask(qpos[:, None], kpos[None, :], window)
    logits = jnp.where(mask[None, None, :, :], logits, _NEG_INF)
    m_blk = jnp.max(logits, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # exp of fully-masked rows underflows to 0 — no NaNs since m_new finite.
    p = jnp.exp(logits - m_new[..., None])
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, o_new


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    mesh: Mesh,
    sp_axis: str = "sp",
    causal: bool = True,
    batch_axes=("dp", "fsdp"),
    head_axis: Optional[str] = "tp",
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Ring attention over the ``sp`` mesh axis (global-view inputs).

    Inputs are global arrays [b, S, h, d] (sharded or not); shard_map splits
    S over ``sp``, and K/V shards rotate around the ring with ppermute while
    each device accumulates blockwise output for its local Q shard.
    """
    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")
    sp = mesh.shape[sp_axis]
    if sp == 1:
        return reference_attention(q, k, v, causal=causal, window=window)
    h, kv_h = q.shape[2], k.shape[2]
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    if head_axis is not None and head_axis not in mesh.axis_names:
        head_axis = None
    qspec = P(batch_axes if batch_axes else None, sp_axis, head_axis, None)

    def local_fn(q_loc, k_loc, v_loc):
        b, sq, h_loc, d = q_loc.shape
        idx = jax.lax.axis_index(sp_axis)
        scale = d ** -0.5
        qpos = idx * sq + jnp.arange(sq)
        perm = [(i, (i + 1) % sp) for i in range(sp)]

        def compute(t, k_cur, v_cur, m, l, o):
            src_block = (idx - t) % sp
            if causal:
                kpos = src_block * sq + jnp.arange(k_cur.shape[1])
                qp = qpos
            else:
                kpos = jnp.zeros((k_cur.shape[1],), jnp.int32)
                qp = jnp.zeros((sq,), jnp.int32)
            return _blockwise_step(
                q_loc, k_cur, v_cur, m, l, o, qpos=qp, kpos=kpos,
                scale=scale, window=window
            )

        def body(t, carry):
            k_cur, v_cur, m, l, o = carry
            m, l, o = compute(t, k_cur, v_cur, m, l, o)
            k_nxt = jax.lax.ppermute(k_cur, sp_axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, sp_axis, perm)
            return k_nxt, v_nxt, m, l, o

        m0 = jnp.full((b, h_loc, sq), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h_loc, sq), jnp.float32)
        o0 = jnp.zeros((b, sq, h_loc, d), jnp.float32)
        # Mark the accumulators device-varying so the loop carry typechecks
        # under shard_map's varying-axis tracking.
        m0, l0, o0 = jax.lax.pcast(
            (m0, l0, o0), tuple(mesh.axis_names), to="varying"
        )
        # Last block: compute only — its rotated K/V would be discarded, so
        # running the final ppermute pair would waste two ICI collectives.
        k_l, v_l, m, l, o = jax.lax.fori_loop(
            0, sp - 1, body, (k_loc, v_loc, m0, l0, o0)
        )
        m, l, o = compute(sp - 1, k_l, v_l, m, l, o)
        l = jnp.maximum(l, 1e-30)
        out = o / l.transpose(0, 2, 1)[..., None]
        return out.astype(q_loc.dtype)

    # GQA-expand before shard_map so head counts line up under tp sharding.
    k = _repeat_kv(k, h // kv_h)
    v = _repeat_kv(v, h // kv_h)
    # check_vma=False: outputs are trivially replicated over mesh axes the
    # specs never mention (e.g. a size-1 "pp"), which the static VMA check
    # cannot infer through the ppermute ring.
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=(qspec, qspec, qspec), out_specs=qspec,
        check_vma=False,
    )(q, k, v)


def attention_impl(seq_q: int, mesh: Optional[Mesh] = None,
                   sp_axis: str = "sp") -> str:
    """What ``dot_product_attention``'s ``impl="auto"`` runs for ``seq_q``
    queries: 'ring' when the mesh shards the sequence (sp > 1), the Pallas
    flash kernel on a TPU from 256 queries on, the reference elsewhere (the
    CPU's test meshes).  Read by callers that have a path of their own to
    fall back to (``models/mla.py``)."""
    if (mesh is not None and sp_axis in mesh.axis_names
            and mesh.shape[sp_axis] > 1):
        return "ring"
    if jax.default_backend() == "tpu" and seq_q >= 256:
        return "flash"
    return "ref"


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    impl: str = "auto",
    mesh: Optional[Mesh] = None,
    sp_axis: str = "sp",
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Dispatching attention entry point used by the model layer.

    impl: 'auto' | 'ref' | 'flash' | 'ring'.  'auto' is ``attention_impl``:
    ring when the mesh shards sequence (sp>1), Pallas flash on TPU
    otherwise, and the reference path on CPU test meshes.  ``window``
    (sliding-window / Mistral-style) is supported by all three; the flash
    kernel's forward skips the K blocks before the window, its backward has
    no window yet and raises by name (differentiate 'ref' or 'ring' under a
    window).  ``scale`` (the scores' factor where it is not
    ``head_dim ** -0.5``) and values of another width than the keys
    (``v: [b, s, kv_h, dv]``, the output ``dv`` wide) are taken by 'ref' and
    by the flash forward on one device, forward only.
    """
    if mesh is not None and (scale is not None
                             or v.shape[-1] != q.shape[-1]):
        raise NotImplementedError(
            "dot_product_attention takes a scale of its own, or values of "
            "another width than the keys, on one device only (the ring and "
            "the sharded flash call have neither)")
    if impl == "auto":
        impl = attention_impl(q.shape[1], mesh, sp_axis)
    if impl == "ring":
        assert mesh is not None, "ring attention needs a mesh"
        return ring_attention(
            q, k, v, mesh=mesh, sp_axis=sp_axis, causal=causal,
            window=window
        )
    if impl == "flash":
        from ray_tpu.ops.pallas.flash_attention import flash_attention

        if mesh is None:
            return flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale)
        # The pallas_call is opaque to GSPMD: run it per-shard under
        # shard_map, with batch sharded over dp/fsdp and heads over tp
        # (sequence is whole per device since sp==1 on this path).
        # check_vma=False: pallas_call is also opaque to the
        # varying-axis type system.
        batch_axes = tuple(
            a for a in ("dp", "fsdp") if a in mesh.axis_names
        )
        head_axis = "tp" if "tp" in mesh.axis_names else None
        qspec = P(batch_axes if batch_axes else None, None, head_axis, None)
        kvspec = qspec
        return jax.shard_map(
            lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=causal,
                                               window=window),
            mesh=mesh,
            in_specs=(qspec, kvspec, kvspec),
            out_specs=qspec,
            check_vma=False,
        )(q, k, v)
    return reference_attention(q, k, v, causal=causal, window=window,
                               scale=scale)
