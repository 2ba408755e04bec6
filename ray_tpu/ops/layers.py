"""Elementwise/norm/rotary building blocks (XLA-fused; no kernels needed).

These stay as plain jnp: XLA fuses them into adjacent matmuls, so a Pallas
kernel would only add boundary overhead.  Computation is done in fp32 and
cast back, the standard TPU-stability recipe for bf16 activations.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm in fp32, output in x.dtype. scale has shape [dim]."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jnp.reciprocal(jnp.sqrt(var + eps))
    return (x * scale.astype(jnp.float32)).astype(dtype)


def rope_frequencies(
    head_dim: int, max_seq_len: int, theta: float = 10000.0
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute rotary cos/sin tables [max_seq_len, head_dim // 2] (fp32)."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
    precise: bool = False,
) -> jnp.ndarray:
    """Rotary position embedding.

    x: [batch, seq, heads, head_dim]; cos/sin: [max_seq, head_dim//2];
    positions: optional [batch, seq] int32 (defaults to arange).

    By default the rotation runs in x.dtype: cos/sin are in [-1, 1], so
    bf16 rotation loses <0.4% relative precision while cutting the fp32
    intermediate HBM traffic that otherwise dominates this op's cost
    (measured +2% end-to-end MFU on v5e).  precise=True keeps fp32.
    """
    b, s, h, d = x.shape
    ct = jnp.float32 if precise else x.dtype
    if positions is None:
        cos_g = cos[:s][None, :, None, :].astype(ct)
        sin_g = sin[:s][None, :, None, :].astype(ct)
    else:
        cos_g = cos[positions][:, :, None, :].astype(ct)
        sin_g = sin[positions][:, :, None, :].astype(ct)
    x1, x2 = jnp.split(x.astype(ct), 2, axis=-1)
    out = jnp.concatenate(
        [x1 * cos_g - x2 * sin_g, x2 * cos_g + x1 * sin_g], axis=-1
    )
    return out.astype(x.dtype)


def swiglu(gate: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    """SwiGLU activation: silu(gate) * up."""
    g = gate.astype(jnp.float32)
    return (g * jnp.reciprocal(1.0 + jnp.exp(-g))).astype(gate.dtype) * up


def heads_projection(y: jnp.ndarray, w: jnp.ndarray, heads: int) -> jnp.ndarray:
    """``y [..., hidden] @ w [hidden, heads * hd]`` handed on as
    ``[..., heads, hd]``: the one place a cached decoder layer projects onto
    its heads (``paged_generation._layer_with_cache``: wq, wk, wv;
    ``longcat``'s ``w_qb``).

    The barrier keeps the product apart from the reshape.  Written as
    ``(y @ w).reshape(...)``, or as an einsum onto ``w.reshape(hidden, heads,
    hd)``, XLA:TPU folds the two into one convolution with the heads as a
    window, wants ``w`` with its hidden axis minor for it, and so slices and
    TRANSPOSES THE WHOLE WEIGHT every step (``slice_bitcast_fusion`` +
    ``copy`` in the compiled program: 50 MB a layer at Mistral-7B's widths,
    3.3 ms of a 16.5 ms decode step, PERF.md section 6 PR 35).  Held apart,
    the product's own fusion streams ``w`` from where it lies, a layer's
    slice of a stacked parameter included, and the relayout falls on the
    result, which a decode step's few rows make small.
    ``tests/test_flash_compile_v5e.py`` holds the compiled program to it.
    """
    out = jax.lax.optimization_barrier(y @ w)
    return out.reshape(*y.shape[:-1], heads, -1)
