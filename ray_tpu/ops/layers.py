"""Elementwise/norm/rotary building blocks (XLA-fused; no kernels needed).

These stay as plain jnp: XLA fuses them into adjacent matmuls, so a Pallas
kernel would only add boundary overhead.  Computation is done in fp32 and
cast back, the standard TPU-stability recipe for bf16 activations.
"""

from __future__ import annotations

import math
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


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature for a context stretched ``factor``
    times: ``0.1 * mscale * ln(factor) + 1`` (1 where nothing is
    stretched)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_correction_range(head_dim: int, theta: float, original_max_len: int,
                          beta_fast: float, beta_slow: float
                          ) -> Tuple[int, int]:
    """The rotary pairs between which YaRN blends: pair ``i`` turns
    ``original_max_len * theta^(-2i/d) / 2pi`` times over the original
    context; pairs below ``low`` (more than ``beta_fast`` turns) keep their
    frequency, pairs above ``high`` (fewer than ``beta_slow``) are
    interpolated."""
    def pair(turns):
        return (head_dim * math.log(original_max_len / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), head_dim - 1))


def yarn_rope_frequencies(
    head_dim: int, max_seq_len: int, theta: float, *, factor: float,
    original_max_len: int, beta_fast: float = 32.0, beta_slow: float = 1.0,
    mscale: float = 1.0, mscale_all_dim: float = 0.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``rope_frequencies`` under YaRN (``rope_scaling`` of type ``yarn`` as
    DeepSeek-V3's family publishes it): pair ``i`` keeps ``f_i =
    theta^(-2i/d)`` below the correction range, takes ``f_i / factor``
    above it and a linear blend inside; cos and sin are scaled by
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``.
    The table acts at every position, not only past ``original_max_len``."""
    low, high = yarn_correction_range(head_dim, theta, original_max_len,
                                      beta_fast, beta_slow)
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    freq = 1.0 / theta ** (2 * i / head_dim)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    inv_freq = freq * (1 - ramp) + freq / factor * ramp
    t = jnp.arange(max_seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
    return jnp.cos(freqs) * m, jnp.sin(freqs) * m


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
