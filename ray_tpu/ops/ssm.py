"""State-space (Mamba-1) building blocks: the selective scan over a prompt,
its one-position update at decode, the causal depthwise convolution in
front of it (a sequence's, and one position's over a flat tail), and
LayerNorm.  Plain ``jax.numpy`` / ``lax``: XLA fuses the
update into one elementwise pass over the state.

The recurrence, channel ``i`` of ``I``, state ``n`` of ``N``::

    s_t[n, i] = exp(dt_t[i] * A[n, i]) * s_{t-1}[n, i] + dt_t[i] * x_t[i] * B_t[n]
    y_t[i]    = sum_n s_t[n, i] * C_t[n] + D[i] * x_t[i]

**Layout.**  The state is ``[..., N, I]`` float32, channels minor: ``I``
fills the lanes and ``N`` (16) the sublanes of a TPU tile, where ``[..., I,
N]`` would pad every row of 16 to 128 lanes and read eight times the bytes.
``A`` is handed over in the same orientation, ``[N, I]``.

**A prompt shorter than its bucket.**  ``selective_scan`` and
``causal_conv1d`` take the true ``length``: positions at or past it leave
the state as it is (``dt = 0`` there: ``exp(0) = 1`` and nothing is added)
and the convolution's tail is taken at ``length``, not at the bucket's end,
so a prompt leaves the same state whatever bucket it was padded to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# positions one iteration of the scan's loop takes (the loop is over chunks,
# a chunk's positions are unrolled into the iteration's one fusion)
SCAN_CHUNK = 16


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-5) -> jnp.ndarray:
    """LayerNorm (mean subtracted, a bias) in fp32, output in x.dtype."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


def silu(x: jnp.ndarray) -> jnp.ndarray:
    g = x.astype(jnp.float32)
    return (g * jnp.reciprocal(1.0 + jnp.exp(-g))).astype(x.dtype)


def causal_conv1d(x, w, b, tail, length=None):
    """Causal depthwise convolution with its tail in and out.

    x ``[b, s, I]``; w ``[K, I]`` (tap ``K - 1`` multiplies the current
    position); b ``[I]``; tail ``[b, K - 1, I]``: the inputs just before
    ``x[:, 0]`` (zeros at a sequence's start).  Returns ``(y [b, s, I],
    tail')`` with ``tail'`` the ``K - 1`` inputs that end at position
    ``length - 1`` (``length`` a scalar, default ``s``): what the next
    position's convolution needs."""
    s, K = x.shape[1], w.shape[0]
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    acc = b.astype(jnp.float32)
    for j in range(K):
        acc = acc + xp[:, j:j + s].astype(jnp.float32) * w[j].astype(
            jnp.float32)
    if length is None:
        new_tail = xp[:, s:]
    else:
        new_tail = jax.lax.dynamic_slice_in_dim(xp, length, K - 1, axis=1)
    return acc.astype(x.dtype), new_tail


def causal_conv1d_step(x, w, tail):
    """One position of ``causal_conv1d`` (no bias) with the tail held FLAT:
    x ``[r, I]``; w ``[K, I]``; tail ``[r, (K - 1) I]``, the ``K - 1``
    inputs before ``x``, oldest first.  Returns ``(y [r, I], tail')``.
    What GigaChat3.5's decode update runs off the chip (``ops/delta.py:
    delta_update_records``, the gathered path, on the live slots' tails);
    on the chip its kernel sums the same taps in the same order itself.

    Every operand stays a matrix ``[r, I]`` (lane slices of the tail at
    multiples of ``I``): viewed ``[r, K - 1, I]`` the ``K - 1`` rows would
    pad to a whole sublane tile on the chip, five times the bytes at
    ``K = 4`` in bf16 (0.8 ms a layer at 129 records of 16 384 channels,
    my chip run, PR 52)."""
    K, I = w.shape
    taps = [tail[:, j * I:(j + 1) * I] for j in range(K - 1)] + [x]
    acc = sum(t.astype(jnp.float32) * w[j].astype(jnp.float32)
              for j, t in enumerate(taps))
    return acc.astype(x.dtype), jnp.concatenate(taps[1:], axis=-1)


def selective_update(x, dt, A, B, C, D, state):
    """One position: x, dt ``[r, I]``; A ``[N, I]`` (negative); B, C
    ``[r, N]``; D ``[I]``; state ``[r, N, I]`` float32.  Returns ``(y
    [r, I] float32, state')``."""
    x = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    B, C = B.astype(jnp.float32), C.astype(jnp.float32)
    decay = jnp.exp(dt[:, None, :] * A[None].astype(jnp.float32))
    state = decay * state + (dt * x)[:, None, :] * B[:, :, None]
    y = jnp.sum(state * C[:, :, None], axis=1) + D.astype(jnp.float32) * x
    return y, state


def selective_scan(x, dt, A, B, C, D, state0, length=None,
                   chunk: int = SCAN_CHUNK):
    """The recurrence over a sequence: x, dt ``[b, s, I]``; B, C
    ``[b, s, N]``; state0 ``[b, N, I]`` float32; ``length``: positions at or
    past it do not move the state (their ``y`` is of no use).  A scan over
    chunks of ``chunk`` positions, the positions of a chunk unrolled.
    Returns ``(y [b, s, I] float32, state [b, N, I])``."""
    b, s, I = x.shape
    dt = dt.astype(jnp.float32)
    if length is not None:
        dt = jnp.where((jnp.arange(s) < length)[None, :, None], dt, 0.0)
    pad = (-s) % chunk
    if pad:  # dt = 0 there: the state stays
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                       for a in (x, dt, B, C))
    n = (s + pad) // chunk
    # [chunks, chunk, b, ...]: the scan runs over the leading axis
    xs = tuple(jnp.moveaxis(a.reshape(b, n, chunk, a.shape[-1]), 0, 2)
               for a in (x, dt, B, C))

    def one_chunk(state, args):
        xc, dtc, Bc, Cc = args
        ys = []
        for t in range(chunk):
            y, state = selective_update(xc[t], dtc[t], A, Bc[t], Cc[t], D,
                                        state)
            ys.append(y)
        return state, jnp.stack(ys)

    state, y = jax.lax.scan(one_chunk, state0, xs)
    y = jnp.moveaxis(y, 2, 0).reshape(b, s + pad, I)
    return y[:, :s], state
