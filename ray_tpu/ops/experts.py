"""An expert layer that is told which experts it holds.

Expert parallelism gives a chip a contiguous range of a layer's routed
experts.  The router scores all of them; the chip computes what ITS experts
add for the tokens routed to them, and what the absent experts would add is
somebody else's part of the sum (on one chip of a cut deployment: left out).

``route_top_k`` is the router (softmax or sigmoid scores, an additive
selection bias that picks but does not weigh, picks limited to the best
groups of experts and the picked weights renormalised where the model says
so).  ``held_experts_ffn`` is the dispatch: **sorted**, never a
dense all-experts product, no capacity and no dropped token.

Shared by both of its paths: every (token, pick) pair gets a key, its
expert's index in the held range or a sentinel past it; one stable sort puts
the held pairs first, grouped by expert; the experts' counts and offsets,
and the returned ``pairs`` (pairs on held experts) and ``experts_hit``.

Then one of two paths, which share no loop because their needs conflict
(below some 240 rows an expert the weights' bytes bound the layer and the
smallest tile is right; above it the MXU does and whole tiles are):

* ``"grouped"``: the held pairs are walked in chunks of ``chunk`` rows by a
  loop whose trip count is ``ceil(held pairs / chunk)``, a run-time value:
  the work follows the pairs that landed here, and no bound on them is ever
  assumed.  A chunk gathers its tokens' rows, runs the expert's three
  products (gate and up, the ``activation`` of the two, down: SwiGLU unless
  told) as ``jax.lax.ragged_dot`` over the chunk's group sizes (on a TPU a
  Mosaic grouped matmul), weighs each row and scatter-adds it to its token.
  The chunk is the trade between reading an expert's weights again (every
  chunk reads the weights of the experts it holds rows for) and computing
  on padding rows (the last chunk is padded to ``chunk``).
* ``"decode_kernel"`` (``ops/pallas/expert_decode.py``): each HIT expert's
  three weights streamed through VMEM once against its rows in tiles of 16,
  the activation on float32, the weighted float32 rows added to their
  tokens inside the kernel; an expert no pair landed on is not read.

``expert_path`` picks, from what can be seen when the program is traced and
nothing else: the static shapes, the dtype, the backend and its device
count.  The same function labels the engine's programs
(``LLMEngine.stats()["experts"]``).

Name scopes (``docs/observability.md``): the models open ``router`` and
``experts`` around their calls of the two functions; the grouped path's
weigh-and-scatter-add opens ``experts.combine`` inside it, the one step of
the layer whose cost is its own (0.75 us a pair on a v5e).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import tracing
from ray_tpu.ops.layers import swiglu


def route_top_k(y, w_router, bias, k: int, scale: float,
                renormalise: bool = False, score: str = "softmax",
                groups: tuple[int, int] | None = None):
    """y ``[T, H]``, w_router ``[H, N]``, bias ``[N]`` or None -> (idx
    ``[T, k]`` int32, weight ``[T, k]`` float32), and with ``groups`` a
    third: kept ``[T, n_group]`` bool, the groups a token's picks came from.

    ``p = softmax(y W_r)`` in float32 over all N outputs (``score``
    ``"sigmoid"``: each output's own sigmoid); the k largest of ``p + bias``
    are picked; a pick's weight is its own ``p`` (no bias), divided by the
    sum over the k picked where ``renormalise`` (``norm_topk_prob``), times
    ``scale``.  ``groups = (n_group, topk_group)`` limits the picks to
    groups (DeepSeek-V3's ``noaux_tc``): the N outputs are ``n_group`` runs
    of consecutive experts, a group's score is the sum of its two largest
    ``p + bias``, the ``topk_group`` best groups stay and the others'
    ``p + bias`` are set to ``-inf`` before the k are picked (what bounds
    the chips a token travels to).  The product runs at the highest
    precision: a pick is a discrete choice, and N is small."""
    logits = jnp.matmul(y.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    p = jax.nn.sigmoid(logits) if score == "sigmoid" \
        else jax.nn.softmax(logits, axis=-1)
    choice = p if bias is None else p + bias.astype(jnp.float32)
    if groups is not None:
        n_group, topk_group = groups
        by_group = choice.reshape(choice.shape[0], n_group, -1)
        group_score = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)
        _, best = lax.top_k(group_score, topk_group)
        kept = jnp.any(best[:, :, None] == jnp.arange(n_group), axis=1)
        choice = jnp.where(kept[:, :, None], by_group,
                           -jnp.inf).reshape(choice.shape)
    _, idx = lax.top_k(choice, k)
    weight = jnp.take_along_axis(p, idx, axis=-1)
    if renormalise:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    out = (idx.astype(jnp.int32), weight * scale)
    return out if groups is None else (*out, kept)


def default_chunk(pairs: int) -> int:
    """Rows a chunk of the dispatch holds for ``pairs`` (token, pick)
    pairs: an eighth of them (at an even routing over 48 times as many
    experts as are held, a fraction of that lands here), in whole MXU
    tiles, between 256 and 4096."""
    return int(min(4096, max(256, -(-pairs // 8 // 128) * 128)))


def reglu(gate: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    """ReGLU activation: relu(gate) * up."""
    return jnp.maximum(gate, 0) * up


# The most (token, pick) pairs ``expert_path`` hands the decode kernel: ONE
# rule on the static pair count, measured on a v5e at both served models'
# widths with every token live (ms an expert layer, the kernel | the grouped
# path; my chip runs, PR 32, call 1).  SmallThinker (H 2560, F 768, 64
# experts, 6 picks): 192 pairs (its decode step) 0.97 | 1.87, 768: 1.03 |
# 2.09, 1536: 1.04 | 2.28, 3072: 1.06 | 2.60, 6144: 1.18 | 6.44, 12 288:
# 2.22 | 11.07.  LongCat (H 6144, F 2048, 16 of 768 held, 12 picks): 1536
# pairs (its decode step) 1.53 | 2.29, 3072: 1.71 | 2.25, 6144: 1.76 | 2.87,
# 12 288: 1.87 | 4.61, 24 576: 2.27 | 6.07.  Up to 3072 pairs the kernel's
# time is the hit experts' bytes at 84-90% of HBM speed; from 6144 on it
# grows with the rows (each 16-row tile of an expert loads the weights into
# the MXU again), which is the grouped path's kind of problem.  The kernel
# stays ahead there only because the grouped path pays 0.75 us a pair for
# its scatter-add: that is ROADMAP A3's to cure, in the grouped path.  E did
# not enter: 16 held experts and 64 read the same.
DECODE_KERNEL_MAX_PAIRS = 4096
# y and the result stay whole in VMEM as float32, two stages of weights
# beside them (ops/pallas/expert_decode.py:vmem_bytes; the v5e has 128 MiB)
_DECODE_KERNEL_MAX_ROWS_BYTES = 32 << 20


def expert_path(T: int, k: int, H: int, F: int, dtype) -> str:
    """Which path ``held_experts_ffn`` takes for T tokens of k picks over
    experts of widths H and F: ``"decode_kernel"`` or ``"grouped"``.
    A function of static shapes, the dtype, the backend and its device
    count, never of a knob: one TPU device (the kernel is not under
    ``shard_map``; ``ops/attention.py`` keeps Pallas off the CPU path the
    same way), widths in whole 128-lane tiles and tokens in whole sublane
    tiles (Mosaic refuses others), 16- or 32-bit floats, rows that fit
    VMEM, and at most ``DECODE_KERNEL_MAX_PAIRS`` pairs."""
    if (jax.default_backend() != "tpu" or jax.device_count() != 1
            or H % 128 or F % 128 or T % 8
            or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32)
            or 2 * T * H * 4 > _DECODE_KERNEL_MAX_ROWS_BYTES):
        return "grouped"
    return "decode_kernel" if T * k <= DECODE_KERNEL_MAX_PAIRS else "grouped"


def held_experts_ffn(y, idx, weight, w_gate, w_up, w_down, *, first: int,
                     live=None, chunk: int | None = None,
                     activation=swiglu):
    """What the held experts add to each token.

    y ``[T, H]``; idx / weight ``[T, k]`` from the router (global expert
    ids); w_gate / w_up ``[E, H, F]``, w_down ``[E, F, H]``: the weights of
    experts ``first .. first + E - 1``.  ``live`` ``[T]`` bool: tokens that
    are padding (a freed slot, a bucket's tail) are routed nowhere.
    ``activation(gate, up)``: what stands between an expert's first two
    products and its third.
    Returns (out ``[T, H]`` float32, pairs, experts_hit): the weighted sum
    over the held experts a token picked, how many (token, pick) pairs
    landed on held experts, and how many held experts got at least one.
    """
    T, k = idx.shape
    E = w_gate.shape[0]
    N = T * k
    C = chunk or default_chunk(N)
    local = idx - first
    held = (local >= 0) & (local < E)
    if live is not None:
        held &= live[:, None]
    key = jnp.where(held, local, E).reshape(N)
    order = jnp.argsort(key, stable=True)  # held pairs first, by expert
    counts = jnp.sum(key[:, None] == jnp.arange(E)[None, :], axis=0,
                     dtype=jnp.int32)  # [E]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    total = ends[-1]
    hit = jnp.sum(counts > 0, dtype=jnp.int32)
    if expert_path(T, k, y.shape[1], w_gate.shape[2],
                   y.dtype) == "decode_kernel":
        # imported where it is used: a CPU worker never loads Pallas
        from ray_tpu.ops.pallas.expert_decode import expert_decode_ffn

        out = expert_decode_ffn(
            y, order // k, weight.reshape(N)[order], starts, counts,
            w_gate, w_up, w_down, activation=activation)
        return out, total, hit
    # padded by one chunk so that the last chunk's slice never clamps
    token_of = jnp.pad(order // k, (0, C)).astype(jnp.int32)
    w_sorted = jnp.pad(weight.reshape(N)[order], (0, C))
    dt = y.dtype

    def one_chunk(i, out):
        lo = i * C
        rows = lax.dynamic_slice(token_of, (lo,), (C,))
        w = lax.dynamic_slice(w_sorted, (lo,), (C,))
        valid = lo + jnp.arange(C) < total
        sizes = jnp.clip(ends, lo, lo + C) - jnp.clip(starts, lo, lo + C)
        x = y[rows]
        gate = lax.ragged_dot(x, w_gate.astype(dt), sizes)
        up = lax.ragged_dot(x, w_up.astype(dt), sizes)
        o = lax.ragged_dot(activation(gate, up), w_down.astype(dt), sizes,
                           preferred_element_type=jnp.float32)
        # a row past the last group belongs to no expert: whatever the
        # grouped product left there is not read
        with tracing.scope("experts.combine"):
            o = jnp.where(valid[:, None], o * w[:, None], 0.0)
            return out.at[rows].add(o)

    out = lax.fori_loop(0, (total + C - 1) // C, one_chunk,
                        jnp.zeros((T, y.shape[1]), jnp.float32))
    return out, total, hit
