"""LongCat-Flash's language model, pure-functional JAX, as ``LLMEngine``
serves it.

Written from the published configuration (``config.json`` of
``meituan-longcat/LongCat-Flash-Omni``; the audio and vision encoders and
the codec decoder of the Omni model are not here: this is the language
model) and the family's published modelling code.  Three things set it
apart from the dense decoder in ``models/llama.py``:

* **Latent attention (MLA).**  Queries go through a rank-``q_lora_rank``
  bottleneck; keys and values are up-projections of ONE latent row a token,
  ``c_kv`` (``kv_lora_rank`` wide, normed and scaled) beside a rotated
  ``k_pe`` (``qk_rope_head_dim`` wide) that all heads share.  **The cache
  holds that row** and nothing per head.  Prefill up-projects the rows it
  attends over (the cached prefix's too) and runs the non-absorbed form:
  keys ``[k_nope | k_pe]`` of ``qk_nope_head_dim + qk_rope_head_dim``
  beside values of ``v_head_dim``.  A prompt with no cached prefix, on one
  TPU device and 256 tokens or more, attends through the flash kernel
  (``ops/pallas/flash_attention.py``, which takes values narrower than the
  keys): all heads in one call and no score matrix in HBM.  Every other
  prefill keeps the plain path, float32 scores a group of ``_HEAD_GROUP``
  heads at a time (``prefill_attention_path``): a prefix hit, because the
  prefix's rows come padded to a bucket of blocks and the mask that hides
  the pad is not causal, which is the only mask the kernel builds; a short
  prompt and the CPU, by ``dot_product_attention``'s own rule.
  Decode absorbs ``W_kvb``: ``q_nope W_kvb,k^T`` is scored against ``c_kv``
  itself, the probabilities weight ``c_kv``, and ``W_kvb,v`` then ``W_o``
  follow; it reads the two halves as leaves of their own (``w_uk``,
  ``w_uv``: ``absorbed_pair``), laid out for those two products.
* **The shortcut-connected double layer.**  A layer is two attention blocks
  and two dense SwiGLU blocks; the expert branch starts after the first
  attention and joins after the second dense block::

      h1 = h  + MLA_0(norm_in0(h))
      y  = norm_post0(h1)
      s  = MoE(y)
      h2 = h1 + FFN_0(y)
      h3 = h2 + MLA_1(norm_in1(h2))
      h' = h3 + FFN_1(norm_post1(h3)) + s

* **The expert layer.**  A router over ``num_experts + zero_experts``
  outputs (softmax scores, top-k of ``score + bias``, weights not
  renormalised, times ``routed_scaling_factor``); a routed expert is a
  SwiGLU, a zero-compute expert returns its input.  The model is told which
  routed experts it **holds** (``first_expert``, ``held_experts``: a chip's
  share under expert parallelism): it routes over all of them, computes its
  own experts' part (``ops/experts.py``: sorted, grouped, no capacity, no
  dropped token) and the zero-compute experts' part in full (every token's
  own chip computes that, so it is counted once, like a shared expert).
  What absent experts would add is not computed and nothing stands in for it.

The paged latent pool is ``{"kv": [2 * L, NB, bs, W]}``: one row a token
and attention block, ``[c_kv | k_pe | 0]``, ``W`` the row padded to whole
128-lane tiles (576 -> 640: a TPU array's minor dimension is tiled by 128
in HBM whether or not the program says so, and the kernel copies whole
pages).  The decode step reads it through ``ops/pallas/paged_attention.py``'s
latent arm where ``decode_attention_path`` says so, and by a gather of the
whole table elsewhere (the CPU, the tests' reference).

Departures from the published modelling code: the rotary pairs.  The
published code rotates columns ``(2i, 2i+1)`` of ``q_pe`` / ``k_pe``;
``ops/layers.apply_rope`` rotates ``(i, i + d/2)``.  The parameters here
hold those columns de-interleaved (published column ``2i`` at ``i``,
``2i+1`` at ``i + d/2``: a fixed permutation of ``W_qb``'s and ``W_kva``'s
rope columns that a checkpoint loader applies once), and a score is a dot
product, so it does not see the order.  RMSNorm's epsilon is the published
1e-5.  The selection bias is a parameter (zeros at init).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu._private import tracing
from ray_tpu.models.paged_generation import (decode_attention_path,
                                             embed_tokens, sample_next)
from ray_tpu.ops.attention import attention_impl, dot_product_attention
from ray_tpu.ops.experts import held_experts_ffn, route_top_k
from ray_tpu.ops.layers import (apply_rope, heads_projection, rms_norm,
                                rope_frequencies, swiglu)

_LANES = 128


@dataclasses.dataclass(frozen=True)
class LongcatConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    num_layers: int = 28            # double layers
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_dim: int = 12288            # the two dense SwiGLU blocks
    expert_ffn_dim: int = 2048
    num_experts: int = 512          # routed experts the router knows
    zero_experts: int = 256         # zero-compute (identity) experts
    experts_per_token: int = 12
    routed_scaling_factor: float = 6.0
    # the routed experts held here: ``held_experts`` of them from
    # ``first_expert`` on (None: all of them)
    first_expert: int = 0
    held_experts: Optional[int] = None
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def num_held(self) -> int:
        return self.num_experts if self.held_experts is None \
            else self.held_experts

    @property
    def latent_width(self) -> int:
        """A cached row, padded to whole lane tiles."""
        w = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-w // _LANES) * _LANES

    @staticmethod
    def tiny(**kw) -> "LongcatConfig":
        """Test-scale model (CPU, float32)."""
        defaults = dict(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, ffn_dim=128,
            expert_ffn_dim=32, num_experts=8, zero_experts=4,
            experts_per_token=3, max_seq_len=128, rope_theta=1e4,
            dtype=jnp.float32, param_dtype=jnp.float32)
        defaults.update(kw)
        return LongcatConfig(**defaults)


# ------------------------------------------------------------------ params

def absorbed_pair(w_kvb, cfg: LongcatConfig):
    """``w_kvb [kr, nh * (dn + dv)]`` (the published ``kv_b_proj``) ->
    ``(w_uk [nh, dn, kr], w_uv [nh, kr, dv])``: its keys' half and its
    values' half with the heads leading, each laid out as the decode
    step's absorbed product streams it (``_mla_absorbed``).  A slice and a
    transposition, no arithmetic: every element of ``w_kvb`` is in exactly
    one of the two, bit for bit.

    The pair is DERIVED, not trained: ``longcat_init`` makes it here from
    the ``w_kvb`` it has just drawn, and whoever else writes ``w_kvb`` (a
    checkpoint loader after reading ``kv_b_proj``, an update of the
    weights) calls this again, or the decode step keeps multiplying by the
    old matrix while the prefill uses the new one."""
    kr, nh, dn = cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim
    w = w_kvb.reshape(kr, nh, -1)
    return (jnp.transpose(w[..., :dn], (1, 2, 0)),
            jnp.transpose(w[..., dn:], (1, 0, 2)))


@functools.partial(jax.jit, static_argnames=("cfg",))
def longcat_init(key: jax.Array, cfg: LongcatConfig) -> Dict[str, Any]:
    """Seeded parameters.  ``layers`` is a list of L double layers, each
    ``{"attn": [2 blocks], "ffn": [2 blocks], "router", "experts"}``, every
    weight a leaf of its own: nothing is stacked over depth, because a
    slice of a stacked weight is a copy wherever XLA cannot fold it into
    its consumer (a Mosaic call's operand, a weight two blocks share), and
    a decode step that copies its weights reads them twice.  ONE program: a
    10 GB tree made leaf by leaf is a hundred dispatches whose time moves
    with the host.

    An attention block holds ``kv_b_proj`` as THREE leaves: ``w_kvb [kr,
    nh * (dn + dv)]``, the published matrix, which the prefill
    (``_mla_plain``: ``latent_prefill_suffix``, ``longcat_apply``) and the
    benchmark's reference read and through which alone a gradient of
    ``longcat_apply`` flows; and ``w_uk [nh, dn, kr]`` / ``w_uv [nh, kr,
    dv]``, its two halves as the decode step's absorbed products read them
    (``_mla_absorbed``, which never touches ``w_kvb``), made from it here
    by ``absorbed_pair`` and by nobody else.  The second copy costs
    ``kr * nh * (dn + dv)`` parameters a block: 16.8 MB in bf16 at the
    published widths, 134 MB over the serving cell's eight blocks (1.3% of
    its weights)."""
    L, H, nh = cfg.num_layers, cfg.hidden_size, cfg.num_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F, Fe, E = cfg.ffn_dim, cfg.expert_ffn_dim, cfg.num_held
    N = cfg.num_experts + cfg.zero_experts
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 2 + 20 * L))

    def w(*shape):
        return jax.random.normal(next(keys), shape, pd) * 0.02

    def ones(*shape):
        return jnp.ones(shape, pd)

    def attention():
        w_kvb = w(kr, nh * (dn + dv))
        w_uk, w_uv = absorbed_pair(w_kvb, cfg)
        return {"norm": ones(H), "w_qa": w(H, qr), "q_norm": ones(qr),
                "w_qb": w(qr, nh * (dn + dr)), "w_kva": w(H, kr + dr),
                "kv_norm": ones(kr), "w_kvb": w_kvb, "w_uk": w_uk,
                "w_uv": w_uv, "w_o": w(nh * dv, H)}

    def dense():
        return {"norm": ones(H), "w_gate": w(H, F), "w_up": w(H, F),
                "w_down": w(F, H)}

    def layer():
        return {"attn": [attention(), attention()],
                "ffn": [dense(), dense()],
                "router": {"w": w(H, N), "bias": jnp.zeros((N,), jnp.float32)},
                "experts": {"w_gate": w(E, H, Fe), "w_up": w(E, H, Fe),
                            "w_down": w(E, Fe, H)}}

    return {"embed": w(cfg.vocab_size, H),
            "layers": [layer() for _ in range(L)],
            "final_norm": ones(H),
            "lm_head": w(H, cfg.vocab_size)}


# ------------------------------------------------------------------ blocks

def _mla_project(x, ap, cfg: LongcatConfig, cos, sin, positions):
    """x ``[b, s, H]`` -> q_nope ``[b, s, nh, dn]``, q_pe ``[b, s, nh, dr]``
    (rotated), c_kv ``[b, s, kr]`` (normed and scaled: what the cache
    holds), k_pe ``[b, s, dr]`` (rotated, shared by the heads)."""
    H = x.shape[-1]
    dt = cfg.dtype
    nh, dn = cfg.num_heads, cfg.qk_nope_head_dim
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    with tracing.scope("attn.proj"):
        c_q = rms_norm(x @ ap["w_qa"].astype(dt), ap["q_norm"],
                       cfg.rms_norm_eps)
        if cfg.mla_scale_q_lora:
            c_q = c_q * (H / qr) ** 0.5
        q = heads_projection(c_q, ap["w_qb"].astype(dt), nh)
        kv = x @ ap["w_kva"].astype(dt)
        c_kv = rms_norm(kv[..., :kr], ap["kv_norm"], cfg.rms_norm_eps)
        if cfg.mla_scale_kv_lora:
            c_kv = c_kv * (H / kr) ** 0.5
        q_pe = apply_rope(q[..., dn:], cos, sin, positions)
        k_pe = apply_rope(kv[..., kr:][:, :, None], cos, sin,
                          positions)[:, :, 0]
        return q[..., :dn], q_pe, c_kv, k_pe


def _softmax_scale(cfg: LongcatConfig) -> float:
    return float(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def prefill_attention_path(seq: int, prefix: int, impl: str = "auto") -> str:
    """Which form ``seq`` queries attend through after ``prefix`` cached rows
    (the padded counts a program is traced at): ``"flash"`` | ``"plain"``.
    Flash where the keys are exactly the queries' positions (no prefix: the
    mask is then the causal one for every live query, which is the only mask
    the kernel builds) and ``dot_product_attention``'s own rule
    (``attention_impl``) picks the kernel: one TPU device, 256 queries or
    more.  ``impl="flash"`` stands in for that rule (the tests' interpreter,
    a compile for the chip from the CPU); a prefix keeps the plain form
    whatever it says."""
    if prefix == 0 and (impl == "flash" or impl == "auto"
                        and attention_impl(seq) == "flash"):
        return "flash"
    return "plain"


# heads a score matrix is made for at a time where it is large: a 2048-token
# prefill's float32 scores are 16 MB a head, 1 GB for all 64 at once
_HEAD_GROUP = 16


def _mla_plain(q_nope, q_pe, c_kv, k_pe, mask, ap, cfg: LongcatConfig,
               impl: str = "auto"):
    """The non-absorbed form over rows ``c_kv [b, t, kr]`` / ``k_pe
    [b, t, dr]`` (up-projected here); mask ``[b, s, t]``.  With ``t == s``
    the callers' mask is causal for every live query, and the flash kernel
    takes it from there where ``prefill_attention_path`` says so: all heads
    in one call, keys ``[k_nope | k_pe]`` beside narrower values, no score
    matrix in HBM."""
    b, s, nh, dn = q_nope.shape
    t = c_kv.shape[1]
    dt, dv = cfg.dtype, cfg.v_head_dim
    with tracing.scope("attn.proj"):  # the cached rows' up-projection
        kvb = (c_kv @ ap["w_kvb"].astype(dt)).reshape(b, t, nh, dn + dv)

    def heads(args):
        qn, qr, kn, v = args  # [b, s|t, g, d]: one group of heads
        scores = (jnp.einsum("bshd,bthd->bhst", qn, kn,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshr,btr->bhst", qr, k_pe,
                               preferred_element_type=jnp.float32))
        scores = jnp.where(mask[:, None], scores * _softmax_scale(cfg),
                           -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        return jnp.einsum("bhst,bthd->bshd", probs, v,
                          preferred_element_type=jnp.float32).astype(dt)

    parts = (q_nope, q_pe, kvb[..., :dn], kvb[..., dn:])
    g = _HEAD_GROUP
    with tracing.scope("attn.core"):
        if prefill_attention_path(s, t - s, impl) == "flash":
            pe = jnp.broadcast_to(k_pe[:, :, None],
                                  (b, t, nh, k_pe.shape[-1]))
            out = dot_product_attention(
                jnp.concatenate([q_nope, q_pe], -1),
                jnp.concatenate([kvb[..., :dn], pe], -1), kvb[..., dn:],
                causal=True, impl="flash", scale=_softmax_scale(cfg))
        elif nh <= g or nh % g:
            out = heads(parts)
        else:  # one group of heads after another
            split = lambda a: jnp.moveaxis(  # noqa: E731
                a.reshape(*a.shape[:2], nh // g, g, a.shape[-1]), 2, 0)
            out = jax.lax.map(heads, tuple(split(a) for a in parts))
            out = jnp.moveaxis(out, 0, 2).reshape(b, s, nh, dv)
    with tracing.scope("attn.out"):
        return out.reshape(b, s, nh * dv) @ ap["w_o"].astype(dt)


def _mla_absorbed(q_nope, q_pe, ap, cfg: LongcatConfig, attend_rows):
    """One query token a slot, ``W_kvb`` absorbed.  q_nope ``[b, nh, dn]``,
    q_pe ``[b, nh, dr]``; ``attend_rows(q [b, nh, W]) -> [b, nh, kr]``
    scores the query against the cached rows and returns the weighted
    ``c_kv``.

    Both products are batched over the heads and read the block's derived
    pair (``absorbed_pair``), never ``w_kvb``: as strided halves of that one
    leaf, laid out for the prefill's ``c_kv @ w_kvb``, XLA:TPU fetched the
    whole matrix transposed into fast memory in every block of every step.
    The way in is spelt with the heads leading on both sides and each swap
    held apart from the product by a barrier: left to itself XLA multiplies
    with the slots minor (``[nh, kr, b]``) and transposes the result back
    for the kernel, which takes ``[b, nh, W]``; held apart, the product
    emits ``[nh, b, kr]`` and the swap is a permutation of whole rows.
    Either alone buys nothing (the pair under the plain spelling 0.04 ms of
    a 14.6 ms step, the spelling over ``w_kvb``'s halves none), together
    0.56 ms: PERF.md section 6, PR 45;
    ``tests/test_flash_compile_v5e.py`` holds the compiled program to it."""
    b, nh, dn = q_nope.shape
    dt, kr, dv = cfg.dtype, cfg.kv_lora_rank, cfg.v_head_dim
    barrier = jax.lax.optimization_barrier
    with tracing.scope("attn.proj"):  # the query into the latent space
        q_lat = jnp.einsum("hbd,hdk->hbk",
                           barrier(jnp.swapaxes(q_nope, 0, 1)),
                           ap["w_uk"].astype(dt),
                           preferred_element_type=jnp.float32).astype(dt)
        q_lat = jnp.swapaxes(barrier(q_lat), 0, 1)
        pad = cfg.latent_width - kr - q_pe.shape[-1]
        q = jnp.concatenate(
            [q_lat, q_pe, jnp.zeros((b, nh, pad), dt)], axis=-1)
    with tracing.scope("attn.core"):
        o_lat = attend_rows(q)
    with tracing.scope("attn.out"):  # out of it again, then W_o
        out = jnp.einsum("bhk,hkd->bhd", o_lat, ap["w_uv"].astype(dt),
                         preferred_element_type=jnp.float32).astype(dt)
        return out.reshape(b, nh * dv) @ ap["w_o"].astype(dt)


def _pack_rows(c_kv, k_pe, cfg: LongcatConfig):
    """``[..., kr]``, ``[..., dr]`` -> the cached row ``[..., W]``."""
    pad = cfg.latent_width - c_kv.shape[-1] - k_pe.shape[-1]
    return jnp.concatenate(
        [c_kv, k_pe, jnp.zeros((*c_kv.shape[:-1], pad), c_kv.dtype)], -1)


def _ffn(x, fp, cfg: LongcatConfig):
    dt = cfg.dtype
    act = swiglu(x @ fp["w_gate"].astype(dt), x @ fp["w_up"].astype(dt))
    return act @ fp["w_down"].astype(dt)


def _moe(y, router, ep, cfg: LongcatConfig, live):
    """The shortcut branch.  y ``[b, s, H]``, live ``[b, s]`` bool;
    ``router`` the router's leaves, ``ep`` the held experts'.
    Returns (s ``[b, s, H]``, int32 ``[3]``: pairs on held experts, held
    experts hit, zero-compute picks; of live tokens only)."""
    b, s, H = y.shape
    yf, lf = y.reshape(b * s, H), live.reshape(b * s)
    with tracing.scope("router"):
        idx, weight = route_top_k(
            yf, router["w"], router["bias"], cfg.experts_per_token,
            cfg.routed_scaling_factor)
    with tracing.scope("experts"):
        out, pairs, hit = held_experts_ffn(
            yf, idx, weight, ep["w_gate"], ep["w_up"], ep["w_down"],
            first=cfg.first_expert, live=lf)
        zero = idx >= cfg.num_experts  # identity experts: counted in full
        out = out + (jnp.sum(jnp.where(zero, weight, 0.0), -1)[:, None]
                     * yf.astype(jnp.float32))
        picks = jnp.sum(zero & lf[:, None], dtype=jnp.int32)
        return (out.astype(cfg.dtype).reshape(b, s, H),
                jnp.stack([pairs, hit, picks]).astype(jnp.int32))


def _double_layer(h, lp, cfg: LongcatConfig, attend, live):
    """``lp``: one layer's leaves.  ``attend(x_normed, ap) -> [b, s, H]``:
    an attention block's output (projection, cache and ``W_o`` are the
    caller's), called for block 0 then block 1."""
    eps = cfg.rms_norm_eps
    at, ff = lp["attn"], lp["ffn"]
    def attention(h, ap):  # attend opens attn.cache / .core / .out itself
        with tracing.scope("attn.proj"):
            xn = rms_norm(h, ap["norm"], eps)
        o = attend(xn, ap)
        with tracing.scope("attn.out"):
            return h + o

    h1 = attention(h, at[0])
    with tracing.scope("ffn"):
        y = rms_norm(h1, ff[0]["norm"], eps)
    s, stats = _moe(y, lp["router"], lp["experts"], cfg, live)
    with tracing.scope("ffn"):
        h2 = h1 + _ffn(y, ff[0], cfg)
    h3 = attention(h2, at[1])
    with tracing.scope("ffn"):
        out = h3 + _ffn(rms_norm(h3, ff[1]["norm"], eps), ff[1], cfg) + s
    return out, stats


def _layers(params, x, cfg: LongcatConfig, attend, live):
    stats = jnp.zeros(3, jnp.int32)
    for lp in params["layers"]:
        x, st = _double_layer(x, lp, cfg, attend, live)
        stats += st
    return x, stats


def _lm_head(params, cfg: LongcatConfig, x):
    with tracing.scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return jnp.einsum("bsh,hv->bsv", x,
                          params["lm_head"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- programs

def longcat_apply(params, tokens, cfg: LongcatConfig, *, mesh=None,
                  return_stats: bool = False):
    """tokens ``[b, s]`` -> logits ``[b, s, vocab]`` float32: the plain
    causal forward, no cache (tests, and what a trainer would start from)."""
    if mesh is not None:
        raise NotImplementedError("LongCat-Flash has no sharded forward yet")
    b, s = tokens.shape
    cos, sin = rope_frequencies(cfg.qk_rope_head_dim, s, cfg.rope_theta)
    mask = jnp.broadcast_to(
        jnp.arange(s)[None, :, None] >= jnp.arange(s)[None, None, :],
        (b, s, s))
    live = jnp.ones((b, s), bool)

    def attend(xn, ap):
        return _mla_plain(*_mla_project(xn, ap, cfg, cos, sin, None), mask,
                          ap, cfg)

    x, stats = _layers(params, embed_tokens(params, tokens, cfg.dtype),
                       cfg, attend, live)
    logits = _lm_head(params, cfg, x)
    return (logits, stats) if return_stats else logits


def init_latent_pool(cfg: LongcatConfig, num_blocks: int, block_size: int,
                     kv_dtype: str | None = None):
    """``{"kv": [2 * L, NB, bs, W]}``; block 0 is the scratch block."""
    if kv_dtype not in (None, "auto"):
        raise ValueError(
            f"the latent pool is stored in the model's dtype: kv_dtype "
            f"{kv_dtype!r} is not supported for LongCat-Flash (None/'auto')")
    return {"kv": jnp.zeros((2 * cfg.num_layers, num_blocks, block_size,
                             cfg.latent_width), cfg.dtype)}


def gather_latent_prefix(pool, blocks, cfg: LongcatConfig):
    """The cached rows of a block list ``[P]``: (c_kv ``[2L, P*bs, kr]``,
    k_pe ``[2L, P*bs, dr]``)."""
    A, _, bs, W = pool["kv"].shape
    rows = pool["kv"][:, blocks].reshape(A, blocks.shape[0] * bs, W)
    kr = cfg.kv_lora_rank
    return rows[..., :kr], rows[..., kr:kr + cfg.qk_rope_head_dim]


def latent_prefill_suffix(params, tokens, length, start_pos, prefix_ckv,
                          prefix_kpe, prefix_len, dst_blocks, dst_offsets,
                          pool, cfg: LongcatConfig, attn_impl: str = "auto"):
    """b=1 prefill of a prompt *suffix* against a cached prefix: the
    contract of ``paged_generation.prefill_suffix`` with latent rows for
    keys and values (``gather_latent_prefix``'s pair).  The prefix's rows
    are up-projected with the suffix's and the plain form runs over both;
    a prompt with no cached prefix (``P == 0``) attends through the flash
    kernel where ``prefill_attention_path`` says so (``attn_impl``: its
    ``impl``).  Returns ``(logits_at_last [1, vocab], pool, stats int32[3])``."""
    _, S = tokens.shape
    P = prefix_ckv.shape[1]
    dt = cfg.dtype
    cos, sin = rope_frequencies(cfg.qk_rope_head_dim, P + S, cfg.rope_theta)
    positions = start_pos + jnp.arange(S)[None, :]
    sfx = jnp.arange(S)
    pmask = jnp.arange(P)[None, None, :] < prefix_len
    smask = (sfx[None, None, :] <= sfx[None, :, None]) & (
        sfx[None, None, :] < length)
    mask = jnp.concatenate(
        [jnp.broadcast_to(pmask, (1, S, P)), smask], axis=-1)
    live = (sfx < length)[None, :]
    kv = pool["kv"]
    a = 0  # index of the attention block in the stacked pool

    def attend(xn, ap):
        nonlocal kv, a
        q_nope, q_pe, c_kv, k_pe = _mla_project(xn, ap, cfg, cos, sin,
                                                positions)
        with tracing.scope("attn.cache"):
            # pad lanes land in the scratch block
            kv = kv.at[a, dst_blocks, dst_offsets].set(
                _pack_rows(c_kv[0], k_pe[0], cfg))
            c_all = jnp.concatenate(
                [prefix_ckv[a][None].astype(dt), c_kv], 1)
            pe_all = jnp.concatenate(
                [prefix_kpe[a][None].astype(dt), k_pe], 1)
        a += 1
        return _mla_plain(q_nope, q_pe, c_all, pe_all, mask, ap, cfg,
                          attn_impl)

    x, stats = _layers(params, embed_tokens(params, tokens, cfg.dtype),
                       cfg, attend, live)
    logits = _lm_head(params, cfg, x)
    with tracing.scope("head"):
        last = jnp.take_along_axis(
            logits, (length - 1)[None, None, None].astype(jnp.int32),
            axis=1)[:, 0]
    return last, {"kv": kv}, stats


def latent_decode_step(params, token, cur_len, block_tables, pool,
                       cfg: LongcatConfig, attn: str | None = None):
    """One token for every slot against block-table caches of latent rows:
    the contract of ``paged_generation.paged_decode_step``.  Returns
    ``(logits [b, vocab], pool, stats int32[3])``; a slot whose table row
    is all scratch holds no request: it attends over nothing, is routed to
    no expert and is not counted."""
    if attn is None:
        attn = decode_attention_path(pool)
    b = token.shape[0]
    MB = block_tables.shape[1]
    bs = pool["kv"].shape[2]
    dt, kr = cfg.dtype, cfg.kv_lora_rank
    with tracing.scope("attn.proj"):  # the rotary table
        cos, sin = rope_frequencies(cfg.qk_rope_head_dim, MB * bs,
                                    cfg.rope_theta)
    positions = cur_len[:, None]
    idx = jnp.arange(MB * bs)
    mask = idx[None, None, :] <= cur_len[:, None, None]
    with tracing.scope("attn.cache"):  # where the step's rows go
        rows = jnp.arange(b)
        blk = block_tables[rows, cur_len // bs]
        off = cur_len % bs
        live = block_tables[:, 0] != 0
        lengths = jnp.where(live, cur_len + 1, 0)
    scale = _softmax_scale(cfg)
    kv = pool["kv"]
    a = 0

    def attend(xn, ap):
        nonlocal kv, a
        q_nope, q_pe, c_kv, k_pe = _mla_project(xn, ap, cfg, cos, sin,
                                                positions)
        with tracing.scope("attn.cache"):
            # the new row first, so that the token attends to itself
            kv = kv.at[a, blk, off].set(
                _pack_rows(c_kv[:, 0], k_pe[:, 0], cfg))
        block = a
        a += 1

        def kernel(q):
            from ray_tpu.ops.pallas.paged_attention import \
                latent_paged_attention

            return latent_paged_attention(
                q, kv, block_tables, lengths, layer=block, value_width=kr,
                scale=scale)

        def gather(q):
            g = kv[block, block_tables].reshape(b, MB * bs, -1)
            scores = jnp.einsum("bhw,btw->bht", q, g,
                                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(mask, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(dt)
            return jnp.einsum("bht,btk->bhk", probs, g[..., :kr],
                              preferred_element_type=jnp.float32).astype(dt)

        with tracing.scope("attn.proj"):
            q_nope, q_pe = q_nope[:, 0], q_pe[:, 0]
        return _mla_absorbed(
            q_nope, q_pe, ap, cfg,
            kernel if attn == "latent_kernel" else gather)[:, None]

    x, stats = _layers(params,
                       embed_tokens(params, token, cfg.dtype)[:, None], cfg,
                       attend, live[:, None])
    return _lm_head(params, cfg, x)[:, 0], {"kv": kv}, stats


def latent_decode_sample(params, token, cur_len, block_tables, pool, key,
                         temps, cfg: LongcatConfig, attn: str | None = None):
    """``paged_generation.paged_decode_sample``'s contract (on-device
    sampling, every output the next step needs a device array), plus the
    step's three expert counters."""
    ML = block_tables.shape[1] * pool["kv"].shape[2]
    safe_cur = jnp.minimum(cur_len, ML - 1)
    logits, pool, stats = latent_decode_step(
        params, token, safe_cur, block_tables, pool, cfg=cfg, attn=attn)
    nxt, key = sample_next(logits, key, temps)
    return nxt, cur_len + 1, key, pool, stats
