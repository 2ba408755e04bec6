"""LongCat-Flash's language model, pure-functional JAX, as ``LLMEngine``
serves it.

Written from the published configuration (``config.json`` of
``meituan-longcat/LongCat-Flash-Omni``; the audio and vision encoders and
the codec decoder of the Omni model are not here: this is the language
model) and the family's published modelling code.  Three things set it
apart from the dense decoder in ``models/llama.py``:

* **Latent attention (MLA)** (the block itself is ``models/mla.py``'s,
  shared with ``models/deepseek_v3.py``; this model turns its two factors
  on the latents on).  Queries go through a rank-``q_lora_rank``
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
  prefill keeps the plain path, float32 scores a group of
  ``mla._HEAD_GROUP`` heads at a time (``prefill_attention_path``): a
  prefix hit, because the prefix's rows come padded to a bucket of blocks
  and the mask that hides the pad is not causal, which is the only mask
  the kernel builds; a short prompt and the CPU, by
  ``dot_product_attention``'s own rule.
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
from ray_tpu.models import mla
from ray_tpu.models.mla import (absorbed_pair, gather_latent_prefix,
                                init_latent_pool)
from ray_tpu.models.paged_generation import (decode_attention_path,
                                             embed_tokens, sample_next)
from ray_tpu.ops.attention import attention_impl
from ray_tpu.ops.experts import held_experts_ffn, route_top_k
from ray_tpu.ops.layers import rms_norm, rope_frequencies, swiglu

__all__ = ["LongcatConfig", "absorbed_pair", "gather_latent_prefix",
           "init_latent_pool", "latent_decode_sample", "latent_decode_step",
           "latent_prefill_suffix", "longcat_apply", "longcat_init",
           "prefill_attention_path"]


@dataclasses.dataclass(frozen=True)
class LongcatConfig(mla.LatentWidths):
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
    def attention_blocks(self) -> int:
        """What the latent pool stacks: two blocks a double layer."""
        return 2 * self.num_layers

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

    An attention block (``mla.init_block``) holds ``kv_b_proj`` as THREE
    leaves: the published ``w_kvb`` and its derived pair ``w_uk`` /
    ``w_uv``, 134 MB over the serving cell's eight blocks (1.3% of its
    weights)."""
    L, H = cfg.num_layers, cfg.hidden_size
    F, Fe, E = cfg.ffn_dim, cfg.expert_ffn_dim, cfg.num_held
    N = cfg.num_experts + cfg.zero_experts
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 2 + 20 * L))

    def w(*shape):
        return jax.random.normal(next(keys), shape, pd) * 0.02

    def ones(*shape):
        return jnp.ones(shape, pd)

    def attention():
        return mla.init_block(w, ones, cfg)

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

# the latent-attention block is ``models/mla.py``'s, shared with
# ``models/deepseek_v3.py``; the decode step reaches its absorbed form
# through this name
_mla_absorbed = mla.absorbed


def prefill_attention_path(seq: int, prefix: int, impl: str = "auto") -> str:
    """``mla.prefill_attention_path`` under this module's
    ``attention_impl``."""
    return mla.prefill_attention_path(seq, prefix, impl, attention_impl)


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
    path = prefill_attention_path(s, 0)

    def attend(xn, ap):
        return mla.plain(*mla.project(xn, ap, cfg, cos, sin, None), mask,
                         ap, cfg, path)

    x, stats = _layers(params, embed_tokens(params, tokens, cfg.dtype),
                       cfg, attend, live)
    logits = _lm_head(params, cfg, x)
    return (logits, stats) if return_stats else logits


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
    cos, sin = rope_frequencies(cfg.qk_rope_head_dim, P + S, cfg.rope_theta)
    attend = mla.SuffixAttend(
        pool, cfg, cos, sin, S, length, start_pos, prefix_ckv, prefix_kpe,
        prefix_len, dst_blocks, dst_offsets,
        prefill_attention_path(S, P, attn_impl))
    x, stats = _layers(params, embed_tokens(params, tokens, cfg.dtype),
                       cfg, attend, attend.live)
    logits = _lm_head(params, cfg, x)
    with tracing.scope("head"):
        last = jnp.take_along_axis(
            logits, (length - 1)[None, None, None].astype(jnp.int32),
            axis=1)[:, 0]
    return last, {"kv": attend.kv}, stats


def latent_decode_step(params, token, cur_len, block_tables, pool,
                       cfg: LongcatConfig, attn: str | None = None):
    """One token for every slot against block-table caches of latent rows:
    the contract of ``paged_generation.paged_decode_step``.  Returns
    ``(logits [b, vocab], pool, stats int32[3])``; a slot whose table row
    is all scratch holds no request: it attends over nothing, is routed to
    no expert and is not counted."""
    if attn is None:
        attn = decode_attention_path(pool)
    ML = block_tables.shape[1] * pool["kv"].shape[2]
    with tracing.scope("attn.proj"):  # the rotary table
        cos, sin = rope_frequencies(cfg.qk_rope_head_dim, ML,
                                    cfg.rope_theta)
    attend = mla.StepAttend(pool, cfg, cos, sin, cur_len, block_tables,
                            attn, _mla_absorbed)
    x, stats = _layers(params,
                       embed_tokens(params, token, cfg.dtype)[:, None], cfg,
                       attend, attend.live[:, None])
    return _lm_head(params, cfg, x)[:, 0], {"kv": attend.kv}, stats


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
