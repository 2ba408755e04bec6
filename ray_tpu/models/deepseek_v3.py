"""DeepSeek-V3's family (``model_type`` ``deepseek_v3``: DeepSeek-V3 / R1,
GigaChat3.1-702B-A36B, ...), pure-functional JAX, as ``LLMEngine`` serves
it.

Written from the published configuration (``config.json`` of
``ai-sage/GigaChat3.1-702B-A36B``) and the family's published modelling
code.  What sets it apart from the other served models:

* **Latent attention** at values wider than the nope half (``v_head_dim``
  192 beside ``qk_nope_head_dim`` 128), with no factor on the latents: the
  block is ``models/mla.py``'s, shared with ``models/longcat.py``.  The
  cache holds ``[c_kv | k_pe]``, one row a token and layer.
* **YaRN** (``rope_scaling``): the rotary table blends each pair's
  frequency towards ``1 / rope_factor`` of itself
  (``ops/layers.yarn_rope_frequencies``) and the softmax scale carries the
  temperature squared, ``(nope + rope)^-0.5 * yarn_mscale(factor,
  mscale_all_dim)^2``.  Both act at every position.
* **The router** (``ops/experts.route_top_k``): sigmoid scores over all
  ``num_experts`` outputs, an additive selection bias that picks and does
  not weigh, picks limited to the ``topk_group`` best of ``n_group`` groups
  of consecutive experts, the picked weights renormalised and times
  ``routed_scaling_factor``.
* **The expert layer**: ``sum_picks w_e SwiGLU_e(x) + SwiGLU_shared(x)``.
  The model is told which routed experts it **holds** (``first_expert``,
  ``held_experts``: a chip's share under expert parallelism): it routes
  over all of them, computes its own experts' part (``ops/experts.py``) and
  the shared expert in full (every token's own chip computes that, so it
  is counted once).  What absent experts would add is not computed and
  nothing stands in for it.
* **A stack that is not one repeated period**: ``dense_layers`` leading
  layers whose FFN is a dense SwiGLU ``ffn_dim`` wide, then expert layers;
  pre-norm residual blocks ``h += MLA(norm(h)); h += FFN(norm(h))``.

Not here: the multi-token-prediction module (``num_nextn_predict_layers``).
The logits do not depend on it, and the engine's step yields one token a
slot.

The paged latent pool is ``{"kv": [L, NB, bs, W]}`` (``models/mla.py``).
The rotary pairs are held de-interleaved, as LongCat's (``models/mla.py``).
The selection bias is a parameter (zeros at init).  A group that is not
kept has its choices set to ``-inf`` (the family's own inference code; the
Hugging Face port writes 0.0: the same picks wherever ``sigmoid + bias >
0``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu._private import tracing
from ray_tpu.models import mla
from ray_tpu.models.mla import (gather_latent_prefix, init_latent_pool,
                                prefill_attention_path)
from ray_tpu.models.paged_generation import (decode_attention_path,
                                             embed_tokens, sample_next)
from ray_tpu.ops.experts import held_experts_ffn, route_top_k
from ray_tpu.ops.layers import rms_norm, swiglu

__all__ = ["DeepseekV3Config", "decode_sample", "decode_step",
           "deepseek_v3_apply", "deepseek_v3_init", "gather_latent_prefix",
           "init_latent_pool", "prefill_attention_path", "prefill_suffix"]

# what the programs return beside the rest, in this order
COUNTERS = ("moe_pairs_held", "moe_experts_hit", "moe_zero_picks",
            "moe_group_tokens")


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config(mla.YarnLatentWidths):
    """GigaChat3.1-702B-A36B's published values."""
    vocab_size: int = 128256
    hidden_size: int = 7168
    num_layers: int = 64            # the leading dense layers among them
    dense_layers: int = 3           # first_k_dense_replace
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 192
    ffn_dim: int = 18432            # a leading layer's dense SwiGLU
    expert_ffn_dim: int = 2048
    num_experts: int = 256          # routed experts the router knows
    shared_experts: int = 1         # one SwiGLU, this many experts wide
    experts_per_token: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    # the routed experts held here: ``held_experts`` of them from
    # ``first_expert`` on (None: all of them)
    first_expert: int = 0
    held_experts: Optional[int] = None
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e5
    # rope_scaling (YaRN); at a factor of 1 the table is the plain one
    rope_factor: float = 64.0
    rope_original_max_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @property
    def num_held(self) -> int:
        return self.num_experts if self.held_experts is None \
            else self.held_experts

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.dense_layers

    @property
    def attention_blocks(self) -> int:
        """What the latent pool stacks: one block a layer."""
        return self.num_layers

    @property
    def held_groups(self) -> tuple[bool, ...]:
        """Of the router's ``n_group`` groups, those a held expert lies
        in."""
        size = self.num_experts // self.n_group
        last = self.first_expert + self.num_held - 1
        return tuple(self.first_expert // size <= g <= last // size
                     for g in range(self.n_group))

    @staticmethod
    def tiny(**kw) -> "DeepseekV3Config":
        """Test-scale model (CPU, float32): one dense layer, two expert
        layers, 16 experts in 4 groups of which 2 stay."""
        defaults = dict(
            vocab_size=256, hidden_size=64, num_layers=3, dense_layers=1,
            num_heads=4, q_lora_rank=32, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
            ffn_dim=128, expert_ffn_dim=32, num_experts=16,
            experts_per_token=3, n_group=4, topk_group=2, max_seq_len=128,
            rope_theta=1e4, rope_factor=4.0, rope_original_max_len=32,
            dtype=jnp.float32, param_dtype=jnp.float32)
        defaults.update(kw)
        return DeepseekV3Config(**defaults)


# ------------------------------------------------------------------ params

@functools.partial(jax.jit, static_argnames=("cfg",))
def deepseek_v3_init(key: jax.Array, cfg: DeepseekV3Config) -> Dict[str, Any]:
    """Seeded parameters.  ``layers`` is a list of L layers, every weight a
    leaf of its own and ONE program (``models/longcat.py``'s
    ``longcat_init`` says why): ``{"attn": mla.init_block's, "ffn": {norm,
    w_gate, w_up, w_down}}`` for a leading dense layer, ``{"attn", "moe":
    {norm, router: {w [H, N], bias [N]}, experts: {w_gate / w_up [E, H, F],
    w_down [E, F, H]}, shared: {w_gate, w_up, w_down}}}`` for an expert
    layer."""
    L, H = cfg.num_layers, cfg.hidden_size
    Fe, E, N = cfg.expert_ffn_dim, cfg.num_held, cfg.num_experts
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 2 + 12 * L))

    def w(*shape):
        return jax.random.normal(next(keys), shape, pd) * 0.02

    def ones(*shape):
        return jnp.ones(shape, pd)

    def mlp(F):
        return {"w_gate": w(H, F), "w_up": w(H, F), "w_down": w(F, H)}

    def layer(i):
        attn = mla.init_block(w, ones, cfg)
        if i < cfg.dense_layers:
            return {"attn": attn, "ffn": {"norm": ones(H),
                                          **mlp(cfg.ffn_dim)}}
        return {"attn": attn, "moe": {
            "norm": ones(H),
            "router": {"w": w(H, N), "bias": jnp.zeros((N,), jnp.float32)},
            "experts": {"w_gate": w(E, H, Fe), "w_up": w(E, H, Fe),
                        "w_down": w(E, Fe, H)},
            "shared": mlp(Fe * cfg.shared_experts)}}

    return {"embed": w(cfg.vocab_size, H),
            "layers": [layer(i) for i in range(L)],
            "final_norm": ones(H),
            "lm_head": w(H, cfg.vocab_size)}


# ------------------------------------------------------------------ blocks

_rope_table = mla.yarn_rope_table


def _mlp(x, fp, cfg: DeepseekV3Config):
    dt = cfg.dtype
    act = swiglu(x @ fp["w_gate"].astype(dt), x @ fp["w_up"].astype(dt))
    return act @ fp["w_down"].astype(dt)


def _moe(y, mp, cfg: DeepseekV3Config, live):
    """The expert layer.  y ``[b, s, H]`` (normed), live ``[b, s]`` bool;
    ``mp`` the layer's ``moe`` leaves.  Returns (``[b, s, H]``, int32
    ``[4]``: pairs on held experts, held experts hit, 0 (there is no
    zero-compute expert to pick), tokens one of whose kept groups holds
    experts of this chip; of live tokens only)."""
    b, s, H = y.shape
    yf, lf = y.reshape(b * s, H), live.reshape(b * s)
    router, ep = mp["router"], mp["experts"]
    with tracing.scope("router"):
        idx, weight, kept = route_top_k(
            yf, router["w"], router["bias"], cfg.experts_per_token,
            cfg.routed_scaling_factor, renormalise=cfg.norm_topk_prob,
            score="sigmoid", groups=(cfg.n_group, cfg.topk_group))
        here = jnp.any(kept & jnp.asarray(cfg.held_groups), axis=-1)
        group_tokens = jnp.sum(here & lf, dtype=jnp.int32)
    with tracing.scope("experts"):
        out, pairs, hit = held_experts_ffn(
            yf, idx, weight, ep["w_gate"], ep["w_up"], ep["w_down"],
            first=cfg.first_expert, live=lf)
        with tracing.scope("experts.shared"):  # every token's own chip
            out = out + _mlp(yf, mp["shared"], cfg).astype(jnp.float32)
        zero = jnp.zeros((), jnp.int32)
        return (out.astype(cfg.dtype).reshape(b, s, H),
                jnp.stack([pairs, hit, zero, group_tokens]).astype(jnp.int32))


def _layers(params, x, cfg: DeepseekV3Config, attend, live):
    """``attend(x_normed, ap) -> [b, s, H]``: an attention block's output
    (projection, cache and ``W_o`` are the caller's), called layer by
    layer."""
    eps = cfg.rms_norm_eps
    stats = jnp.zeros(len(COUNTERS), jnp.int32)
    for lp in params["layers"]:
        ap = lp["attn"]
        with tracing.scope("attn.proj"):
            xn = rms_norm(x, ap["norm"], eps)
        o = attend(xn, ap)  # opens attn.cache / .core / .out itself
        with tracing.scope("attn.out"):
            x = x + o
        if "ffn" in lp:  # a leading dense layer
            with tracing.scope("ffn"):
                fp = lp["ffn"]
                x = x + _mlp(rms_norm(x, fp["norm"], eps), fp, cfg)
            continue
        mp = lp["moe"]
        with tracing.scope("experts"):
            y = rms_norm(x, mp["norm"], eps)
        out, st = _moe(y, mp, cfg, live)
        with tracing.scope("experts"):
            x = x + out
        stats += st
    return x, stats


def _lm_head(params, cfg: DeepseekV3Config, x):
    with tracing.scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return jnp.einsum("bsh,hv->bsv", x,
                          params["lm_head"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- programs

def deepseek_v3_apply(params, tokens, cfg: DeepseekV3Config, *, mesh=None,
                      return_stats: bool = False):
    """tokens ``[b, s]`` -> logits ``[b, s, vocab]`` float32: the plain
    causal forward, no cache (tests, and what a trainer would start from)."""
    if mesh is not None:
        raise NotImplementedError("DeepSeek-V3 has no sharded forward yet")
    b, s = tokens.shape
    cos, sin = _rope_table(cfg, s)
    mask = jnp.broadcast_to(
        jnp.arange(s)[None, :, None] >= jnp.arange(s)[None, None, :],
        (b, s, s))
    path = prefill_attention_path(s, 0)

    def attend(xn, ap):
        return mla.plain(*mla.project(xn, ap, cfg, cos, sin, None), mask,
                         ap, cfg, path)

    x, stats = _layers(params, embed_tokens(params, tokens, cfg.dtype),
                       cfg, attend, jnp.ones((b, s), bool))
    logits = _lm_head(params, cfg, x)
    return (logits, stats) if return_stats else logits


def prefill_suffix(params, tokens, length, start_pos, prefix_ckv,
                   prefix_kpe, prefix_len, dst_blocks, dst_offsets, pool,
                   cfg: DeepseekV3Config, attn_impl: str = "auto"):
    """b=1 prefill of a prompt *suffix* against a cached prefix: the
    contract of ``paged_generation.prefill_suffix`` with latent rows for
    keys and values (``gather_latent_prefix``'s pair), as
    ``longcat.latent_prefill_suffix``.  Returns ``(logits_at_last [1,
    vocab], pool, stats int32[4])``."""
    _, S = tokens.shape
    P = prefix_ckv.shape[1]
    attend = mla.SuffixAttend(
        pool, cfg, *_rope_table(cfg, P + S), S, length, start_pos,
        prefix_ckv, prefix_kpe, prefix_len, dst_blocks, dst_offsets,
        prefill_attention_path(S, P, attn_impl))
    x, stats = _layers(params, embed_tokens(params, tokens, cfg.dtype),
                       cfg, attend, attend.live)
    # the head for the last true position only
    with tracing.scope("head"):
        last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    return _lm_head(params, cfg, last)[:, 0], {"kv": attend.kv}, stats


def decode_step(params, token, cur_len, block_tables, pool,
                cfg: DeepseekV3Config, attn: str | None = None):
    """One token for every slot against block-table caches of latent rows:
    the contract of ``paged_generation.paged_decode_step``.  Returns
    ``(logits [b, vocab], pool, stats int32[4])``; a slot whose table row
    is all scratch holds no request: it attends over nothing, is routed to
    no expert and is not counted."""
    if attn is None:
        attn = decode_attention_path(pool)
    ML = block_tables.shape[1] * pool["kv"].shape[2]
    with tracing.scope("attn.proj"):  # the rotary table
        cos, sin = _rope_table(cfg, ML)
    attend = mla.StepAttend(pool, cfg, cos, sin, cur_len, block_tables,
                            attn)
    x, stats = _layers(params,
                       embed_tokens(params, token, cfg.dtype)[:, None], cfg,
                       attend, attend.live[:, None])
    return _lm_head(params, cfg, x)[:, 0], {"kv": attend.kv}, stats


def decode_sample(params, token, cur_len, block_tables, pool, key, temps,
                  cfg: DeepseekV3Config, attn: str | None = None):
    """``paged_generation.paged_decode_sample``'s contract (on-device
    sampling, every output the next step needs a device array), plus the
    step's four expert counters."""
    ML = block_tables.shape[1] * pool["kv"].shape[2]
    safe_cur = jnp.minimum(cur_len, ML - 1)
    logits, pool, stats = decode_step(
        params, token, safe_cur, block_tables, pool, cfg=cfg, attn=attn)
    nxt, key = sample_next(logits, key, temps)
    return nxt, cur_len + 1, key, pool, stats
