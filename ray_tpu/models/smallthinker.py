"""SmallThinker's decoder (PowerInfer, 21B-A3B), pure-functional JAX, as
``LLMEngine`` serves it.

Written from the published configuration (``config.json`` of
``PowerInfer/SmallThinker-21BA3B-Instruct``) and the family's published
modelling code.  What sets it apart from the other served models:

* **Two kinds of layer in one stack.**  ``layer_period`` (published
  ``sliding_window_layout`` = ``rope_layout`` = ``[0, 1, 1, 1] x 13``) says
  which: a ``"full"`` layer attends causally over every earlier position and
  rotates nothing (NoPE); a ``"window"`` layer attends over the last
  ``sliding_window`` positions (``ops.attention.sliding_window_mask``) with
  rotary embedding, pairs ``(i, i + head_dim / 2)``.  GQA, no biases.
* **The router reads the layer's input.**  ``softmax(x W_r)`` of the
  un-normalised residual stream ``x``, before attention (so that the
  experts' weights can be fetched while attention runs); top-k of the
  scores, renormalised over the k picked (``norm_topk_prob``).
* **ReGLU experts, nothing else.**  ``relu(gate) * up``, then down; no
  shared expert, no dense layer.  The model is told which experts it
  **holds** (``first_expert``, ``held_experts``), as ``models/longcat.py``
  is: it routes over all of them and computes its own experts' part
  (``ops/experts.py``).

One layer, ``x`` its input::

    idx, w = top_k(softmax(x W_r)),  w / sum(w)
    h   = x + Attn(RMSNorm(x))
    out = h + sum_j w_j ReGLU_{idx_j}(RMSNorm(h))

**The cache is two pools**, one a layer type (``layer_types``):
``{"full": {"k", "v": [L_full, NB_full, bs, KVH, hd]}, "window": {"k", "v":
[L_window, NB_window, bs, KVH, hd]}}``, and every program takes its block
tables and scatter coordinates by type too.  A window layer never reads a
position more than ``sliding_window`` behind the one it writes, so the
engine gives the window pool's blocks behind that back while a request
decodes, and never allocates them for a long prompt (``llm/engine.py``); a
table entry that has no block is the scratch block, and nothing reads it.
A slot holds a request where its FULL table's first entry is a block.

A prompt is prefilled whole (``ops.attention.dot_product_attention``: on a
TPU the flash kernel, which skips the K blocks before a window): a cached
prefix would need the window type's blocks for the positions before the hit,
which this model does not keep, so ``prefill_suffix`` takes no prefix and
says so.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import tracing
from ray_tpu.models import paged_generation as pg
from ray_tpu.ops.attention import dot_product_attention, sliding_window_mask
from ray_tpu.ops.experts import held_experts_ffn, reglu, route_top_k
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies

FULL, WINDOW = "full", "window"


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_layers: int = 52
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    expert_ffn_dim: int = 768
    num_experts: int = 64           # experts the router knows
    experts_per_token: int = 6
    # the experts held here: ``held_experts`` of them from ``first_expert``
    # on (None: all of them)
    first_expert: int = 0
    held_experts: Optional[int] = None
    sliding_window: int = 4096
    # the layer types of one period, repeated over the depth
    layer_period: Tuple[str, ...] = (FULL, WINDOW, WINDOW, WINDOW)
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    max_seq_len: int = 16384
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        period = tuple(self.layer_period)
        if not set(period) <= {FULL, WINDOW} or FULL not in period:
            raise ValueError(
                f"layer_period {period!r}: '{FULL}' / '{WINDOW}' entries, "
                f"at least one '{FULL}' (a slot is live where its full "
                f"table holds a block)")
        object.__setattr__(self, "layer_period", period)

    @property
    def num_held(self) -> int:
        return self.num_experts if self.held_experts is None \
            else self.held_experts

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """The type of every layer, by depth."""
        p = self.layer_period
        return tuple(p[i % len(p)] for i in range(self.num_layers))

    @staticmethod
    def tiny(**kw) -> "SmallThinkerConfig":
        """Test-scale model (CPU, float32): two periods, a window of two
        blocks of 4."""
        defaults = dict(
            vocab_size=256, hidden_size=64, num_layers=8, num_heads=4,
            num_kv_heads=2, head_dim=16, expert_ffn_dim=32, num_experts=8,
            experts_per_token=3, sliding_window=8, max_seq_len=128,
            rope_theta=1e4, dtype=jnp.float32, param_dtype=jnp.float32)
        defaults.update(kw)
        return SmallThinkerConfig(**defaults)


def layer_types(cfg: SmallThinkerConfig) -> Dict[str, Dict[str, Any]]:
    """What ``LLMEngine`` builds a pool, a block manager and a table for:
    the layers of each type and the window behind which a type's blocks
    are dead.  The type without a window comes first."""
    types = cfg.layer_types
    return {t: {"layers": types.count(t), "window": w}
            for t, w in ((FULL, None), (WINDOW, cfg.sliding_window))
            if t in types}


# ------------------------------------------------------------------ params

@functools.partial(jax.jit, static_argnames=("cfg",))
def smallthinker_init(key: jax.Array, cfg: SmallThinkerConfig
                      ) -> Dict[str, Any]:
    """Seeded parameters.  ``layers`` is a list of L layers, every weight a
    leaf of its own and ONE program (``models/longcat.py``'s
    ``longcat_init`` says why)."""
    L, H = cfg.num_layers, cfg.hidden_size
    nh, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    F, E, N = cfg.expert_ffn_dim, cfg.num_held, cfg.num_experts
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 2 + 8 * L))

    def w(*shape):
        return jax.random.normal(next(keys), shape, pd) * 0.02

    def layer():
        return {"attn": {"norm": jnp.ones((H,), pd), "w_q": w(H, nh * hd),
                         "w_k": w(H, kvh * hd), "w_v": w(H, kvh * hd),
                         "w_o": w(nh * hd, H)},
                "router": {"w": w(H, N)},
                "ffn_norm": jnp.ones((H,), pd),
                "experts": {"w_gate": w(E, H, F), "w_up": w(E, H, F),
                            "w_down": w(E, F, H)}}

    return {"embed": w(cfg.vocab_size, H),
            "layers": [layer() for _ in range(L)],
            "final_norm": jnp.ones((H,), pd),
            "lm_head": w(H, cfg.vocab_size)}


# ------------------------------------------------------------------ blocks

def _layer(x, lp, kind: str, cfg: SmallThinkerConfig, cos, sin, positions,
           attend, live):
    """One layer.  x ``[b, s, H]``; ``attend(q [b, s, nh, hd], k, v
    [b, s, kvh, hd]) -> [b, s, nh, hd]`` (the cache is the caller's); live
    ``[b, s]`` bool.  Returns (out, int32 ``[3]``: pairs on held experts,
    held experts hit, 0: there is no zero-compute expert to pick)."""
    b, s, H = x.shape
    dt, eps = cfg.dtype, cfg.rms_norm_eps
    nh, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ap, ep = lp["attn"], lp["experts"]
    with tracing.scope("router"):  # of the layer's input, as it came
        idx, weight = route_top_k(
            x.reshape(b * s, H), lp["router"]["w"], None,
            cfg.experts_per_token, 1.0, renormalise=True)
    with tracing.scope("attn.proj"):
        xn = rms_norm(x, ap["norm"], eps)
        q = (xn @ ap["w_q"].astype(dt)).reshape(b, s, nh, hd)
        k = (xn @ ap["w_k"].astype(dt)).reshape(b, s, kvh, hd)
        v = (xn @ ap["w_v"].astype(dt)).reshape(b, s, kvh, hd)
        if kind == WINDOW:  # a full layer has no position embedding
            q = apply_rope(q, cos, sin, positions)
            k = apply_rope(k, cos, sin, positions)
    o = attend(q, k, v)  # opens attn.cache and attn.core itself
    with tracing.scope("attn.out"):
        h = x + o.reshape(b, s, nh * hd) @ ap["w_o"].astype(dt)
    with tracing.scope("experts"):
        y = rms_norm(h, lp["ffn_norm"], eps).reshape(b * s, H)
        out, pairs, hit = held_experts_ffn(
            y, idx, weight, ep["w_gate"], ep["w_up"], ep["w_down"],
            first=cfg.first_expert, live=live.reshape(b * s),
            activation=reglu)
        out = h.astype(jnp.float32) + out.reshape(b, s, H)
        zero = jnp.zeros((), jnp.int32)
        return (out.astype(dt),
                jnp.stack([pairs, hit, zero]).astype(jnp.int32))


def _layers(params, x, cfg: SmallThinkerConfig, cos, sin, positions,
            attend, live):
    """``attend(kind, a, q, k, v)``: layer ``a`` of its type."""
    stats = jnp.zeros(3, jnp.int32)
    seen = dict.fromkeys(cfg.layer_period, 0)
    for lp, kind in zip(params["layers"], cfg.layer_types):
        x, st = _layer(x, lp, kind, cfg, cos, sin, positions,
                       functools.partial(attend, kind, seen[kind]), live)
        seen[kind] += 1
        stats += st
    return x, stats


def _window(kind: str, cfg: SmallThinkerConfig) -> Optional[int]:
    return cfg.sliding_window if kind == WINDOW else None


def _lm_head(params, cfg: SmallThinkerConfig, x):
    with tracing.scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return jnp.einsum("bsh,hv->bsv", x,
                          params["lm_head"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- programs

def smallthinker_apply(params, tokens, cfg: SmallThinkerConfig, *,
                       mesh=None, return_stats: bool = False):
    """tokens ``[b, s]`` -> logits ``[b, s, vocab]`` float32: the plain
    causal forward, no cache."""
    if mesh is not None:
        raise NotImplementedError("SmallThinker has no sharded forward yet")
    b, s = tokens.shape
    cos, sin = rope_frequencies(cfg.head_dim, s, cfg.rope_theta)

    def attend(kind, a, q, k, v):
        with tracing.scope("attn.core"):
            return dot_product_attention(q, k, v, causal=True,
                                         window=_window(kind, cfg))

    x, stats = _layers(params, pg.embed_tokens(params, tokens, cfg.dtype),
                       cfg, cos, sin, None, attend, jnp.ones((b, s), bool))
    logits = _lm_head(params, cfg, x)
    return (logits, stats) if return_stats else logits


def init_pools(cfg: SmallThinkerConfig, num_blocks: Dict[str, int],
               block_size: int, kv_dtype: str | None = None):
    """``{type: {"k", "v": [layers of the type, num_blocks[type], bs, KVH,
    hd]}}``; block 0 of each pool is its scratch block."""
    if kv_dtype not in (None, "auto"):
        raise ValueError(
            f"the pools are stored in the model's dtype: kv_dtype "
            f"{kv_dtype!r} is not supported for SmallThinker (None/'auto')")
    return {t: {name: jnp.zeros(
        (spec["layers"], num_blocks[t], block_size, cfg.num_kv_heads,
         cfg.head_dim), cfg.dtype) for name in ("k", "v")}
        for t, spec in layer_types(cfg).items()}


def decode_attention_path(pool, **seen) -> str:
    """``paged_generation.decode_attention_path`` of one of the pools (they
    differ in layers and blocks only)."""
    return pg.decode_attention_path(pool[FULL], **seen)


def gather_prefix(pool, blocks, cfg: SmallThinkerConfig):
    """No prefix is ever cached for this model: the empty pair."""
    if blocks.shape[0]:
        raise NotImplementedError(
            "smallthinker takes no prefix hits: a window layer's blocks "
            "before the hit are not kept (docs/llm_serving.md)")
    empty = jnp.zeros((cfg.num_layers, 0, cfg.num_kv_heads, cfg.head_dim),
                      cfg.dtype)
    return empty, empty


def prefill_suffix(params, tokens, length, start_pos, prefix_k, prefix_v,
                   prefix_len, dst_blocks, dst_offsets, pool,
                   cfg: SmallThinkerConfig):
    """b=1 prefill of a whole prompt: ``paged_generation.prefill_suffix``'s
    contract with an empty prefix (``start_pos`` and ``prefix_len`` 0) and
    ``dst_blocks`` by layer type, ``{type: [S]}``: a window layer's keys
    that no later step can see have the scratch block for theirs.  Returns
    ``(logits_at_last [1, vocab], pools, stats int32[3])``."""
    if prefix_k.shape[1]:
        raise NotImplementedError(
            "smallthinker prefills a prompt whole: no cached prefix "
            "(docs/llm_serving.md)")
    _, S = tokens.shape
    cos, sin = rope_frequencies(cfg.head_dim, S, cfg.rope_theta)
    live = (jnp.arange(S) < length)[None, :]
    pool = {t: dict(p) for t, p in pool.items()}

    def attend(kind, a, q, k, v):
        p = pool[kind]  # pad lanes and unseen keys land in the scratch block
        with tracing.scope("attn.cache"):
            p["k"] = p["k"].at[a, dst_blocks[kind], dst_offsets].set(k[0])
            p["v"] = p["v"].at[a, dst_blocks[kind], dst_offsets].set(v[0])
        # the pad tail lies after every true position: causal hides it
        with tracing.scope("attn.core"):
            return dot_product_attention(q, k, v, causal=True,
                                         window=_window(kind, cfg))

    x, stats = _layers(params, pg.embed_tokens(params, tokens, cfg.dtype),
                       cfg, cos, sin, None, attend, live)
    # the head for the last true position only: [S, vocab] float32 logits
    # of a 14k prompt would be 8.7 GB
    with tracing.scope("head"):
        last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    return _lm_head(params, cfg, last)[:, 0], pool, stats


def decode_step(params, token, cur_len, block_tables, pool,
                cfg: SmallThinkerConfig, attn: str | None = None):
    """One token for every slot: ``paged_generation.paged_decode_step``'s
    contract with ``block_tables`` and ``pool`` by layer type.  Returns
    ``(logits [b, vocab], pools, stats int32[3])``; a slot whose FULL table
    row is all scratch holds no request."""
    if attn is None:
        attn = decode_attention_path(pool)
    b = token.shape[0]
    MB = block_tables[FULL].shape[1]
    bs = pool[FULL]["k"].shape[2]
    dt, hd = cfg.dtype, cfg.head_dim
    kvh, rep = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
    with tracing.scope("attn.proj"):  # the rotary table
        cos, sin = rope_frequencies(hd, MB * bs, cfg.rope_theta)
    positions = cur_len[:, None]
    with tracing.scope("attn.cache"):  # where the step's rows go
        rows = jnp.arange(b)
        off = cur_len % bs
        blk = {t: tab[rows, cur_len // bs]
               for t, tab in block_tables.items()}
        live = block_tables[FULL][:, 0] != 0
        lengths = jnp.where(live, cur_len + 1, 0)
    idx = jnp.arange(MB * bs)
    seen = idx[None, :] <= cur_len[:, None]  # [b, MB * bs]
    pool = {t: dict(p) for t, p in pool.items()}

    def attend(kind, a, q, k, v):
        p, tab = pool[kind], block_tables[kind]
        with tracing.scope("attn.cache"):
            # the new keys and values first: the token attends to itself
            p["k"] = p["k"].at[a, blk[kind], off].set(k[:, 0])
            p["v"] = p["v"].at[a, blk[kind], off].set(v[:, 0])
        with tracing.scope("attn.core"):
            return core(kind, a, q, p, tab)

    def core(kind, a, q, p, tab):
        if attn == "paged_kernel":
            from ray_tpu.ops.pallas.paged_attention import paged_attention

            return paged_attention(
                q[:, 0], p["k"], p["v"], tab, lengths, layer=a,
                window=_window(kind, cfg))[:, None]
        mask = seen
        if kind == WINDOW:
            mask = mask & sliding_window_mask(
                cur_len[:, None], idx[None, :], cfg.sliding_window)
        gk = p["k"][a][tab].reshape(b, MB * bs, kvh, hd)
        gv = p["v"][a][tab].reshape(b, MB * bs, kvh, hd)
        scores = jnp.einsum("bgrd,btgd->bgrt", q.reshape(b, kvh, rep, hd),
                            gk, preferred_element_type=jnp.float32)
        scores = jnp.where(mask[:, None, None], scores * hd ** -0.5, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        return jnp.einsum("bgrt,btgd->bgrd", probs, gv,
                          preferred_element_type=jnp.float32).astype(
                              dt).reshape(b, 1, kvh * rep, hd)

    x, stats = _layers(params,
                       pg.embed_tokens(params, token, cfg.dtype)[:, None],
                       cfg, cos, sin, positions, attend, live[:, None])
    return _lm_head(params, cfg, x)[:, 0], pool, stats


def decode_sample(params, token, cur_len, block_tables, pool, key, temps,
                  cfg: SmallThinkerConfig, attn: str | None = None):
    """``paged_generation.paged_decode_sample``'s contract, plus the step's
    expert counters."""
    ML = block_tables[FULL].shape[1] * pool[FULL]["k"].shape[2]
    safe_cur = jnp.minimum(cur_len, ML - 1)
    logits, pool, stats = decode_step(params, token, safe_cur, block_tables,
                                      pool, cfg=cfg, attn=attn)
    nxt, key = pg.sample_next(logits, key, temps)
    return nxt, cur_len + 1, key, pool, stats
