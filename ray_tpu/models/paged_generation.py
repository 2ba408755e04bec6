"""Paged KV cache ops: block-table attention for the LLM engine.

Reference capability: ``ray.llm`` reaches paged attention + automatic
prefix caching through vLLM
(``python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_models.py:123-127``).
TPU-native redesign of the same ideas:

* The KV cache is a global **block pool** ``[L, num_blocks, block_size,
  KVH, hd]``; a sequence's cache is a **block table** (int32 indices into
  the pool).  Capacity is blocks, not slots×max_len — short requests stop
  reserving worst-case memory, and identical prompt prefixes share blocks.
* All shapes are static — one compiled decode program forever — and the
  decode step's attention takes one of two paths, chosen by
  ``decode_attention_path`` from what it can see (the pool's keys, a
  mesh, the backend), never by a knob:

  - ``"paged_kernel"`` (dense pool, one query token, one device, TPU):
    ``ops/pallas/paged_attention.py`` reads each slot's *live* blocks in
    place out of the stacked pool, by block table and length; a slot
    whose table row is all scratch (a freed slot) reads nothing.  What a
    step moves follows the tokens held, not the tables' capacity.
  - ``"gather"`` (int8 pool, an engine with a mesh, any other
    backend): gather every slot's whole table
    (``[b, MB·bs]`` keys, MB = max_len/block_size) and mask by
    ``cur_len``.  XLA-friendly, sharding-transparent, and the plain
    reference the kernel is tested against.

  Block 0 is a reserved scratch block: table padding and masked scatter
  lanes land there, so no write needs a branch.
* Prefix-cached prefill runs per request (b=1): the cached prefix KV is
  gathered from the pool, only the suffix runs through the layers (RoPE
  offset by ``start_pos``), and the suffix KV is scattered back into
  freshly allocated blocks.

The block manager / prefix hash-chain lives in ``llm/engine.py`` (host
side, pure numpy); this module is only the jittable math, and what that
math is made of: the cached decoder layer (``_layer_with_cache``, its two
masked attentions, ``_stacked_layers``) and ``SamplingParams``.  The
dense-cache reference (``generation.py``, beside this file) takes them
from here; nothing here or under ``llm/`` imports that file.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu._private import tracing
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.attention import sliding_window_mask
from ray_tpu.ops.layers import (apply_rope, heads_projection, rms_norm,
                                rope_frequencies, swiglu)


def init_kv_pool(cfg: LlamaConfig, num_blocks: int, block_size: int,
                 kv_dtype: str | None = None):
    """Block pool; block 0 is the reserved scratch block.

    ``kv_dtype="int8"`` stores KV as symmetric per-(token, kv-head) int8
    with bf16 scales: ~half the pool HBM of bf16, so ~2x the concurrent
    sequences fit next to the weights on one chip (decode throughput on a
    weight-bandwidth-bound chip scales with batch).  Matches the intent of
    vLLM's ``kv_cache_dtype`` (the reference's engine flag) TPU-natively:
    quantize/dequantize fuse into the scatter/gather, no custom kernel.
    """
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, num_blocks, block_size, cfg.num_kv_heads, hd)
    if kv_dtype == "int8":
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1], jnp.bfloat16),
                "v_scale": jnp.zeros(shape[:-1], jnp.bfloat16)}
    if kv_dtype not in (None, "auto"):
        raise ValueError(f"kv_dtype must be None/'auto'/'int8', got "
                         f"{kv_dtype!r}")
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def _quantize_kv(x):
    """[..., hd] -> (int8 values, bf16 per-vector scale)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=-1) / 127.0, 1e-8)
    q = jnp.round(x / scale[..., None]).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _store_kv(pool, i, blk, off, k, v):
    """Scatter one layer's new KV at (blk, off), quantizing if the pool
    is int8.  k/v: [n, KVH, hd] (n = batch or suffix length)."""
    if "k_scale" in pool:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        pool["k"] = pool["k"].at[i, blk, off].set(kq)
        pool["v"] = pool["v"].at[i, blk, off].set(vq)
        pool["k_scale"] = pool["k_scale"].at[i, blk, off].set(ks)
        pool["v_scale"] = pool["v_scale"].at[i, blk, off].set(vs)
    else:
        pool["k"] = pool["k"].at[i, blk, off].set(k)
        pool["v"] = pool["v"].at[i, blk, off].set(v)
    return pool


# BLOCK-TABLE CAPACITY (MB*bs == the engine's max_len, in tokens) above
# which the int8 decode path keeps KV quantized through attention
# (scale-folded dots) instead of dequantizing eagerly in the gather.
# Capacity — not the sequences' true lengths — is the right knob: an
# int8 pool always decodes on the "gather" path, which gathers the full
# static table width (the paged kernel has no int8 arm yet), so the
# dequant-materialization cost scales with capacity.  Measured crossover
# on v5e @ 7B: eager wins at max_len 176 (295 vs 230 tok/s — the
# int8-operand dot's mixed-precision path is slower), folded wins at
# max_len 512 (194 vs 160 — the avoided [b, max_len, KVH, hd] dequant
# materialization dominates).
INT8_FOLD_MIN_CONTEXT = 384


def _gather_kv(pool, i, block_tables, dt):
    """Gather one layer's KV for [b, MB] block tables.

    Dense pool -> ``(k, v)`` in dt.  Int8 pool -> eager-dequantized
    ``(k, v)`` below ``INT8_FOLD_MIN_CONTEXT`` tokens of table CAPACITY
    (max_len), still-quantized ``(k_q, ks, v_q, vs)`` above it (consumed
    by the scale-folded attend) — see the crossover note above."""
    k = pool["k"][i, block_tables]
    v = pool["v"][i, block_tables]
    if "k_scale" in pool:
        ks = pool["k_scale"][i, block_tables]
        vs = pool["v_scale"][i, block_tables]
        MB, bs = k.shape[1], k.shape[2]
        if MB * bs >= INT8_FOLD_MIN_CONTEXT:  # static at trace time
            return k, ks, v, vs
        k = k.astype(dt) * ks.astype(dt)[..., None]
        v = v.astype(dt) * vs.astype(dt)[..., None]
    return k, v


def decode_attention_path(pool, *, mesh=None) -> str:
    """Which attention the decode step runs, from what can be seen, never
    from a knob.  Which pool takes which arm of
    ``ops/pallas/paged_attention.py``:

    * a dense pool ``{"k", "v": [L, NB, bs, KVH, hd]}`` on one TPU device
      -> ``"paged_kernel"`` (the dense arm: two pools of equal head
      width, ``hd ** -0.5``);
    * a latent pool ``{"kv": [A, NB, bs, W]}`` (``models/mla.py``: one
      row a token that all heads share) on one TPU device ->
      ``"latent_kernel"`` (the latent arm: one pool, the values its
      leading columns, the caller's scale);
    * ``"gather"`` for an int8 pool (no kernel arm yet), a mesh (the pool
      is sharded over KV heads; the kernel is not under ``shard_map``
      yet) and every backend but TPU
      (``ops/attention.py`` keeps Pallas off the CPU path the same way).

    Mosaic wants a page's rows and the row's width tile-aligned; other
    shapes gather."""
    off_kernel = mesh is not None or jax.default_backend() != "tpu"
    if "kv" in pool:
        bs, width = pool["kv"].shape[2:]
        return ("gather" if off_kernel or width % 128 or bs % 16
                else "latent_kernel")
    bs, kvh, hd = pool["k"].shape[2:]
    if ("k_scale" in pool or off_kernel or hd % 128 or (bs * kvh) % 16):
        return "gather"
    return "paged_kernel"


def _gqa_attend(q, k, v, mask):
    """q [b,sq,H,hd], k/v [b,sk,KVH,hd], mask [b,sq,sk] -> [b,sq,H,hd]."""
    b, sq, H, hd = q.shape
    kvh = k.shape[2]
    group = H // kvh
    q = q.reshape(b, sq, kvh, group, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(hd).astype(logits.dtype)
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, H, hd).astype(q.dtype)


def _gqa_attend_quant(q, k_q, ks, v_q, vs, mask):
    """Int8-KV attention with the scales folded AROUND the matmuls.

    The int8 cache values convert to ``q.dtype`` inside the dots (no
    dequantized ``[b,sk,KVH,hd]`` tensor materializes in HBM) and the
    per-(token, kv-head) scales apply to the ``[.., sq, sk]``-shaped
    scores/probs instead — exact, because the scale is constant along
    the contracted ``hd`` axis: ``q·(k_q·s) == (q·k_q)·s`` and
    ``(p·s)·v_q == p·(v_q·s)``.

    Measured on v5e @ 7B decode: wins at LARGE table capacity (194 vs
    160 tok/s at max_len 512) where the avoided dequant-materialization
    traffic dominates, loses at small capacity (230 vs 295 at max_len
    176) where the int8-operand dot's slower mixed-precision path
    dominates — callers gate on block-table capacity
    (``paged_generation.INT8_FOLD_MIN_CONTEXT``).

    q [b,sq,H,hd]; k_q/v_q [b,sk,KVH,hd] int8; ks/vs [b,sk,KVH];
    mask [b,sq,sk].
    """
    b, sq, H, hd = q.shape
    kvh = k_q.shape[2]
    group = H // kvh
    qg = q.reshape(b, sq, kvh, group, hd)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k_q.astype(q.dtype),
                        preferred_element_type=jnp.float32)
    scale_k = ks.transpose(0, 2, 1)[:, :, None, None, :]  # [b,kvh,1,1,sk]
    logits = logits * scale_k.astype(logits.dtype)
    logits = logits / jnp.sqrt(hd).astype(logits.dtype)
    logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    scale_v = vs.transpose(0, 2, 1)[:, :, None, None, :]
    probs = (probs * scale_v.astype(probs.dtype)).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v_q.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, H, hd).astype(q.dtype)


def _layer_with_cache(x, lp, layer_kv, *, cfg, cos, sin, mask,
                      positions=None, attend=None):
    """One decoder layer reading/returning its kv (cache-enabled twin of
    ``llama._decoder_layer``; same weights, ragged-mask attention).

    ``layer_kv(k, v)`` merges with the cache and returns either
    ``(k_all, v_all)`` (dense) or ``(k_q, ks, v_q, vs)`` (int8 values +
    per-token-head scales — routed through the scale-folded attend).
    ``attend(q, k, v) -> [b, s, H, hd]`` replaces both the merge and the
    masked attention for a caller that never materializes the merged cache
    (the paged decode kernel); ``layer_kv`` and ``mask`` are then unused.

    The layer's parts carry the name scopes of ``docs/observability.md``
    (``attn.proj``, ``attn.cache``, ``attn.core``, ``attn.out``, ``ffn``):
    a profiler trace's device time is cut by them."""
    b, s, h = x.shape
    dt = cfg.dtype
    with tracing.scope("attn.proj"):
        y = rms_norm(x, lp["attn_norm"])
        q = heads_projection(y, lp["wq"].astype(dt), cfg.num_heads)
        k = heads_projection(y, lp["wk"].astype(dt), cfg.num_kv_heads)
        v = heads_projection(y, lp["wv"].astype(dt), cfg.num_kv_heads)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
    if attend is not None:
        attn = attend(q, k, v)  # opens attn.cache and attn.core itself
    else:
        with tracing.scope("attn.cache"):
            merged = layer_kv(k, v)  # merge with cache; full keys/vals
        with tracing.scope("attn.core"):
            if len(merged) == 4:
                attn = _gqa_attend_quant(q, *merged, mask)
            else:
                attn = _gqa_attend(q, merged[0], merged[1], mask)
    with tracing.scope("attn.out"):
        x = x + (attn.reshape(b, s, -1) @ lp["wo"].astype(dt))
    with tracing.scope("ffn"):
        y = rms_norm(x, lp["mlp_norm"])
        act = swiglu(y @ lp["w_gate"].astype(dt), y @ lp["w_up"].astype(dt))
        x = x + act @ lp["w_down"].astype(dt)
    return x, (k, v)


def _stacked_layers(params):
    """Iterate stacked layer params [L, ...] without lax.scan (generation
    caches differ per layer; a python loop keeps it simple and L is static).

    What ``a[i]`` costs in the compiled program: nothing, where a product
    reads it.  XLA:TPU makes the layer's slice of the stacked parameter an
    operand of the product's own fusion (``fusion(%params__layers____wo__,
    ...)``) and streams the weight from where it lies; the seven weights of
    ``_layer_with_cache`` are all read so since ``heads_projection`` keeps
    wq, wk and wv from being transposed first (PR 35; compiled for the v5e
    in ``tests/test_flash_compile_v5e.py``).  It is a copy only for an
    operand of a Mosaic call or under a ``lax.scan`` over the steps
    (``paged_decode_sample``)."""
    L = jax.tree.leaves(params["layers"])[0].shape[0]
    for i in range(L):
        yield i, jax.tree.map(lambda a: a[i], params["layers"])


def _lm_head(params, cfg, x):
    with tracing.scope("head"):
        x = rms_norm(x, params["final_norm"])
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(cfg.dtype)
        return jnp.einsum("bsh,hv->bsv", x, head,
                          preferred_element_type=jnp.float32)


def embed_tokens(params, tokens, dt):
    """The embedding rows of ``tokens`` in ``dt``: every served model's
    first step, under the name scope ``embed``."""
    with tracing.scope("embed"):
        return params["embed"][tokens].astype(dt)


def paged_decode_step(params, token, cur_len, block_tables, pool,
                      cfg: LlamaConfig, attn: str | None = None):
    """One token for every slot against block-table caches.

    token ``[b]`` int32; cur_len ``[b]`` write positions; block_tables
    ``[b, MB]`` int32 pool indices (pad with 0 = scratch).  Returns
    ``(logits [b, vocab], pool)`` with each sequence's new KV written at
    ``block_tables[i, cur_len // bs][cur_len % bs]``.

    ``attn``: the ``decode_attention_path`` the caller resolved (the engine
    knows its mesh); None resolves it here from
    the pool and the backend.  On the kernel path a slot whose first table
    entry is the scratch block holds no request (the engine zeroes a freed
    slot's row): it attends over nothing and its logits are discarded.
    """
    if attn is None:
        attn = decode_attention_path(pool)
    b = token.shape[0]
    MB = block_tables.shape[1]
    bs = pool["k"].shape[2]
    hd = cfg.resolved_head_dim
    dt = cfg.dtype
    with tracing.scope("attn.proj"):  # the rotary table
        cos, sin = rope_frequencies(hd, MB * bs, cfg.rope_theta)
    positions = cur_len[:, None]
    x = embed_tokens(params, token, dt)[:, None]
    # logical position j visible iff j <= cur_len (own slot included)
    idx = jnp.arange(MB * bs)
    mask = idx[None, None, :] <= cur_len[:, None, None]
    if cfg.sliding_window is not None:
        mask &= sliding_window_mask(cur_len[:, None, None],
                                    idx[None, None, :], cfg.sliding_window)
    with tracing.scope("attn.cache"):  # where the step's rows go
        rows = jnp.arange(b)
        blk = block_tables[rows, cur_len // bs]  # [b] target block per seq
        off = cur_len % bs
        # the kernel's view of a slot: positions 0..cur_len, or nothing
        lengths = jnp.where(block_tables[:, 0] != 0, cur_len + 1, 0)

    for i, lp in _stacked_layers(params):
        def merge(k, v, i=i):
            nonlocal pool
            # write new kv first so the token attends to itself
            pool = _store_kv(pool, i, blk, off, k[:, 0], v[:, 0])
            # gather this sequence's blocks in logical order; 2-tuple =
            # dense/dequantized, 4-tuple = quantized + scales (folded
            # attend) — _layer_with_cache dispatches on the arity
            g = _gather_kv(pool, i, block_tables, dt)
            return tuple(a.reshape(b, MB * bs, *a.shape[3:]) for a in g)

        def attend(q, k, v, i=i):
            nonlocal pool
            # imported where it is used, as ops/attention.py does with the
            # flash kernel: a process that never takes the kernel path
            # (every CPU worker, the driver) never loads Pallas
            from ray_tpu.ops.pallas.paged_attention import paged_attention

            with tracing.scope("attn.cache"):
                pool = _store_kv(pool, i, blk, off, k[:, 0], v[:, 0])
            with tracing.scope("attn.core"):
                return paged_attention(
                    q[:, 0], pool["k"], pool["v"], block_tables, lengths,
                    layer=i, window=cfg.sliding_window)[:, None]

        x, _ = _layer_with_cache(
            x, lp, merge, cfg=cfg, cos=cos, sin=sin, mask=mask,
            positions=positions,
            attend=attend if attn == "paged_kernel" else None)
    return _lm_head(params, cfg, x)[:, 0], pool


def prefill_suffix(params, tokens, length, start_pos, prefix_k, prefix_v,
                   prefix_len, dst_blocks, dst_offsets, pool,
                   cfg: LlamaConfig):
    """b=1 prefill of a prompt *suffix* against a cached prefix.

    tokens ``[1, S]`` right-padded suffix; length: true suffix length;
    start_pos: absolute position of tokens[0] (== true prefix length);
    prefix_k/v ``[L, P, KVH, hd]`` gathered prefix (P static bucket,
    ``prefix_len`` true length, 0 for no prefix); dst_blocks/dst_offsets
    ``[S]`` pool coordinates for each suffix position (pad lanes -> the
    scratch block).  Returns ``(logits_at_last [1, vocab], pool)``.
    """
    _, S = tokens.shape
    P = prefix_k.shape[1]
    hd = cfg.resolved_head_dim
    dt = cfg.dtype
    cos, sin = rope_frequencies(hd, P + S, cfg.rope_theta)
    positions = start_pos + jnp.arange(S)[None, :]  # [1, S] absolute
    x = embed_tokens(params, tokens, dt)
    sfx = jnp.arange(S)
    # keys = [prefix (P) | suffix (S)]; query i sees prefix j < prefix_len
    # and suffix j' <= i (within true suffix length)
    pmask = (jnp.arange(P)[None, None, :] < prefix_len)  # [1, 1, P]
    smask = (sfx[None, None, :] <= sfx[None, :, None]) & (
        sfx[None, None, :] < length)  # [1, S, S]
    if cfg.sliding_window is not None:
        W = cfg.sliding_window
        # absolute positions: prefix key j at j, suffix query i at
        # start_pos + i (suffix keys share the start_pos offset, so the
        # suffix-suffix clamp is index arithmetic)
        pmask = pmask & sliding_window_mask(
            positions[:, :, None], jnp.arange(P)[None, None, :], W)
        smask = smask & sliding_window_mask(
            sfx[None, :, None], sfx[None, None, :], W)
    mask = jnp.concatenate(
        [jnp.broadcast_to(pmask, (1, S, P)), smask], axis=-1)

    for i, lp in _stacked_layers(params):
        def merge(k, v, i=i):
            nonlocal pool
            # scatter suffix kv into its blocks (pad lanes hit scratch)
            pool = _store_kv(pool, i, dst_blocks, dst_offsets, k[0], v[0])
            k_all = jnp.concatenate([prefix_k[i][None].astype(k.dtype), k],
                                    axis=1)
            v_all = jnp.concatenate([prefix_v[i][None].astype(v.dtype), v],
                                    axis=1)
            return k_all, v_all

        x, _ = _layer_with_cache(x, lp, merge, cfg=cfg, cos=cos, sin=sin,
                                 mask=mask, positions=positions)
    logits = _lm_head(params, cfg, x)
    with tracing.scope("head"):
        last = jnp.take_along_axis(
            logits, (length - 1)[None, None, None].astype(jnp.int32),
            axis=1)[:, 0]
    return last, pool


def paged_decode_sample(params, token, cur_len, block_tables, pool, key,
                        temps, cfg: LlamaConfig, attn: str | None = None):
    """One decode step with ON-DEVICE sampling, shaped for host-free
    chaining: every output the next step needs (token, position, PRNG key)
    is returned as a device array, so the engine can dispatch K steps
    back-to-back and fetch the sampled tokens ONCE per window.

    Why not fuse the K steps into one ``lax.scan`` program: under a scan
    the per-layer weight slices of the stacked params materialize as HLO
    temps (~weights-sized extra HBM), which OOMs a 7B model on one 16 GB
    chip.  In this single-step program a layer's slice costs nothing: each
    of its seven products reads its weight in place out of the stacked
    parameter (5 MB of temporaries a step at 22 layers of Mistral-7B's
    widths, compiled for the v5e; 278 MB, and the q/k/v weights sliced and
    transposed every token, before ``ops/layers.py:heads_projection``).
    Chained single-step dispatch keeps memory at single-step level
    while still amortizing the host↔device round trip (per-token host
    sampling pays one sync per step regardless of model speed).

    Sampling: greedy for temp<=0, else categorical at the slot's
    temperature.  Finished slots clamp their writes to the last position
    (the host discards their tokens).
    """
    ML = block_tables.shape[1] * pool["k"].shape[2]
    safe_cur = jnp.minimum(cur_len, ML - 1)
    logits, pool = paged_decode_step(params, token, safe_cur, block_tables,
                                     pool, cfg=cfg, attn=attn)
    nxt, key = sample_next(logits, key, temps)
    return nxt, cur_len + 1, key, pool


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 1.0
    top_k: int = 0  # 0 = disabled
    top_p: float = 1.0  # 1.0 = disabled
    max_tokens: int = 64
    stop_token_id: Optional[int] = None


def sample_next(logits, key, temps):
    """What every model's ``decode_sample`` ends in: the key split, one
    half spent on ``sample_token_batch``.  Returns (token, the key to
    carry)."""
    with tracing.scope("sample"):
        key, sub = jax.random.split(key)
        return sample_token_batch(logits, sub, temps), key


def sample_token_batch(logits, key, temps):
    """Per-slot temperature sampling: greedy for temp<=0, categorical
    otherwise.  The ONE sampler for both the decode window and batched
    admission first-tokens (``LLMEngine._sample``)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t = jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.random.categorical(key, logits / t).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


def gather_prefix(pool, blocks):
    """Gather ``[L, P·bs, KVH, hd]`` prefix KV for a block list ``[P]``,
    dequantized to bf16 when the pool is int8."""
    L, _, bs = pool["k"].shape[:3]
    P = blocks.shape[0]
    k = pool["k"][:, blocks]
    v = pool["v"][:, blocks]
    if "k_scale" in pool:
        k = k.astype(jnp.bfloat16) * pool["k_scale"][:, blocks].astype(
            jnp.bfloat16)[..., None]
        v = v.astype(jnp.bfloat16) * pool["v_scale"][:, blocks].astype(
            jnp.bfloat16)[..., None]
    k = k.reshape(L, P * bs, *pool["k"].shape[3:])
    v = v.reshape(L, P * bs, *pool["v"].shape[3:])
    return k, v


