"""Autoregressive generation for the Llama family over a DENSE KV cache:
the plain reference the paged engine's tokens are held to.

Nothing on the serving path imports this file (``llm/`` runs the programs
of ``models/paged_generation.py``, which also holds the cached decoder
layer and ``SamplingParams`` that this file takes from there); tests and
``models/__init__.py`` do.  Its shape:

- static shapes everywhere (cache is [L, b, max_len, kvh, hd]; per-sequence
  lengths are data, not shapes) so prefill and decode each compile once;
- decode writes the new kv slot with a vmapped dynamic_update_slice and
  attends over the full cache under a length mask — no recompilation as
  sequences grow;
- right-padded prompts: per-sequence RoPE positions and cache slots come
  from a ``cur_len`` vector, so ragged batches share one program.
"""

from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.paged_generation import (SamplingParams,
                                              _layer_with_cache,
                                              _stacked_layers)
from ray_tpu.ops.attention import sliding_window_mask
from ray_tpu.ops.layers import rms_norm, rope_frequencies


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int):
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def prefill(params, tokens, lengths, cache, cfg: LlamaConfig):
    """Process right-padded prompts, filling cache[:, :, :S].

    tokens: [b, S] int32; lengths: [b] true prompt lengths.
    Returns (logits_at_last [b, vocab], cache).
    """
    b, S = tokens.shape
    max_len = cache["k"].shape[2]
    cos, sin = rope_frequencies(cfg.resolved_head_dim, S, cfg.rope_theta)
    x = params["embed"][tokens].astype(cfg.dtype)
    # causal AND within true length: key j visible to query i iff j<=i and
    # j < len (padded keys never visible)
    idx = jnp.arange(S)
    mask = (idx[None, None, :] <= idx[None, :, None]) & (
        idx[None, None, :] < lengths[:, None, None])
    if cfg.sliding_window is not None:
        mask &= sliding_window_mask(idx[None, :, None], idx[None, None, :],
                                    cfg.sliding_window)
    new_k = []
    new_v = []
    for i, lp in _stacked_layers(params):
        def merge(k, v):
            return k, v

        x, (k, v) = _layer_with_cache(x, lp, merge, cfg=cfg, cos=cos,
                                      sin=sin, mask=mask)
        new_k.append(k)
        new_v.append(v)
    cache = {
        "k": cache["k"].at[:, :, :S].set(jnp.stack(new_k)),
        "v": cache["v"].at[:, :, :S].set(jnp.stack(new_v)),
    }
    x = rms_norm(x, params["final_norm"])
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cfg.dtype)
    logits = jnp.einsum("bsh,hv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    last = jnp.take_along_axis(
        logits, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    return last, cache


def decode_step(params, token, cur_len, cache, cfg: LlamaConfig):
    """One token per sequence: token [b] int32, cur_len [b] = positions to
    write.  Returns (logits [b, vocab], cache with slot cur_len filled)."""
    b = token.shape[0]
    max_len = cache["k"].shape[2]
    hd = cfg.resolved_head_dim
    # RoPE at each sequence's own position
    cos, sin = rope_frequencies(hd, max_len, cfg.rope_theta)
    positions = cur_len[:, None]  # [b, 1]
    x = params["embed"][token][:, None].astype(cfg.dtype)  # [b, 1, h]
    # key slot j visible iff j <= cur_len (the new token's own slot included)
    idx = jnp.arange(max_len)
    mask = idx[None, None, :] <= cur_len[:, None, None]
    if cfg.sliding_window is not None:
        mask &= sliding_window_mask(cur_len[:, None, None],
                                    idx[None, None, :], cfg.sliding_window)

    write = jax.vmap(
        lambda c, kv, pos: jax.lax.dynamic_update_slice(
            c, kv, (pos, jnp.int32(0), jnp.int32(0))))

    for i, lp in _stacked_layers(params):
        def merge(k, v, i=i):
            ck = write(cache["k"][i], k, cur_len)
            cv = write(cache["v"][i], v, cur_len)
            cache["k"] = cache["k"].at[i].set(ck)
            cache["v"] = cache["v"].at[i].set(cv)
            return ck, cv

        x, _ = _layer_with_cache(x, lp, merge, cfg=cfg, cos=cos, sin=sin,
                                 mask=mask, positions=positions)
    x = rms_norm(x, params["final_norm"])
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cfg.dtype)
    logits = jnp.einsum("bsh,hv->bsv", x, head,
                        preferred_element_type=jnp.float32)
    return logits[:, 0], cache


def sample_token(logits, key, sp: SamplingParams):
    """Greedy when temperature==0, else temperature/top-k/top-p sampling."""
    if sp.temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / sp.temperature
    if sp.top_k and sp.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -sp.top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if sp.top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob >= top_p
        cutoff_idx = jnp.sum(cum < sp.top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None],
                                     axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


def generate(params, cfg: LlamaConfig, prompts: List[List[int]],
             sampling: SamplingParams, *, key=None,
             max_len: Optional[int] = None) -> List[List[int]]:
    """Batched generation; returns new token ids per prompt (no echo).

    Prefill compiles once per padded prompt length bucket; the decode step
    compiles once per (batch, max_len) and is reused for every token.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    b = len(prompts)
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    S = max(len(p) for p in prompts)
    if max_len is None:
        max_len = min(cfg.max_seq_len, S + sampling.max_tokens)
    padded = jnp.asarray(
        [list(p) + [0] * (S - len(p)) for p in prompts], jnp.int32)
    cache = init_kv_cache(cfg, b, max_len)

    prefill_fn = jax.jit(functools.partial(prefill, cfg=cfg))
    decode_fn = jax.jit(functools.partial(decode_step, cfg=cfg))

    logits, cache = prefill_fn(params, padded, lengths, cache)
    cur_len = lengths
    out_tokens = []
    was_done = []  # done state BEFORE each step's token (per sequence)
    done = jnp.zeros((b,), bool)
    for t in range(sampling.max_tokens):
        was_done.append(jax.device_get(done))
        key, k = jax.random.split(key)
        token = sample_token(logits, k, sampling)
        if sampling.stop_token_id is not None:
            done = done | (token == sampling.stop_token_id)
        out_tokens.append(jax.device_get(token))
        # per-sequence capacity stop: one long sequence filling its cache
        # lane must not truncate the others
        done = done | (cur_len >= max_len - 1)
        if bool(done.all()):
            break
        logits, cache = decode_fn(params, token, cur_len, cache)
        cur_len = jnp.where(done, cur_len, cur_len + 1)

    results = []
    for i in range(b):
        seq = []
        for t in range(len(out_tokens)):
            if was_done[t][i]:
                break
            tok = int(out_tokens[t][i])
            if sampling.stop_token_id is not None and tok == sampling.stop_token_id:
                break
            seq.append(tok)
        results.append(seq)
    return results
