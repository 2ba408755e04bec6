"""Autoregressive generation for the Llama family: KV cache + sampling.

Reference capability: ``ray.llm`` delegates generation to vLLM
(``python/ray/llm/_internal/serve/deployments/llm/vllm/``); here the engine
is TPU-native jax:

- static shapes everywhere (cache is [L, b, max_len, kvh, hd]; per-sequence
  lengths are data, not shapes) so prefill and decode each compile once;
- decode writes the new kv slot with a vmapped dynamic_update_slice and
  attends over the full cache under a length mask — no recompilation as
  sequences grow;
- right-padded prompts: per-sequence RoPE positions and cache slots come
  from a ``cur_len`` vector, so ragged batches share one program.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import tracing
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.attention import sliding_window_mask  # noqa: F401
from ray_tpu.ops.layers import (apply_rope, heads_projection, rms_norm,
                                rope_frequencies, swiglu)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 1.0
    top_k: int = 0  # 0 = disabled
    top_p: float = 1.0  # 1.0 = disabled
    max_tokens: int = 64
    stop_token_id: Optional[int] = None


def init_kv_cache(cfg: LlamaConfig, batch: int, max_len: int):
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, hd)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


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


def verify_step(params, tokens, cur_len, cache, cfg: LlamaConfig):
    """Speculative-decoding verify: feed K+1 tokens per sequence in ONE
    forward (tokens[:, 0] is the last accepted token, 1..K the draft).

    logits[:, j] predicts the token at position cur_len+j+1, so greedy
    acceptance compares argmax(logits[:, j]) with draft token j+1.  Cache
    slots cur_len..cur_len+K are written; slots past the accepted prefix
    hold draft-conditioned K/V but stay invisible (masks are <= cur_len)
    and are overwritten when those positions are genuinely reached.

    The reference reaches speculative decoding through vLLM; here it is a
    first-class cache op.
    """
    b, kp1 = tokens.shape
    max_len = cache["k"].shape[2]
    hd = cfg.resolved_head_dim
    cos, sin = rope_frequencies(hd, max_len, cfg.rope_theta)
    positions = cur_len[:, None] + jnp.arange(kp1)[None]  # [b, K+1]
    x = params["embed"][tokens].astype(cfg.dtype)
    idx = jnp.arange(max_len)
    # query at global position p sees key slots <= p (its own included)
    mask = idx[None, None, :] <= positions[:, :, None]
    if cfg.sliding_window is not None:
        mask &= sliding_window_mask(positions[:, :, None],
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
    return logits, cache


def _propose_ngram(history: List[int], k: int, ngram: int = 2) -> List[int]:
    """Prompt-lookup drafting (self-speculation, no draft model): find the
    most recent earlier occurrence of the trailing n-gram whose
    continuation is FULL-LENGTH and propose the k tokens that followed
    it; fall back to the longest partial continuation.  (A match
    adjacent to the tail — every periodic sequence has one — truncates
    its continuation at the sequence end, so stopping at the first
    match capped steady-loop workloads at ~1 proposed token.)"""
    n = len(history)
    if n < ngram + 1:
        return []
    tail = history[-ngram:]
    best: List[int] = []
    # search right-to-left, excluding the trailing occurrence itself
    for start in range(n - ngram - 1, -1, -1):
        if history[start:start + ngram] == tail:
            cont = history[start + ngram:start + ngram + k]
            if len(cont) == k:
                return cont
            if len(cont) > len(best):
                best = cont
    return best


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


def _generate_speculative(params, cfg: LlamaConfig, prompts: List[List[int]],
                          sampling: SamplingParams, logits, cache, lengths,
                          max_len: int, K: int, decode_fn) -> List[List[int]]:
    """Greedy prompt-lookup speculative decoding driver.

    Per step: draft up to K tokens per sequence from its own history
    (``_propose_ngram``), verify pending-token + drafts in one jitted
    ``verify_step`` forward, accept the longest greedy-matching draft
    prefix plus the bonus token.  Exactly reproduces greedy ``generate``
    output (the acceptance rule only keeps tokens argmax would have
    produced); steps where no sequence has a draft fall back to
    ``decode_fn``.  All acceptance/stop/budget bookkeeping is host-side;
    the device work is one verify (or decode) program per step.
    """
    b = len(prompts)
    verify_fn = jax.jit(functools.partial(verify_step, cfg=cfg))
    stop = sampling.stop_token_id
    # Greedy emits at most max(1, max_len - prompt_len) tokens before its
    # capacity stop (cur_len >= max_len - 1) fires — the prefill token is
    # always emitted BEFORE the stop is checked; mirror that exactly.
    budget = [min(sampling.max_tokens, max(1, max_len - len(p)))
              for p in prompts]
    histories = [list(p) for p in prompts]
    results: List[List[int]] = [[] for _ in range(b)]
    done = [budget[i] <= 0 for i in range(b)]
    # cur_np[i] = cache slot where sequence i's next token's K/V goes; the
    # last emitted ("pending") token has not been written yet.
    cur_np = [int(x) for x in jax.device_get(lengths)]
    pending = [int(t) for t in jax.device_get(jnp.argmax(logits, -1))]

    def emit(i: int, tok: int) -> bool:
        """Record one accepted token; returns False once i is finished."""
        if stop is not None and tok == stop:
            done[i] = True
            return False
        results[i].append(tok)
        histories[i].append(tok)
        if len(results[i]) >= budget[i]:
            done[i] = True
            return False
        return True

    for i in range(b):
        if not done[i]:
            emit(i, pending[i])

    while not all(done):
        drafts, dlens = [], []
        for i in range(b):
            d = _propose_ngram(histories[i], K) if not done[i] else []
            d = d[:K]
            dlens.append(len(d))
            drafts.append(d + [0] * (K - len(d)))
        cur = jnp.asarray(cur_np, jnp.int32)
        token_col = jnp.asarray(pending, jnp.int32)
        if max(dlens) == 0:
            logits, cache = decode_fn(params, token_col, cur, cache)
            preds = jax.device_get(jnp.argmax(logits, -1))  # [b]
            for i in range(b):
                if done[i]:
                    continue
                cur_np[i] += 1
                tok = int(preds[i])
                if emit(i, tok):
                    pending[i] = tok
            continue
        tokens = jnp.concatenate(
            [token_col[:, None], jnp.asarray(drafts, jnp.int32)], axis=1)
        logits, cache = verify_fn(params, tokens, cur, cache)
        preds = jax.device_get(jnp.argmax(logits, -1))  # [b, K+1]
        for i in range(b):
            if done[i]:
                continue
            a = 0
            while a < dlens[i] and drafts[i][a] == int(preds[i][a]):
                a += 1
            # pending + a accepted drafts now hold valid cache slots
            cur_np[i] += 1 + a
            alive = True
            for tok in drafts[i][:a]:
                if not (alive := emit(i, tok)):
                    break
            if alive:
                bonus = int(preds[i][a])
                if emit(i, bonus):
                    pending[i] = bonus
    return results


def generate(params, cfg: LlamaConfig, prompts: List[List[int]],
             sampling: SamplingParams, *, key=None,
             max_len: Optional[int] = None,
             speculative: int = 0) -> List[List[int]]:
    """Batched generation; returns new token ids per prompt (no echo).

    Prefill compiles once per padded prompt length bucket; the decode step
    compiles once per (batch, max_len) and is reused for every token.

    ``speculative=K`` turns on prompt-lookup speculative decoding (greedy
    only): K draft tokens per step are proposed from each sequence's own
    history and verified in one forward — exact greedy outputs, fewer
    sequential steps when text repeats (code, structured output).
    """
    if speculative > 0 and sampling.temperature != 0.0:
        # fail before any device allocation / compilation happens
        raise ValueError("speculative decoding requires greedy "
                         "sampling (temperature=0)")
    if key is None:
        key = jax.random.PRNGKey(0)
    b = len(prompts)
    lengths = jnp.asarray([len(p) for p in prompts], jnp.int32)
    S = max(len(p) for p in prompts)
    if max_len is None:
        max_len = min(cfg.max_seq_len, S + sampling.max_tokens)
    padded = jnp.asarray(
        [list(p) + [0] * (S - len(p)) for p in prompts], jnp.int32)
    # Speculative verify writes K+1 slots per step; give the cache K+1 slots
    # of slack past the logical max_len so writes never clamp.  The logical
    # stopping rule (emit at most max_len - prompt_len tokens) is enforced
    # host-side in _generate_speculative.
    cache_len = max_len + (speculative + 1 if speculative > 0 else 0)
    cache = init_kv_cache(cfg, b, cache_len)

    prefill_fn = jax.jit(functools.partial(prefill, cfg=cfg))
    decode_fn = jax.jit(functools.partial(decode_step, cfg=cfg))

    logits, cache = prefill_fn(params, padded, lengths, cache)
    if speculative > 0:
        return _generate_speculative(
            params, cfg, prompts, sampling, logits, cache, lengths,
            max_len, speculative, decode_fn)
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
