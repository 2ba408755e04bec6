"""Llama-family decoder-only transformer, pure-functional JAX.

TPU-first choices:
- params are a plain pytree + a parallel *spec tree* of logical axis names
  (mapped to mesh axes by ``ray_tpu.parallel.sharding``) — DP/FSDP/TP/SP are
  rule-table changes, not model changes;
- layers are stacked and iterated with ``lax.scan`` (one trace, O(1) compile
  time in depth) with per-layer ``jax.checkpoint`` rematerialisation;
- bf16 activations / fp32 master params; all matmuls hit the MXU with fp32
  accumulation (``preferred_element_type``);
- attention dispatches through ``ray_tpu.ops`` (Pallas flash on-chip, ring
  attention when the mesh shards sequence).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu._private import tracing
from ray_tpu.ops.attention import dot_product_attention
from ray_tpu.ops.head_loss import head_loss, head_loss_chunks
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies, swiglu


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    mlp_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # "full": recompute everything in bwd (min memory);
    # "save_attn": keep attention outputs (skips flash-kernel recompute —
    # ~64 MB/layer at b8/s2048/h1024, usually the right trade on TPU).
    remat_policy: str = "save_attn"
    scan_layers: bool = True
    attention_impl: str = "auto"
    # sliding-window (Mistral/Qwen2-style) causal attention: query p
    # attends keys in (p - sliding_window, p].  None = full causal.
    sliding_window: Optional[int] = None
    tie_embeddings: bool = False
    # Microbatches for pipeline parallelism (mesh "pp" axis); default 2*pp.
    pp_microbatches: Optional[int] = None

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    # --- presets -----------------------------------------------------------
    @staticmethod
    def llama2_7b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama2_13b() -> "LlamaConfig":
        return LlamaConfig(
            hidden_size=5120, num_layers=40, num_heads=40, num_kv_heads=40,
            mlp_dim=13824,
        )

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128256, hidden_size=4096, num_layers=32, num_heads=32,
            num_kv_heads=8, mlp_dim=14336, max_seq_len=8192,
            rope_theta=500000.0,
        )

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Test-scale model (runs on CPU mesh in <1s)."""
        defaults = dict(
            vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
            num_kv_heads=2, mlp_dim=128, max_seq_len=128,
            dtype=jnp.float32, param_dtype=jnp.float32,
        )
        defaults.update(kw)
        return LlamaConfig(**defaults)

    def num_params(self) -> int:
        hd = self.resolved_head_dim
        per_layer = (
            self.hidden_size * (self.num_heads * hd)          # wq
            + 2 * self.hidden_size * (self.num_kv_heads * hd)  # wk, wv
            + (self.num_heads * hd) * self.hidden_size         # wo
            + 3 * self.hidden_size * self.mlp_dim              # gate/up/down
            + 2 * self.hidden_size                             # norms
        )
        embed = self.vocab_size * self.hidden_size
        head = 0 if self.tie_embeddings else embed
        return embed + head + self.num_layers * per_layer + self.hidden_size


def _layer_init(key, cfg: LlamaConfig) -> Dict[str, jnp.ndarray]:
    hd = cfg.resolved_head_dim
    h, q_out, kv_out = cfg.hidden_size, cfg.num_heads * hd, cfg.num_kv_heads * hd
    ks = jax.random.split(key, 7)
    std = 0.02
    init = lambda k, shape: (
        jax.random.normal(k, shape, cfg.param_dtype) * std
    )
    return {
        "attn_norm": jnp.ones((h,), cfg.param_dtype),
        "wq": init(ks[0], (h, q_out)),
        "wk": init(ks[1], (h, kv_out)),
        "wv": init(ks[2], (h, kv_out)),
        "wo": init(ks[3], (q_out, h)),
        "mlp_norm": jnp.ones((h,), cfg.param_dtype),
        "w_gate": init(ks[4], (h, cfg.mlp_dim)),
        "w_up": init(ks[5], (h, cfg.mlp_dim)),
        "w_down": init(ks[6], (cfg.mlp_dim, h)),
    }


def llama_init(key: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    """Initialize the parameter pytree (host or per-device; pure)."""
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    if cfg.scan_layers:
        layers = jax.vmap(lambda k: _layer_init(k, cfg))(layer_keys)
    else:
        layers = [_layer_init(k, cfg) for k in layer_keys]
    params = {
        "embed": jax.random.normal(
            k_embed, (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype
        ) * 0.02,
        "layers": layers,
        "final_norm": jnp.ones((cfg.hidden_size,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            k_head, (cfg.hidden_size, cfg.vocab_size), cfg.param_dtype
        ) * 0.02
    return params


def llama_param_specs(cfg: LlamaConfig) -> Dict[str, Any]:
    """Logical-axis spec tree matching ``llama_init``'s structure."""
    layer = {
        "attn_norm": ("norm",),
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
        "mlp_norm": ("norm",),
        "w_gate": ("embed", "mlp"),
        "w_up": ("embed", "mlp"),
        "w_down": ("mlp", "embed"),
    }
    if cfg.scan_layers:
        layers = {k: ("layers",) + v for k, v in layer.items()}
    else:
        layers = [dict(layer) for _ in range(cfg.num_layers)]
    specs = {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": ("norm",),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ("embed", "vocab")
    return specs


def _constrain(x, mesh, *axes, rules=None):
    if mesh is None:
        return x
    from ray_tpu.parallel.sharding import with_logical_constraint

    return with_logical_constraint(x, mesh, *axes, rules=rules)


def _embed_lookup(params, tokens, cfg: LlamaConfig, *, mesh, rules=None):
    """Embedding gather under layout discipline.

    The gather's OPERANDS are pinned before the gather itself: the
    table keeps its vocab sharding but replicates the model dim (the
    FSDP all-gather every weight pays for compute anyway), and the
    token indices carry the batch/seq layout.  The gather output then
    *is* the canonical activation layout — without the operand pins,
    XLA propagates the table's model-dim sharding into the output and
    the very next activation constraint forces an involuntary full
    rematerialization (``tests/test_sharding_discipline.py`` holds the
    compiled step to zero such warnings).
    """
    if mesh is None:
        return params["embed"][tokens].astype(cfg.dtype)
    table = _constrain(params["embed"], mesh, "vocab", None, rules=rules)
    toks = _constrain(tokens, mesh, "batch", "seq", rules=rules)
    x = table[toks].astype(cfg.dtype)
    return _constrain(x, mesh, "batch", "seq", None, rules=rules)


def _decoder_layer(x, lp, *, cfg: LlamaConfig, cos, sin, mesh, rules=None):
    b, s, h = x.shape
    hd = cfg.resolved_head_dim
    dt = cfg.dtype
    # Attention block; the name scopes are docs/observability.md's parts.
    with tracing.scope("attn.proj"):
        y = rms_norm(x, lp["attn_norm"])
        q = jnp.einsum("bsh,hq->bsq", y, lp["wq"].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
        k = jnp.einsum("bsh,hq->bsq", y, lp["wk"].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
        v = jnp.einsum("bsh,hq->bsq", y, lp["wv"].astype(dt),
                       preferred_element_type=jnp.float32).astype(dt)
        q = q.reshape(b, s, cfg.num_heads, hd)
        k = k.reshape(b, s, cfg.num_kv_heads, hd)
        v = v.reshape(b, s, cfg.num_kv_heads, hd)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        q = _constrain(q, mesh, "batch", "seq", "heads", None, rules=rules)
    with tracing.scope("attn.core"):
        attn = dot_product_attention(
            q, k, v, causal=True, impl=cfg.attention_impl, mesh=mesh,
            window=cfg.sliding_window
        )
        attn = checkpoint_name(attn, "attn_out")
    with tracing.scope("attn.out"):
        attn = attn.reshape(b, s, cfg.num_heads * hd)
        x = x + jnp.einsum("bsq,qh->bsh", attn, lp["wo"].astype(dt),
                           preferred_element_type=jnp.float32).astype(dt)
        x = _constrain(x, mesh, "batch", "seq", None, rules=rules)
    # MLP block.
    with tracing.scope("ffn"):
        y = rms_norm(x, lp["mlp_norm"])
        gate = jnp.einsum("bsh,hm->bsm", y, lp["w_gate"].astype(dt),
                          preferred_element_type=jnp.float32).astype(dt)
        up = jnp.einsum("bsh,hm->bsm", y, lp["w_up"].astype(dt),
                        preferred_element_type=jnp.float32).astype(dt)
        act = checkpoint_name(swiglu(gate, up), "mlp_act")
        x = x + jnp.einsum("bsm,mh->bsh", act, lp["w_down"].astype(dt),
                           preferred_element_type=jnp.float32).astype(dt)
        return _constrain(x, mesh, "batch", "seq", None, rules=rules)


def _llama_hidden(params, tokens, cfg: LlamaConfig, *, mesh, rules):
    """tokens [b, s] → (final-normed hidden states [b, s, h], head [h, v]),
    both in ``cfg.dtype``: all of the forward but the vocabulary product,
    for :func:`llama_apply` and :func:`llama_loss`."""
    s = tokens.shape[1]
    cos, sin = rope_frequencies(cfg.resolved_head_dim, s, cfg.rope_theta)
    with tracing.scope("embed"):
        x = _embed_lookup(params, tokens, cfg, mesh=mesh, rules=rules)

    layer_fn = functools.partial(_decoder_layer, cfg=cfg, cos=cos, sin=sin,
                                 mesh=mesh, rules=rules)
    if cfg.remat:
        if cfg.remat_policy == "save_attn":
            # Also save the flash kernel's residuals (output + lse) so the
            # backward does not replay the forward kernel to regenerate them.
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "flash_out", "flash_lse"
            )
        elif cfg.remat_policy == "save_attn_mlp":
            # save_attn plus the swiglu activation: the backward replays
            # only norms/rope/QKV projections instead of also re-running
            # the gate/up matmuls (2 of the 3 MLP matmuls) — a middle
            # point between save_attn and save_dots,
            # costing b*s*mlp_dim bf16 per layer of extra live memory
            policy = jax.checkpoint_policies.save_only_these_names(
                "attn_out", "flash_out", "flash_lse", "mlp_act"
            )
        elif cfg.remat_policy == "save_dots":
            # Save every matmul output (highest memory of the remat
            # policies, least recompute): the backward replays only the
            # cheap elementwise ops.
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        elif cfg.remat_policy == "full":
            policy = jax.checkpoint_policies.nothing_saveable
        else:
            raise ValueError(
                f"remat_policy must be 'full', 'save_attn', "
                f"'save_attn_mlp' or 'save_dots', "
                f"got {cfg.remat_policy!r}"
            )
        layer_fn = jax.checkpoint(layer_fn, policy=policy)
    from ray_tpu.parallel.pipeline import pipeline_microbatches, pp_size

    n_stages = pp_size(mesh)
    if n_stages > 1:
        # Pipeline path: layers are stage-sharded over "pp"; the
        # microbatch rotate schedule runs in plain GSPMD over a
        # stage-dim-sharded buffer (parallel/pipeline.py).  Per-stage
        # compute carries a leading stage dim under a vmap, which the
        # rank-sensitive constraints and attention impls don't expect,
        # so inside a stage we drop constraints and use an attention
        # impl GSPMD can partition over the remaining axes.
        if not cfg.scan_layers:
            raise ValueError("pp>1 requires scan_layers=True (stacked params)")
        from ray_tpu.parallel.pipeline import pipeline_apply

        if cfg.attention_impl not in ("auto", "ref"):
            raise ValueError(
                f"attention_impl={cfg.attention_impl!r} is incompatible "
                "with pp>1: ring needs its own (nested) shard_map and "
                "pallas flash can't be auto-partitioned under the "
                "pipeline's vmapped stage dim; use 'auto' or 'ref'"
            )
        stage_cfg = dataclasses.replace(cfg, attention_impl="ref")
        stage_fn = functools.partial(
            _decoder_layer, cfg=stage_cfg, cos=cos, sin=sin, mesh=None
        )  # mesh=None: no rank-3 constraints under the vmapped stage dim
        if cfg.remat:
            stage_fn = jax.checkpoint(stage_fn, policy=policy)
        x = pipeline_apply(
            stage_fn, params["layers"], x, mesh=mesh,
            num_microbatches=pipeline_microbatches(cfg.pp_microbatches, mesh),
        )
    elif cfg.scan_layers:
        x, _ = jax.lax.scan(
            lambda carry, lp: (layer_fn(carry, lp), None),
            x,
            params["layers"],
        )
    else:
        for lp in params["layers"]:
            x = layer_fn(x, lp)
    with tracing.scope("head"):
        x = rms_norm(x, params["final_norm"])
        head = (
            params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        ).astype(cfg.dtype)
        return x, head


def llama_apply(
    params: Dict[str, Any],
    tokens: jnp.ndarray,
    cfg: LlamaConfig,
    *,
    mesh=None,
    rules=None,
) -> jnp.ndarray:
    """Forward pass: tokens [b, s] int32 → logits [b, s, vocab] (fp32).

    ``rules`` is the logical-axis rule table the surrounding trainer
    shards params with (None = ``DEFAULT_RULES``): activations are
    constrained through the SAME table, so layouts stay consistent end
    to end — the named-sharding discipline.
    """
    x, head = _llama_hidden(params, tokens, cfg, mesh=mesh, rules=rules)
    with tracing.scope("head"):
        logits = jnp.einsum("bsh,hv->bsv", x, head,
                            preferred_element_type=jnp.float32)
        return _constrain(logits, mesh, "batch", "seq", None, rules=rules)


def _head_chunks(b: int, s: int, cfg: LlamaConfig, mesh, rules) -> int:
    """Sequence chunks of the loss's head, from static shapes: the batch
    rows a device holds decide a chunk's size, and a mesh that shards the
    sequence axis leaves it whole (each device holds a piece already)."""
    if mesh is not None:
        from jax.sharding import NamedSharding

        from ray_tpu.parallel.sharding import logical_to_pspec

        b, held = NamedSharding(mesh, logical_to_pspec(
            ("batch", "seq"), rules, mesh=mesh)).shard_shape((b, s))
        if held < s:
            return 1
    return head_loss_chunks(b, s, cfg.vocab_size)


def llama_loss(
    params: Dict[str, Any],
    batch: Dict[str, jnp.ndarray],
    cfg: LlamaConfig,
    *,
    mesh=None,
    rules=None,
) -> jnp.ndarray:
    """Next-token cross-entropy; batch has 'tokens' [b,s] and optional
    'mask' [b,s] (1 = contribute to loss).

    The head and the loss are one function with its own backward rule
    (``ops/head_loss.py``): no ``[b, s, vocab]`` float32 array is kept,
    or, past ``CHUNK_LOGITS_BYTES``, formed."""
    tokens = batch["tokens"]
    x, head = _llama_hidden(params, tokens[:, :-1], cfg, mesh=mesh,
                            rules=rules)
    targets = tokens[:, 1:]
    with tracing.scope("loss"):
        mask = batch.get("mask")
        if mask is not None:
            mask = mask[:, 1:].astype(jnp.float32)
            weights = mask / jnp.maximum(jnp.sum(mask), 1.0)
        else:
            weights = jnp.full(targets.shape, 1.0 / targets.size,
                               jnp.float32)
    return head_loss(
        x, head, targets, weights,
        _head_chunks(*targets.shape, cfg, mesh, rules),
        lambda a: _constrain(a, mesh, "batch", "seq", None, rules=rules))
