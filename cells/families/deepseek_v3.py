"""Family ``deepseek_v3``: DeepSeek-V3's family (GigaChat3.1-702B-A36B:
YaRN latent attention at 192-wide values, a group-limited sigmoid router, a
shared expert beside the held routed ones, leading dense layers) through the
program's ``ray_tpu.models.deepseek_v3``, against
``cells/families/deepseek_v3_reference.py``.

Served only: the family supplies no trainer (at 16 bytes a parameter one
expert layer of 8 held experts is already 8.5 GB).  The wrappers add
nothing to the program's own entry points.  The arithmetic is computed from
a configuration's ``model`` group (a plain dict) and imports neither
``ray_tpu`` nor ``jax``.

**The ``model`` group's depth.**  ``num_layers`` is the number of EXPERT
layers (``cells/expert_counters.py`` divides the expert counters by it),
``dense_layers`` the leading dense ones (``first_k_dense_replace``) and
``hidden_layers`` their sum (``num_hidden_layers``); ``config()`` hands the
program the sum.  ``rope_scaling`` is the source's own group.
"""

from cells.families.longcat_flash import (  # latent attention's arithmetic
    LANES, attention_params, expert_params, held, latent_attention_bytes,
    latent_row)
from cells.flops import DTYPE_BYTES

# --rehearse: the same code paths on the CPU in seconds, never a result
TOY_MODEL = {
    "vocab_size": 256, "hidden_size": 64, "hidden_layers": 3,
    "num_layers": 2, "dense_layers": 1, "num_heads": 4, "q_lora_rank": 32,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 24, "ffn_dim": 128, "expert_ffn_dim": 32,
    "num_experts": 32, "shared_experts": 1, "experts_per_token": 4,
    "n_group": 4, "topk_group": 2, "first_expert": 8, "held_experts": 8,
    "rope_theta": 1e4,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32,
                     "rope_type": "yarn"},
    "max_seq_len": 128, "dtype": "float32", "param_dtype": "float32"}

# the source's config.json key -> the ``model`` group's key
SOURCE_KEYS = {
    "hidden_size": "hidden_size", "intermediate_size": "ffn_dim",
    "moe_intermediate_size": "expert_ffn_dim",
    "num_attention_heads": "num_heads", "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "num_experts_per_tok": "experts_per_token",
    "n_shared_experts": "shared_experts", "n_group": "n_group",
    "topk_group": "topk_group",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob", "rope_theta": "rope_theta",
    "rope_scaling": "rope_scaling", "rms_norm_eps": "rms_norm_eps",
    "num_hidden_layers": "hidden_layers",
    "first_k_dense_replace": "dense_layers",
    "n_routed_experts": "held_experts", "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len"}
# the source's keys no configuration may reduce
WIDTHS = frozenset(SOURCE_KEYS) - {
    "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
    "vocab_size", "max_position_embeddings"}

# --------------------------------------------------------------- the program

def config(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models.deepseek_v3 import DeepseekV3Config

    kw = {k: v for k, v in model.items()
          if k not in ("control_dtype", "hidden_layers", "rope_scaling")}
    if model["hidden_layers"] != model["num_layers"] + model["dense_layers"]:
        raise ValueError(
            "hidden_layers is num_layers (the expert layers) + dense_layers")
    kw["num_layers"] = model["hidden_layers"]
    rs = model["rope_scaling"]
    if rs["rope_type"] != "yarn":
        raise ValueError(f"rope_scaling of type {rs['rope_type']!r}")
    kw.update(rope_factor=rs["factor"],
              rope_original_max_len=rs["original_max_position_embeddings"],
              rope_beta_fast=rs["beta_fast"], rope_beta_slow=rs["beta_slow"],
              rope_mscale=rs["mscale"],
              rope_mscale_all_dim=rs["mscale_all_dim"])
    for key in ("dtype", "param_dtype"):
        if key in kw:
            kw[key] = jnp.dtype(kw[key])
    return DeepseekV3Config(**kw)


def model_of(cfg) -> dict:
    """``config``'s inverse: the ``model`` group of a program's
    configuration (what the reference is handed)."""
    import dataclasses

    import numpy as np

    m = dataclasses.asdict(cfg)
    rs = {"rope_type": "yarn",
          "original_max_position_embeddings": m.pop("rope_original_max_len")}
    for name in ("factor", "beta_fast", "beta_slow", "mscale",
                 "mscale_all_dim"):
        rs[name] = m.pop("rope_" + name)
    m.update(rope_scaling=rs, hidden_layers=cfg.num_layers,
             num_layers=cfg.expert_layers)
    for key in ("dtype", "param_dtype"):
        m[key] = np.dtype(m[key]).name
    return m


def init(key, cfg):
    from ray_tpu.models.deepseek_v3 import deepseek_v3_init

    return deepseek_v3_init(key, cfg)


def apply(params, tokens, cfg, mesh):
    from ray_tpu.models.deepseek_v3 import deepseek_v3_apply

    return deepseek_v3_apply(params, tokens, cfg, mesh=mesh)


def serve_programs(cfg, engine: dict, prompt_len: int):
    """For ``tools/compile_for_v5e.py`` only: the engine's decode step, one
    prefill of ``prompt_len`` tokens, the seeded weights' one program and
    the plain reference over ``max_len`` positions as
    ``serve_runner.reference_check`` runs it beside the weights, each as
    (name, function, donated argument numbers, abstract arguments)."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_v3 as ds

    B, bs = engine["batch_slots"], engine["block_size"]
    MB = -(-engine["max_len"] // bs)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(
        functools.partial(ds.deepseek_v3_init, cfg=cfg), key)
    pool = jax.eval_shape(lambda: ds.init_latent_pool(
        cfg, engine.get("num_blocks") or B * MB + 1, bs))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    S = prompt_len
    empty = lambda w: jax.ShapeDtypeStruct(  # noqa: E731
        (cfg.num_layers, 0, w), cfg.dtype)
    ref, model = reference(), model_of(cfg)

    def gaps(params, tokens):  # serve_runner.reference_check's program
        lg = ref.logits(params, tokens[:-1], model)
        chosen = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - chosen

    return [
        ("decode step", functools.partial(
            ds.decode_sample, cfg=cfg, attn="latent_kernel"),
         (4,), (params, i32(B), i32(B), i32(B, MB), pool, key,
                jax.ShapeDtypeStruct((B,), jnp.float32))),
        (f"prefill of {S} tokens",
         functools.partial(ds.prefill_suffix, cfg=cfg),
         (9,), (params, i32(1, S), i32(), i32(), empty(cfg.kv_lora_rank),
                empty(cfg.qk_rope_head_dim), i32(), i32(S), i32(S), pool)),
        ("seeded weights", functools.partial(
            ds.deepseek_v3_init.__wrapped__, cfg=cfg), (), (key,)),
        (f"reference over {engine['max_len']} positions", gaps, (),
         (params, i32(engine["max_len"] + 1)))]


def reference():
    """The plain reference: ``logits``, ``loss`` (contract in its
    docstring)."""
    from cells.families import deepseek_v3_reference

    return deepseek_v3_reference


# ------------------------------------------------------------- arithmetic

def derived_pair_params(m: dict) -> int:
    """``w_uk`` and ``w_uv`` of one block: ``W_kvb`` a second time, laid
    out for the decode step (held in memory, not a parameter of the
    model)."""
    return (m["kv_lora_rank"] * m["num_heads"]
            * (m["qk_nope_head_dim"] + m["v_head_dim"]))


def layers(m: dict) -> int:
    return m["num_layers"] + m["dense_layers"]


def _norms(m: dict) -> int:
    """A layer's: before attention, on the two latents, before the FFN."""
    return 2 * m["hidden_size"] + m["q_lora_rank"] + m["kv_lora_rank"]


def dense_layer_params(m: dict) -> int:
    return (attention_params(m) + 3 * m["hidden_size"] * m["ffn_dim"]
            + _norms(m))


def layer_params_outside_experts(m: dict) -> int:
    """An expert layer less its routed experts: the attention block, the
    shared expert, the router, the selection bias and the norms."""
    return (attention_params(m) + m["shared_experts"] * expert_params(m)
            + m["hidden_size"] * m["num_experts"] + m["num_experts"]
            + _norms(m))


def num_params(m: dict) -> int:
    """Parameters held here: the chip's share."""
    per_layer = layer_params_outside_experts(m) + held(m) * expert_params(m)
    return (2 * m["vocab_size"] * m["hidden_size"]
            + m["dense_layers"] * dense_layer_params(m)
            + m["num_layers"] * per_layer + m["hidden_size"])


def weight_bytes(m: dict) -> int:
    """The selection bias is float32 whatever the parameters are.  The
    derived pairs are not in it (``derived_pair_params``)."""
    b = DTYPE_BYTES[m["param_dtype"]]
    return num_params(m) * b + m["num_layers"] * m["num_experts"] * (4 - b)


def kv_bytes_per_token(m: dict) -> int:
    """What a cached position takes in the pool, over all layers: one row
    a layer, padded to whole 128-lane tiles (576 -> 640)."""
    width = -(-latent_row(m) // LANES) * LANES
    return layers(m) * width * DTYPE_BYTES[m.get("dtype", "bfloat16")]


def latent_bytes_per_token(m: dict) -> int:
    """What attention has to read of a cached position, over all layers:
    the rows without their padding."""
    return layers(m) * latent_row(m) * DTYPE_BYTES[m.get("dtype",
                                                         "bfloat16")]


def decode_step_bytes(m: dict, live_tokens: float,
                      experts_hit_share: float = 1.0) -> float:
    """Bytes one decode step has to move: every weight outside the
    embedding table (looked up, not read) and outside the routed experts
    once (the shared expert among them; the absorbed pair in ``W_kvb``'s
    place, not beside it), the weights of the held experts that got a token
    once, and the live latent rows once."""
    b = DTYPE_BYTES[m["param_dtype"]]
    experts = m["num_layers"] * held(m) * expert_params(m) * b
    embed = m["vocab_size"] * m["hidden_size"] * b
    return (weight_bytes(m) - embed - experts + experts * experts_hit_share
            + live_tokens * latent_bytes_per_token(m))
