"""Family ``longcat_flash``: LongCat-Flash's language model (latent
attention, the shortcut-connected double layer, routed and zero-compute
experts with a held range) through the program's ``ray_tpu.models.longcat``,
against ``cells/families/longcat_flash_reference.py``.

Served only: the family supplies no trainer (16 bytes a parameter of one
layer outside its experts is already 10 GB).  The wrappers add nothing to
the program's own entry points.  The arithmetic is computed from a
configuration's ``model`` group (a plain dict) and imports neither
``ray_tpu`` nor ``jax``.
"""

from cells.flops import DTYPE_BYTES

# --rehearse: the same code paths on the CPU in seconds, never a result
TOY_MODEL = {
    "vocab_size": 256, "hidden_size": 64, "num_layers": 2, "num_heads": 4,
    "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "ffn_dim": 128,
    "expert_ffn_dim": 32, "num_experts": 32, "zero_experts": 16,
    "experts_per_token": 4, "first_expert": 8, "held_experts": 8,
    "max_seq_len": 128, "dtype": "float32", "param_dtype": "float32"}

# the source's config.json key -> the ``model`` group's key
SOURCE_KEYS = {
    "hidden_size": "hidden_size", "ffn_hidden_size": "ffn_dim",
    "expert_ffn_hidden_size": "expert_ffn_dim",
    "num_attention_heads": "num_heads", "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "moe_topk": "experts_per_token", "zero_expert_num": "zero_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps",
    "mla_scale_q_lora": "mla_scale_q_lora",
    "mla_scale_kv_lora": "mla_scale_kv_lora",
    "num_layers": "num_layers", "n_routed_experts": "held_experts",
    "vocab_size": "vocab_size", "max_position_embeddings": "max_seq_len"}
# the source's keys no configuration may reduce
WIDTHS = frozenset({
    "hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
    "num_attention_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "moe_topk",
    "zero_expert_num", "routed_scaling_factor", "rope_theta",
    "mla_scale_q_lora", "mla_scale_kv_lora"})

LANES = 128  # a cached row is padded to whole lane tiles (models/longcat.py)


# --------------------------------------------------------------- the program

def config(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models.longcat import LongcatConfig

    kw = {k: v for k, v in model.items() if k != "control_dtype"}
    for key in ("dtype", "param_dtype"):
        if key in kw:
            kw[key] = jnp.dtype(kw[key])
    return LongcatConfig(**kw)


def init(key, cfg):
    from ray_tpu.models.longcat import longcat_init

    return longcat_init(key, cfg)


def apply(params, tokens, cfg, mesh):
    from ray_tpu.models.longcat import longcat_apply

    return longcat_apply(params, tokens, cfg, mesh=mesh)


def serve_programs(cfg, engine: dict, prompt_len: int):
    """For ``tools/compile_for_v5e.py`` only: the engine's decode step, one
    prefill of ``prompt_len`` tokens and the seeded weights' one program,
    each as (name, function, donated argument numbers, abstract
    arguments)."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.longcat import (init_latent_pool,
                                        latent_decode_sample,
                                        latent_prefill_suffix, longcat_init)

    B, bs = engine["batch_slots"], engine["block_size"]
    MB = -(-engine["max_len"] // bs)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(functools.partial(longcat_init, cfg=cfg), key)
    pool = jax.eval_shape(lambda: init_latent_pool(
        cfg, engine.get("num_blocks") or B * MB + 1, bs))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    S = prompt_len
    empty = lambda w: jax.ShapeDtypeStruct(  # noqa: E731
        (2 * cfg.num_layers, 0, w), cfg.dtype)
    return [
        ("decode step", functools.partial(
            latent_decode_sample, cfg=cfg, attn="latent_kernel"),
         (4,), (params, i32(B), i32(B), i32(B, MB), pool, key,
                jax.ShapeDtypeStruct((B,), jnp.float32))),
        (f"prefill of {S} tokens",
         functools.partial(latent_prefill_suffix, cfg=cfg),
         (9,), (params, i32(1, S), i32(), i32(), empty(cfg.kv_lora_rank),
                empty(cfg.qk_rope_head_dim), i32(), i32(S), i32(S), pool)),
        ("seeded weights", functools.partial(longcat_init.__wrapped__,
                                             cfg=cfg), (), (key,))]


def reference():
    """The plain reference: ``logits``, ``loss`` (contract in its
    docstring)."""
    from cells.families import longcat_flash_reference

    return longcat_flash_reference


# ------------------------------------------------------------- arithmetic

def attention_params(m: dict) -> int:
    """One latent attention block: W_qa, W_qb, W_kva, W_kvb, W_o."""
    h, nh = m["hidden_size"], m["num_heads"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    qr, kr = m["q_lora_rank"], m["kv_lora_rank"]
    return (h * qr + qr * nh * (dn + dr) + h * (kr + dr)
            + kr * nh * (dn + dv) + nh * dv * h)


def expert_params(m: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * m["hidden_size"] * m["expert_ffn_dim"]


def held(m: dict) -> int:
    return m["num_experts"] if m.get("held_experts") is None \
        else m["held_experts"]


def layer_params_outside_experts(m: dict) -> int:
    """A double layer less its experts: two attention blocks, two dense
    SwiGLU blocks, the router, the selection bias and the norms."""
    h = m["hidden_size"]
    n = m["num_experts"] + m["zero_experts"]
    norms = 4 * h + 2 * m["q_lora_rank"] + 2 * m["kv_lora_rank"]
    return (2 * attention_params(m) + 2 * 3 * h * m["ffn_dim"] + h * n + n
            + norms)


def num_params(m: dict) -> int:
    """Parameters held here: the chip's share."""
    per_layer = layer_params_outside_experts(m) + held(m) * expert_params(m)
    return (2 * m["vocab_size"] * m["hidden_size"]
            + m["num_layers"] * per_layer + m["hidden_size"])


def weight_bytes(m: dict) -> int:
    """The selection bias is float32 whatever the parameters are."""
    n = m["num_experts"] + m["zero_experts"]
    b = DTYPE_BYTES[m["param_dtype"]]
    return num_params(m) * b + m["num_layers"] * n * (4 - b)


def latent_row(m: dict) -> int:
    """Numbers a token and attention block in the cache: c_kv and k_pe."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def kv_bytes_per_token(m: dict) -> int:
    """What a cached position takes in the pool, over all layers: two rows
    a layer, each padded to whole 128-lane tiles (576 -> 640)."""
    width = -(-latent_row(m) // LANES) * LANES
    return (2 * m["num_layers"] * width
            * DTYPE_BYTES[m.get("dtype", "bfloat16")])


def latent_bytes_per_token(m: dict) -> int:
    """What attention has to read of a cached position, over all layers:
    the rows without their padding."""
    return (2 * m["num_layers"] * latent_row(m)
            * DTYPE_BYTES[m.get("dtype", "bfloat16")])


def latent_attention_bytes(m: dict, live_tokens: float) -> float:
    """Bytes ONE latent attention block's decode kernel has to move: the
    live rows once (keys and values are the same row)."""
    return live_tokens * latent_row(m) * DTYPE_BYTES[m.get("dtype",
                                                           "bfloat16")]


def decode_step_bytes(m: dict, live_tokens: float,
                      experts_hit_share: float = 1.0) -> float:
    """Bytes one decode step has to move: every weight outside the
    embedding table (looked up, not read) and outside the experts once,
    the weights of the held experts that got a token once, and the live
    latent rows once."""
    b = DTYPE_BYTES[m["param_dtype"]]
    experts = m["num_layers"] * held(m) * expert_params(m) * b
    embed = m["vocab_size"] * m["hidden_size"] * b
    return (weight_bytes(m) - embed - experts + experts * experts_hit_share
            + live_tokens * latent_bytes_per_token(m))
