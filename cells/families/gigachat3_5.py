"""Family ``gigachat3_5``: GigaChat3.5's family (GigaChat3.5-432B-A28B: Gated
DeltaNet layers whose matrix state is a record a request, beside one gated
latent-attention block in four, a sigmoid router without a group limit, a
shared expert beside the held routed ones, leading dense layers, four
zero-centred gated norms a layer) through the program's
``ray_tpu.models.gigachat3_5``, against
``cells/families/gigachat3_5_reference.py``.

Served only: the family supplies no trainer (the scan has no backward here,
and at 16 bytes a parameter no cut within the floors fits a chip).  The
wrappers add nothing to the program's own entry points.  The arithmetic is
computed from a configuration's ``model`` group (a plain dict) and imports
neither ``ray_tpu`` nor ``jax``.

**The ``model`` group's depth**, as ``cells/families/deepseek_v3.py``:
``num_layers`` is the number of EXPERT layers (``cells/expert_counters.py``
divides the expert counters by it), ``dense_layers`` the leading dense ones
(``first_k_dense_replace``) and ``hidden_layers`` their sum
(``num_hidden_layers``); ``full_attention_layers`` lists, of the
``hidden_layers``, those whose mixer is the latent block.  ``config()`` hands
the program the sum.  ``rope_scaling`` is the source's own group.
"""

from cells.families.deepseek_v3 import derived_pair_params, layers
from cells.families.longcat_flash import (  # latent attention's arithmetic
    LANES, attention_params, expert_params, held, latent_attention_bytes,
    latent_row)
from cells.flops import DTYPE_BYTES

# --rehearse: the same code paths on the CPU in seconds, never a result
TOY_MODEL = {
    "vocab_size": 256, "hidden_size": 64, "hidden_layers": 4,
    "num_layers": 3, "dense_layers": 1, "full_attention_layers": [3],
    "num_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "linear_key_heads": 2, "linear_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel": 4, "ffn_dim": 128, "expert_ffn_dim": 32,
    "num_experts": 32, "shared_experts": 1, "experts_per_token": 4,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "swiglu_limit": 10, "linear_gate_scale": 2, "linear_norm_eps": 1e-6,
    "norm_gate_scale": 2, "rms_norm_eps": 1e-6,
    "first_expert": 8, "held_experts": 8, "rope_theta": 1e4,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32,
                     "type": "yarn"},
    "max_seq_len": 128, "dtype": "float32", "param_dtype": "float32"}

# the source's config.json key -> the ``model`` group's key
SOURCE_KEYS = {
    "hidden_size": "hidden_size", "intermediate_size": "ffn_dim",
    "moe_intermediate_size": "expert_ffn_dim",
    "num_attention_heads": "num_heads", "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "linear_num_key_heads": "linear_key_heads",
    "linear_num_value_heads": "linear_value_heads",
    "linear_key_head_dim": "linear_key_head_dim",
    "linear_value_head_dim": "linear_value_head_dim",
    "linear_conv_kernel_dim": "linear_conv_kernel",
    "linear_sigmoid_gate_scale": "linear_gate_scale",
    "linear_attn_o_norm_eps": "linear_norm_eps",
    "layernorm_gating_weight": "norm_gate_scale",
    "swiglu_limit": "swiglu_limit",
    "num_experts_per_tok": "experts_per_token",
    "n_shared_experts": "shared_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob", "rope_theta": "rope_theta",
    "rope_scaling": "rope_scaling", "rms_norm_eps": "rms_norm_eps",
    "num_hidden_layers": "hidden_layers",
    "first_k_dense_replace": "dense_layers",
    "full_attention_layers": "full_attention_layers",
    "n_routed_experts": "held_experts", "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len"}
# the source's keys no configuration may reduce
WIDTHS = frozenset(SOURCE_KEYS) - {
    "num_hidden_layers", "first_k_dense_replace", "full_attention_layers",
    "n_routed_experts", "vocab_size", "max_position_embeddings"}

# --------------------------------------------------------------- the program

_NOT_THE_PROGRAMS = ("control_dtype", "hidden_layers", "rope_scaling")


def config(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models.gigachat3_5 import GigaChat35Config

    kw = {k: v for k, v in model.items() if k not in _NOT_THE_PROGRAMS}
    if model["hidden_layers"] != model["num_layers"] + model["dense_layers"]:
        raise ValueError(
            "hidden_layers is num_layers (the expert layers) + dense_layers")
    kw["num_layers"] = model["hidden_layers"]
    kw["full_attention_layers"] = tuple(model["full_attention_layers"])
    rs = model["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling of type {rs['type']!r}")
    kw.update(rope_factor=rs["factor"],
              rope_original_max_len=rs["original_max_position_embeddings"],
              rope_beta_fast=rs["beta_fast"], rope_beta_slow=rs["beta_slow"],
              rope_mscale=rs["mscale"],
              rope_mscale_all_dim=rs["mscale_all_dim"])
    for key in ("dtype", "param_dtype"):
        if key in kw:
            kw[key] = jnp.dtype(kw[key])
    return GigaChat35Config(**kw)


def model_of(cfg) -> dict:
    """``config``'s inverse: the ``model`` group of a program's
    configuration (what the reference is handed)."""
    import dataclasses

    import numpy as np

    m = dataclasses.asdict(cfg)
    rs = {"type": "yarn",
          "original_max_position_embeddings": m.pop("rope_original_max_len")}
    for name in ("factor", "beta_fast", "beta_slow", "mscale",
                 "mscale_all_dim"):
        rs[name] = m.pop("rope_" + name)
    m.update(rope_scaling=rs, hidden_layers=cfg.num_layers,
             num_layers=cfg.expert_layers,
             full_attention_layers=list(cfg.full_attention_layers))
    for key in ("dtype", "param_dtype"):
        m[key] = np.dtype(m[key]).name
    return m


def init(key, cfg):
    from ray_tpu.models.gigachat3_5 import gigachat3_5_init

    return gigachat3_5_init(key, cfg)


def apply(params, tokens, cfg, mesh):
    from ray_tpu.models.gigachat3_5 import gigachat3_5_apply

    return gigachat3_5_apply(params, tokens, cfg, mesh=mesh)


def serve_programs(cfg, engine: dict, prompt_len: int):
    """For ``tools/compile_for_v5e.py`` only: the engine's decode step, one
    prefill of ``prompt_len`` tokens, the seeded weights' one program and
    the plain reference over ``max_len`` positions as
    ``serve_runner.reference_check`` runs it beside the weights, each as
    (name, function, donated argument numbers, abstract arguments)."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gigachat3_5 as gc

    B, bs = engine["batch_slots"], engine["block_size"]
    MB = -(-engine["max_len"] // bs)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(
        functools.partial(gc.gigachat3_5_init, cfg=cfg), key)
    pool = jax.eval_shape(
        lambda: gc.init_pools(cfg, engine["num_blocks"], bs))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    S = prompt_len
    empty = lambda w: jax.ShapeDtypeStruct(  # noqa: E731
        (cfg.attention_blocks, 0, w), cfg.dtype)
    tables = {"latent": i32(B, MB), "state": i32(B, 1)}
    dst = {"latent": i32(S), "state": i32(1)}
    ref, model = reference(), model_of(cfg)

    def gaps(params, tokens):  # serve_runner.reference_check's program
        lg = ref.logits(params, tokens[:-1], model)
        chosen = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - chosen

    return [
        ("decode step", functools.partial(
            gc.decode_sample, cfg=cfg, attn="latent_kernel"),
         (4,), (params, i32(B), i32(B), tables, pool, key,
                jax.ShapeDtypeStruct((B,), jnp.float32))),
        (f"prefill of {S} tokens",
         functools.partial(gc.prefill_suffix, cfg=cfg),
         (9,), (params, i32(1, S), i32(), i32(), empty(cfg.kv_lora_rank),
                empty(cfg.qk_rope_head_dim), i32(), dst, i32(S), pool)),
        ("seeded weights", functools.partial(
            gc.gigachat3_5_init.__wrapped__, cfg=cfg), (), (key,)),
        (f"reference over {engine['max_len']} positions", gaps, (),
         (params, i32(engine["max_len"] + 1)))]


def reference():
    """The plain reference: ``logits``, ``loss`` (contract in its
    docstring)."""
    from cells.families import gigachat3_5_reference

    return gigachat3_5_reference


# ------------------------------------------------------------- arithmetic

def latent_blocks(m: dict) -> int:
    return len(m["full_attention_layers"])


def delta_layers(m: dict) -> int:
    return layers(m) - latent_blocks(m)


def conv_channels(m: dict) -> int:
    """What the causal convolution runs over: ``[q | k | v]``."""
    return (2 * m["linear_key_heads"] * m["linear_key_head_dim"]
            + m["linear_value_heads"] * m["linear_value_head_dim"])


def value_width(m: dict) -> int:
    return m["linear_value_heads"] * m["linear_value_head_dim"]


def delta_net_params(m: dict) -> int:
    """One Gated DeltaNet mixer: W_qkv, W_z, W_ba, the convolution, W_o,
    and its float32 vectors (A_log, dt_bias, the output norm's)."""
    h, hv = m["hidden_size"], m["linear_value_heads"]
    return (h * conv_channels(m) + h * value_width(m) + h * 2 * hv
            + m["linear_conv_kernel"] * conv_channels(m)
            + value_width(m) * h + 2 * hv + m["linear_value_head_dim"])


def latent_block_params(m: dict) -> int:
    """One gated latent block: DeepSeek-V3's five matrices, the gate's, and
    the norms on the two latents."""
    return (attention_params(m)
            + m["hidden_size"] * m["num_heads"] * m["v_head_dim"]
            + m["q_lora_rank"] + m["kv_lora_rank"])


def mixer_params(m: dict, layer: int) -> int:
    return latent_block_params(m) if layer in m["full_attention_layers"] \
        else delta_net_params(m)


def ffn_params(m: dict, layer: int) -> int:
    """The dense SwiGLU of a leading layer; else the shared expert, the
    router with its selection bias and the held routed experts."""
    if layer < m["dense_layers"]:
        return 3 * m["hidden_size"] * m["ffn_dim"]
    return ((m["shared_experts"] + held(m)) * expert_params(m)
            + m["hidden_size"] * m["num_experts"] + m["num_experts"])


def float32_params(m: dict) -> int:
    """The leaves kept in float32 whatever ``param_dtype`` says: the four
    norms a layer and the final one, a DeltaNet mixer's three vectors, the
    router's selection bias."""
    h = m["hidden_size"]
    return ((4 * layers(m) + 1) * h
            + delta_layers(m) * (2 * m["linear_value_heads"]
                                 + m["linear_value_head_dim"])
            + m["num_layers"] * m["num_experts"])


def num_params(m: dict) -> int:
    """Parameters held here: the chip's share."""
    h = m["hidden_size"]
    return (2 * m["vocab_size"] * h + h
            + sum(mixer_params(m, l) + ffn_params(m, l) + 4 * h
                  for l in range(layers(m))))


def weight_bytes(m: dict) -> int:
    """The derived pairs are not in it (``derived_pair_params``)."""
    f32 = float32_params(m)
    return (num_params(m) - f32) * DTYPE_BYTES[m["param_dtype"]] + f32 * 4


def _act(m: dict) -> int:
    return DTYPE_BYTES[m.get("dtype", "bfloat16")]


def kv_bytes_per_token(m: dict) -> int:
    """What a cached position takes in the latent pool, over the latent
    blocks: one row a block, padded to whole 128-lane tiles (576 -> 640).
    The DeltaNet layers keep no positions."""
    width = -(-latent_row(m) // LANES) * LANES
    return latent_blocks(m) * width * _act(m)


def latent_bytes_per_token(m: dict) -> int:
    """What attention has to read of a cached position, over the latent
    blocks: the rows without their padding."""
    return latent_blocks(m) * latent_row(m) * _act(m)


def state_record_bytes(m: dict) -> int:
    """One request's record over all DeltaNet layers: a float32 matrix a
    value head and the convolution's tail."""
    one = (m["linear_value_heads"] * m["linear_key_head_dim"]
           * m["linear_value_head_dim"] * 4
           + (m["linear_conv_kernel"] - 1) * conv_channels(m) * _act(m))
    return delta_layers(m) * one


def state_update_bytes(m: dict, records: float) -> float:
    """Bytes a decode step's DeltaNet layers have to move for ``records``
    live requests: every layer's state and tail read once and written
    once."""
    return 2.0 * records * state_record_bytes(m)


def delta_scan_work(m: dict, tokens: float) -> tuple:
    """(operations, bytes) the recurrence itself asks for ``tokens`` prompt
    positions, all DeltaNet layers, whatever implements it: a position and
    value head decays the state, reads it against ``k``, adds a rank-one
    term and reads it against ``q`` (7 operations an element of ``S``), and
    moves ``q``, ``k``, ``v`` in and ``o`` out in the model's dtype and
    ``alpha``, ``beta`` in float32.  A chunked form does more products than
    this count; its share of the roofline says how many."""
    hv = m["linear_value_heads"]
    ops = 7.0 * m["linear_key_head_dim"] * m["linear_value_head_dim"] * hv
    moved = (conv_channels(m) + value_width(m)) * _act(m) + 2 * hv * 4
    return (tokens * delta_layers(m) * ops, tokens * delta_layers(m) * moved)


def decode_step_bytes(m: dict, live_tokens: float,
                      experts_hit_share: float = 1.0,
                      records: float = 0.0) -> float:
    """Bytes one decode step has to move: every weight outside the
    embedding table (looked up, not read) and outside the routed experts
    once (the absorbed pair in ``W_kvb``'s place, not beside it), the
    weights of the held experts that got a token once, the live latent
    rows once, and the live requests' records read and written
    (``records``: ``live_tokens_state`` of ``engine.dispatch_window``; the
    accepted ``decode_step_roofline.steady`` hands over positions and hit
    share alone, so its share leaves the records out and reads low)."""
    b = DTYPE_BYTES[m["param_dtype"]]
    experts = m["num_layers"] * held(m) * expert_params(m) * b
    embed = m["vocab_size"] * m["hidden_size"] * b
    return (weight_bytes(m) - embed - experts + experts * experts_hit_share
            + live_tokens * latent_bytes_per_token(m)
            + state_update_bytes(m, records))
