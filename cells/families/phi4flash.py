"""Family ``phi4flash``: Phi-4-mini-flash-reasoning's decoder (state-space
layers whose state is a record a request, window and full differential
attention, gated memory units and cross-attention layers that read the one
full cache) through the program's ``ray_tpu.models.phi4flash``, against
``cells/families/phi4flash_reference.py``.

Served only: the family supplies no trainer (the scan has no backward here,
and the driver's count found no training cut that fits a chip).  The
wrappers add nothing to the program's own entry points.  The arithmetic is
computed from a configuration's ``model`` group (a plain dict) and imports
neither ``ray_tpu`` nor ``jax``.
"""

from cells.flops import DTYPE_BYTES

# --rehearse: the same code paths on the CPU in seconds, never a result
TOY_MODEL = {
    "vocab_size": 256, "hidden_size": 64, "num_layers": 8, "num_heads": 8,
    "num_kv_heads": 4, "head_dim": 16, "intermediate_size": 128,
    "sliding_window": 32, "mamba_d_state": 4, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 8, "layer_norm_eps": 1e-05,
    "max_seq_len": 128, "dtype": "float32", "param_dtype": "float32"}

# the source's config.json key -> the ``model`` group's key
SOURCE_KEYS = {
    "hidden_size": "hidden_size", "intermediate_size": "intermediate_size",
    "layer_norm_eps": "layer_norm_eps",
    "max_position_embeddings": "max_seq_len",
    "num_attention_heads": "num_heads", "num_hidden_layers": "num_layers",
    "num_key_value_heads": "num_kv_heads", "sliding_window": "sliding_window",
    "vocab_size": "vocab_size"}
# the source's keys no configuration may reduce
WIDTHS = frozenset({
    "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "sliding_window", "vocab_size"})


# --------------------------------------------------------------- the program

def config(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models.phi4flash import Phi4FlashConfig

    kw = {k: v for k, v in model.items() if k != "control_dtype"}
    for key in ("dtype", "param_dtype"):
        if key in kw:
            kw[key] = jnp.dtype(kw[key])
    return Phi4FlashConfig(**kw)


def init(key, cfg):
    from ray_tpu.models.phi4flash import phi4flash_init

    return phi4flash_init(key, cfg)


def apply(params, tokens, cfg, mesh):
    from ray_tpu.models.phi4flash import phi4flash_apply

    return phi4flash_apply(params, tokens, cfg, mesh=mesh)


def serve_programs(cfg, engine: dict, prompt_len: int):
    """For ``tools/compile_for_v5e.py`` only: the engine's decode step, one
    prefill of ``prompt_len`` tokens and the seeded weights' one program,
    each as (name, function, donated argument numbers, abstract
    arguments)."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import phi4flash as pf

    B, bs = engine["batch_slots"], engine["block_size"]
    MB = -(-engine["max_len"] // bs)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(functools.partial(pf.phi4flash_init, cfg=cfg),
                            key)
    blocks = engine["num_blocks"]
    pool = jax.eval_shape(lambda: pf.init_pools(cfg, blocks, bs))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    S = 1  # the engine's bucket: a power of two, capped at max_len
    while S < prompt_len:
        S *= 2
    S = min(S, engine["max_len"])
    empty = jax.ShapeDtypeStruct(
        (1, 0, cfg.num_kv_heads // 2, 2 * cfg.head_dim), cfg.dtype)
    tables = {"full": i32(B, MB), "window": i32(B, MB), "state": i32(B, 1)}
    dst = {"full": i32(S), "window": i32(S), "state": i32(1)}
    return [
        ("decode step", functools.partial(
            pf.decode_sample, cfg=cfg, attn="paged_kernel"),
         (4,), (params, i32(B), i32(B), tables, pool, key,
                jax.ShapeDtypeStruct((B,), jnp.float32))),
        (f"prefill of {S} tokens",
         functools.partial(pf.prefill_suffix, cfg=cfg),
         (9,), (params, i32(1, S), i32(), i32(), empty, empty, i32(),
                dst, i32(S), pool)),
        ("seeded weights", functools.partial(
            pf.phi4flash_init.__wrapped__, cfg=cfg), (), (key,))]


def reference():
    """The plain reference: ``logits``, ``loss`` (contract in its
    docstring)."""
    from cells.families import phi4flash_reference

    return phi4flash_reference


# ------------------------------------------------------------- arithmetic

def layer_kinds(m: dict) -> list:
    """The kind of every layer, by depth: ``L // 4`` pairs (ssm, window),
    the pair (ssm, full), then pairs (gmu, cross)."""
    L = m["num_layers"]
    window_pairs = L // 4
    cross_pairs = L // 2 - 1 - window_pairs
    return (["ssm", "window"] * window_pairs + ["ssm", "full"]
            + ["gmu", "cross"] * cross_pairs)


def inner_size(m: dict) -> int:
    return m["mamba_expand"] * m["hidden_size"]


def mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def ssm_params(m: dict) -> int:
    """in_proj, the convolution and its bias, x_proj, dt_proj and its bias,
    A_log, D, out_proj."""
    h, i, n = m["hidden_size"], inner_size(m), m["mamba_d_state"]
    k, r = m["mamba_d_conv"], m["mamba_dt_rank"]
    return (h * 2 * i + k * i + i + i * (r + 2 * n) + r * i + i + i * n + i
            + i * h)


def _differential_params(m: dict) -> int:
    """out_proj and its bias, four lambda vectors, the norm over a pair's
    value."""
    h, q = m["hidden_size"], m["num_heads"] * m["head_dim"]
    return q * h + h + 4 * m["head_dim"] + 2 * m["head_dim"]


def attention_params(m: dict) -> int:
    """Wqkv and its bias, then the differential block's own."""
    width = (m["num_heads"] + 2 * m["num_kv_heads"]) * m["head_dim"]
    return m["hidden_size"] * width + width + _differential_params(m)


def cross_attention_params(m: dict) -> int:
    """W_q and its bias alone (keys and values are the full layer's)."""
    q = m["num_heads"] * m["head_dim"]
    return m["hidden_size"] * q + q + _differential_params(m)


def gmu_params(m: dict) -> int:
    return 2 * m["hidden_size"] * inner_size(m)


MIX_PARAMS = {"ssm": ssm_params, "window": attention_params,
              "full": attention_params, "gmu": gmu_params,
              "cross": cross_attention_params}


def float32_params(m: dict) -> int:
    """The leaves kept in float32 whatever ``param_dtype`` says: A_log and
    the lambda vectors."""
    kinds = layer_kinds(m)
    return (kinds.count("ssm") * inner_size(m) * m["mamba_d_state"]
            + (len(kinds) - kinds.count("ssm") - kinds.count("gmu"))
            * 4 * m["head_dim"])


def num_params(m: dict) -> int:
    """Every parameter: the embedding once (the head is the same table),
    each layer's mixer, MLP and two LayerNorms, the final LayerNorm."""
    h = m["hidden_size"]
    return (m["vocab_size"] * h + 2 * h
            + sum(MIX_PARAMS[k](m) + mlp_params(m) + 4 * h
                  for k in layer_kinds(m)))


def weight_bytes(m: dict) -> int:
    f32 = float32_params(m)
    return (num_params(m) - f32) * DTYPE_BYTES[m["param_dtype"]] + f32 * 4


def kv_row_bytes(m: dict) -> int:
    """Keys and values of one position in ONE storing layer."""
    return (2 * m["num_kv_heads"] * m["head_dim"]
            * DTYPE_BYTES[m.get("dtype", "bfloat16")])


def storing_layers(m: dict) -> dict:
    """{type: layers that STORE a pool of positions}."""
    kinds = layer_kinds(m)
    return {"full": kinds.count("full"), "window": kinds.count("window")}


def reading_layers(m: dict) -> dict:
    """{type: layers that READ it in a decode step}: the cross-attention
    layers read the full layer's."""
    kinds = layer_kinds(m)
    return {"full": kinds.count("full") + kinds.count("cross"),
            "window": kinds.count("window")}


def kv_bytes_per_token(m: dict) -> int:
    """What a cached position takes over all layers that store it while
    every one holds it (a window layer gives it back once it is behind the
    window)."""
    return sum(storing_layers(m).values()) * kv_row_bytes(m)


def state_record_bytes(m: dict) -> int:
    """One request's record over all state-space layers: the float32 state
    and the convolution's tail."""
    i = inner_size(m)
    one = (i * m["mamba_d_state"] * 4 + i * (m["mamba_d_conv"] - 1)
           * DTYPE_BYTES[m.get("dtype", "bfloat16")])
    return layer_kinds(m).count("ssm") * one


def hybrid_attention_bytes(m: dict, live_by_type: dict) -> float:
    """Bytes the paged decode kernel has to read in ONE step, all its calls
    together: each READING layer its type's live rows once
    (``live_by_type``: ``live_tokens_full`` / ``live_tokens_window`` of
    ``engine.dispatch_window``; a window layer at most its window)."""
    return sum(n * live_by_type[t] * kv_row_bytes(m)
               for t, n in reading_layers(m).items())


def state_update_bytes(m: dict, records: float) -> float:
    """Bytes a decode step's state-space layers have to move for
    ``records`` live requests: every layer's state and tail read and
    written."""
    return 2.0 * records * state_record_bytes(m)


def decode_step_bytes(m: dict, live_by_type: dict) -> float:
    """Bytes one decode step has to move: every weight once (the tied
    table once, as the head; its lookup reads a row a slot), each storing
    type's live rows times the layers that READ it, the live requests'
    state records read and written.  ``live_by_type``: ``full``,
    ``window`` (positions, all slots together) and ``state`` (records)."""
    return (weight_bytes(m) + hybrid_attention_bytes(m, live_by_type)
            + state_update_bytes(m, live_by_type["state"]))
