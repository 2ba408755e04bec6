"""Family ``smallthinker``: SmallThinker's decoder (window and full
attention layers mixed, a router that reads the layer's input, ReGLU
experts with a held range) through the program's
``ray_tpu.models.smallthinker``, against
``cells/families/smallthinker_reference.py``.

Served only: the family supplies no trainer (at 16 bytes a parameter a chip
holds 16 of a layer's 64 experts and 4 layers, and a window of 4096 does
nothing at the train cell's sequence).  The wrappers add nothing to the
program's own entry points.  The arithmetic is computed from a
configuration's ``model`` group (a plain dict) and imports neither
``ray_tpu`` nor ``jax``.
"""

from cells.flops import DTYPE_BYTES

# --rehearse: the same code paths on the CPU in seconds, never a result
TOY_MODEL = {
    "vocab_size": 256, "hidden_size": 64, "num_layers": 4, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 16, "expert_ffn_dim": 32,
    "num_experts": 8, "experts_per_token": 3, "first_expert": 0,
    "held_experts": 8, "sliding_window": 32, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "max_seq_len": 128,
    "dtype": "float32", "param_dtype": "float32"}

# the source's config.json key -> the ``model`` group's key
SOURCE_KEYS = {
    "head_dim": "head_dim", "hidden_size": "hidden_size",
    "moe_ffn_hidden_size": "expert_ffn_dim",
    "moe_num_active_primary_experts": "experts_per_token",
    "moe_num_primary_experts": "held_experts",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "sliding_window_size": "sliding_window", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_norm_eps", "num_hidden_layers": "num_layers",
    "vocab_size": "vocab_size", "max_position_embeddings": "max_seq_len"}
# the source's keys no configuration may reduce
WIDTHS = frozenset({
    "head_dim", "hidden_size", "moe_ffn_hidden_size",
    "moe_num_active_primary_experts", "num_attention_heads",
    "num_key_value_heads", "sliding_window_size", "rope_theta",
    "vocab_size"})

# the source's sliding_window_layout / rope_layout entry -> a layer's type
LAYOUT = {0: "full", 1: "window"}


# --------------------------------------------------------------- the program

def config(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models.smallthinker import SmallThinkerConfig

    kw = {k: v for k, v in model.items() if k != "control_dtype"}
    for key in ("dtype", "param_dtype"):
        if key in kw:
            kw[key] = jnp.dtype(kw[key])
    if "layer_period" in kw:
        kw["layer_period"] = tuple(kw["layer_period"])
    return SmallThinkerConfig(**kw)


def init(key, cfg):
    from ray_tpu.models.smallthinker import smallthinker_init

    return smallthinker_init(key, cfg)


def apply(params, tokens, cfg, mesh):
    from ray_tpu.models.smallthinker import smallthinker_apply

    return smallthinker_apply(params, tokens, cfg, mesh=mesh)


def serve_programs(cfg, engine: dict, prompt_len: int):
    """For ``tools/compile_for_v5e.py`` only: the engine's decode step, one
    prefill of ``prompt_len`` tokens and the seeded weights' one program,
    each as (name, function, donated argument numbers, abstract
    arguments)."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import smallthinker as st

    B, bs = engine["batch_slots"], engine["block_size"]
    MB = -(-engine["max_len"] // bs)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(functools.partial(st.smallthinker_init, cfg=cfg),
                            key)
    blocks = engine["num_blocks"]
    pool = jax.eval_shape(lambda: st.init_pools(cfg, blocks, bs))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    S = 1  # the engine's bucket: a power of two, capped at max_len
    while S < prompt_len:
        S *= 2
    S = min(S, engine["max_len"])
    empty = jax.ShapeDtypeStruct(
        (cfg.num_layers, 0, cfg.num_kv_heads, cfg.head_dim), cfg.dtype)
    by_type = lambda *s: {t: i32(*s) for t in pool}  # noqa: E731
    return [
        ("decode step", functools.partial(
            st.decode_sample, cfg=cfg, attn="paged_kernel"),
         (4,), (params, i32(B), i32(B), by_type(B, MB), pool, key,
                jax.ShapeDtypeStruct((B,), jnp.float32))),
        (f"prefill of {S} tokens",
         functools.partial(st.prefill_suffix, cfg=cfg),
         (9,), (params, i32(1, S), i32(), i32(), empty, empty, i32(),
                by_type(S), i32(S), pool)),
        ("seeded weights", functools.partial(
            st.smallthinker_init.__wrapped__, cfg=cfg), (), (key,))]


def reference():
    """The plain reference: ``logits``, ``loss`` (contract in its
    docstring)."""
    from cells.families import smallthinker_reference

    return smallthinker_reference


# ------------------------------------------------------------- arithmetic

def layer_kinds(m: dict) -> list:
    """The type of every layer, by depth."""
    period = m.get("layer_period") or ["full", "window", "window", "window"]
    return [period[i % len(period)] for i in range(m["num_layers"])]


def attention_params(m: dict) -> int:
    """One attention block: W_q, W_k, W_v, W_o."""
    h, hd = m["hidden_size"], m["head_dim"]
    return 2 * h * m["num_heads"] * hd + 2 * h * m["num_kv_heads"] * hd


def expert_params(m: dict) -> int:
    """One expert: gate, up, down."""
    return 3 * m["hidden_size"] * m["expert_ffn_dim"]


def held(m: dict) -> int:
    return m["num_experts"] if m.get("held_experts") is None \
        else m["held_experts"]


def layer_params_outside_experts(m: dict) -> int:
    """A layer less its experts: attention, the router, the two norms."""
    h = m["hidden_size"]
    return attention_params(m) + h * m["num_experts"] + 2 * h


def num_params(m: dict) -> int:
    """Parameters held here."""
    per_layer = layer_params_outside_experts(m) + held(m) * expert_params(m)
    return (2 * m["vocab_size"] * m["hidden_size"]
            + m["num_layers"] * per_layer + m["hidden_size"])


def weight_bytes(m: dict) -> int:
    return num_params(m) * DTYPE_BYTES[m["param_dtype"]]


def kv_row_bytes(m: dict) -> int:
    """Keys and values of one position in ONE layer."""
    return (2 * m["num_kv_heads"] * m["head_dim"]
            * DTYPE_BYTES[m.get("dtype", "bfloat16")])


def kv_bytes_per_token(m: dict) -> int:
    """What a cached position takes over all layers while every layer
    holds it (a window layer gives it back once it is behind the
    window)."""
    return m["num_layers"] * kv_row_bytes(m)


def decode_step_bytes(m: dict, live_tokens: float,
                      experts_hit_share: float = 1.0) -> float:
    """Bytes one decode step has to move: every weight outside the
    embedding table (looked up, not read) and outside the experts once,
    the weights of the held experts that got a token once, and the live
    cached rows once.  ``live_tokens`` is ``engine.dispatch_window``'s: the
    mean over the layers of the positions a step attends over, a window
    layer counting at most its window, so that times the bytes of a
    position over all layers it is what the layers read together."""
    b = DTYPE_BYTES[m["param_dtype"]]
    experts = m["num_layers"] * held(m) * expert_params(m) * b
    embed = m["vocab_size"] * m["hidden_size"] * b
    return (weight_bytes(m) - embed - experts + experts * experts_hit_share
            + live_tokens * kv_bytes_per_token(m))


def paged_attention_bytes(m: dict, live_by_kind: dict) -> float:
    """Bytes the paged decode kernel has to read in ONE step, all its calls
    together: each layer its type's live rows (``live_by_kind``:
    ``live_tokens_full`` / ``live_tokens_window`` of
    ``engine.dispatch_window``) once."""
    kinds = layer_kinds(m)
    return sum(kinds.count(k) * live * kv_row_bytes(m)
               for k, live in live_by_kind.items())


def _pairs_admitted(n: int, window) -> int:
    """(query, key) pairs of one sequence of ``n`` positions that the
    causal mask and the window admit: query ``q`` sees keys
    ``max(0, q - window + 1) .. q``."""
    if window is None or n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def flash_prefill_flops(m: dict, prompt_tokens: int) -> float:
    """Operations a prefill of ``prompt_tokens`` ASKS of the flash forward
    kernel, all layers: QK^T and PV are 4 * head_dim operations a query
    head and admitted (query, key) pair.  The true prompt, not the bucket
    it is padded to, and not the blocks the kernel runs whole under a
    mask: padding and masked work are the kernel's cost, not its yield."""
    per_pair = 4 * m["head_dim"] * m["num_heads"]
    kinds = layer_kinds(m)
    return float(sum(
        kinds.count(k) * _pairs_admitted(prompt_tokens, w) * per_pair
        for k, w in (("full", None), ("window", m["sliding_window"]))))


def flash_prefill_bytes(m: dict, prompt_tokens: int) -> float:
    """Least HBM traffic of the same calls: q read and o written once, k
    and v read once, at the true prompt length."""
    e = DTYPE_BYTES[m.get("dtype", "bfloat16")]
    q = prompt_tokens * m["num_heads"] * m["head_dim"] * e
    kv = prompt_tokens * m["num_kv_heads"] * m["head_dim"] * e
    return float(m["num_layers"] * (2 * q + 2 * kv))
