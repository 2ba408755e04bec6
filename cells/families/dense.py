"""Family ``dense``: the Llama/Mistral block (pre-RMSNorm, rotary
embedding, grouped-query attention, SwiGLU, untied head) through the
program's ``ray_tpu.models.llama``, against ``cells/reference.py``.

The wrappers add nothing to the program's own entry points, so the
measured path stays the normal path.  The arithmetic is computed from a
configuration's ``model`` group (a plain dict) and imports neither
``ray_tpu`` nor ``jax`` (copied from ``bench.py``'s
``train_flops_per_step`` so that no later PR can move a utilisation by
editing the program's copy).
"""

from cells import flops
from cells.flops import DTYPE_BYTES, head_dim

# --rehearse: the same code paths on the CPU in seconds, never a result
TOY_MODEL = {
    "vocab_size": 256, "hidden_size": 64, "num_layers": 2, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 16, "mlp_dim": 128, "max_seq_len": 128,
    "dtype": "float32", "param_dtype": "float32", "attention_impl": "auto"}

# the source's config.json key -> the ``model`` group's key
SOURCE_KEYS = {
    "hidden_size": "hidden_size", "intermediate_size": "mlp_dim",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "sliding_window": "sliding_window",
    "tie_word_embeddings": "tie_embeddings",
    "num_hidden_layers": "num_layers",
    "max_position_embeddings": "max_seq_len"}
# the source's keys no configuration may reduce
WIDTHS = frozenset({
    "hidden_size", "intermediate_size", "num_attention_heads",
    "num_key_value_heads", "head_dim", "vocab_size", "sliding_window"})


# --------------------------------------------------------------- the program

def config(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    kw = dict(model)
    for key in ("dtype", "param_dtype"):
        if key in kw:
            kw[key] = jnp.dtype(kw[key])
    return LlamaConfig(**kw)


def init(key, cfg):
    from ray_tpu.models.llama import llama_init

    return llama_init(key, cfg)


def apply(params, tokens, cfg, mesh):
    from ray_tpu.models.llama import llama_apply

    return llama_apply(params, tokens, cfg, mesh=mesh)


def make_trainer(cfg, mesh, optimizer: dict):
    """``optimizer``: the traffic file's group, ``default_optimizer``'s
    arguments."""
    from ray_tpu.models.training import default_optimizer, make_llama_trainer

    return make_llama_trainer(
        cfg, mesh, optimizer=default_optimizer(**optimizer))


def serve_programs(cfg, engine: dict, prompt_len: int):
    """For ``tools/compile_for_v5e.py`` only: the engine's decode step and
    one prefill of ``prompt_len`` tokens, each as (name, function, donated
    argument numbers, abstract arguments)."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import llama_init
    from ray_tpu.models.paged_generation import (init_kv_pool,
                                                 paged_decode_sample,
                                                 prefill_suffix)

    B, bs = engine["batch_slots"], engine["block_size"]
    MB = -(-engine["max_len"] // bs)
    params = jax.eval_shape(
        functools.partial(llama_init, cfg=cfg), jax.random.PRNGKey(0))
    pool = jax.eval_shape(lambda: init_kv_pool(
        cfg, engine.get("num_blocks") or B * MB + 1, bs))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    S = prompt_len
    empty = jax.ShapeDtypeStruct(
        (cfg.num_layers, 0, cfg.num_kv_heads, cfg.resolved_head_dim),
        cfg.dtype)
    return [
        ("decode step", functools.partial(paged_decode_sample, cfg=cfg),
         (4,), (params, i32(B), i32(B), i32(B, MB), pool, key,
                jax.ShapeDtypeStruct((B,), jnp.float32))),
        (f"prefill of {S} tokens", functools.partial(prefill_suffix, cfg=cfg),
         (9,), (params, i32(1, S), i32(), i32(), empty, empty, i32(),
                i32(S), i32(S), pool))]


def reference():
    """The plain reference: ``logits``, ``loss``, ``embedding_gradient``
    (contract in its docstring)."""
    from cells import reference

    return reference


# ------------------------------------------------------------- arithmetic

def layer_params(m: dict) -> int:
    """Parameters of one decoder layer: q, k, v, o, gate, up, down, 2 norms."""
    h, hd = m["hidden_size"], head_dim(m)
    q, kv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    return h * q + 2 * h * kv + q * h + 3 * h * m["mlp_dim"] + 2 * h


def num_params(m: dict) -> int:
    embed = m["vocab_size"] * m["hidden_size"]
    head = 0 if m.get("tie_embeddings") else embed
    return (embed + head + m["num_layers"] * layer_params(m)
            + m["hidden_size"])


def train_flops_per_step(m: dict, batch: int, seq: int) -> float:
    """Operations the forward and backward passes need for one step.

    6 per matmul parameter per token (forward 2, backward 4; the
    embedding lookup is not a matmul), plus causal attention
    (``flops.flash_flops_per_step``).  Recomputation is not counted.
    """
    n_matmul = num_params(m) - m["vocab_size"] * m["hidden_size"]
    return (6 * n_matmul * batch * seq
            + flops.flash_flops_per_step(m, batch, seq))


def weight_bytes(m: dict) -> int:
    return num_params(m) * DTYPE_BYTES[m["param_dtype"]]


def kv_bytes_per_token(m: dict) -> int:
    """K and V of one position over all layers, in the cache's type."""
    return (2 * m["num_layers"] * m["num_kv_heads"] * head_dim(m)
            * DTYPE_BYTES[m.get("dtype", "bfloat16")])


def decode_step_bytes(m: dict, live_tokens: float) -> float:
    """Bytes one decode step has to move: every weight once (the
    embedding table is looked up, not read: left out) and the live
    keys and values of the batch once."""
    embed = m["vocab_size"] * m["hidden_size"] * DTYPE_BYTES[m["param_dtype"]]
    return weight_bytes(m) - embed + live_tokens * kv_bytes_per_token(m)
