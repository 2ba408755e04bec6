"""Fixture, not a supported model: what a ``model_config`` PR adds for a
second family, at ``MoEConfig.tiny_moe`` size.  ``test_cells.py`` copies it
to ``cells/families/toy_moe.py`` of a copy of the benchmark and edits no
file that was there.  Wraps the program's ``ray_tpu.models.moe`` (top-k
softmax routing, dense dispatch); the router's auxiliary loss is switched
off in the configuration (``router_aux_coef`` 0) so that the step's loss
is the reference's cross-entropy.
"""

from cells import flops
from cells.flops import DTYPE_BYTES, head_dim

TOY_MODEL = {
    "vocab_size": 256, "hidden_size": 64, "num_layers": 2, "num_heads": 4,
    "num_kv_heads": 2, "head_dim": 16, "mlp_dim": 128, "max_seq_len": 128,
    "num_experts": 4, "experts_per_token": 2, "router_aux_coef": 0.0,
    "dtype": "float32", "param_dtype": "float32", "attention_impl": "auto"}

SOURCE_KEYS = {
    "hidden_size": "hidden_size", "intermediate_size": "mlp_dim",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "vocab_size": "vocab_size",
    "num_experts": "num_experts", "num_experts_per_tok": "experts_per_token",
    "num_hidden_layers": "num_layers",
    "max_position_embeddings": "max_seq_len"}
WIDTHS = frozenset(SOURCE_KEYS) - {"num_hidden_layers",
                                   "max_position_embeddings"}


def config(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models.moe import MoEConfig

    kw = dict(model)
    for key in ("dtype", "param_dtype"):
        kw[key] = jnp.dtype(kw[key])
    return MoEConfig(**kw)


def init(key, cfg):
    from ray_tpu.models.moe import moe_init

    return moe_init(key, cfg)


def apply(params, tokens, cfg, mesh):
    from ray_tpu.models.moe import moe_apply

    return moe_apply(params, tokens, cfg, mesh=mesh)[0]


def make_trainer(cfg, mesh, optimizer: dict):
    from ray_tpu.models.moe import make_moe_trainer
    from ray_tpu.models.training import default_optimizer

    return make_moe_trainer(cfg, mesh,
                            optimizer=default_optimizer(**optimizer))


def reference():
    from cells.families import toy_moe_reference

    return toy_moe_reference


# arithmetic: a token's matmuls touch its own experts only

def _layer_params(m: dict, experts: int) -> int:
    h, hd = m["hidden_size"], head_dim(m)
    q, kv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    return (h * q + 2 * h * kv + q * h + h * m["num_experts"]
            + 3 * experts * h * m["mlp_dim"] + 2 * h)


def num_params(m: dict, experts=None) -> int:
    embed = m["vocab_size"] * m["hidden_size"]
    return (2 * embed + m["hidden_size"] + m["num_layers"] * _layer_params(
        m, m["num_experts"] if experts is None else experts))


def train_flops_per_step(m: dict, batch: int, seq: int) -> float:
    active = num_params(m, m["experts_per_token"]) \
        - m["vocab_size"] * m["hidden_size"]
    return (6 * active * batch * seq
            + flops.flash_flops_per_step(m, batch, seq))


def weight_bytes(m: dict) -> int:
    return num_params(m) * DTYPE_BYTES[m["param_dtype"]]


def kv_bytes_per_token(m: dict) -> int:
    return (2 * m["num_layers"] * m["num_kv_heads"] * head_dim(m)
            * DTYPE_BYTES[m["dtype"]])


def decode_step_bytes(m: dict, live_tokens: float) -> float:
    embed = m["vocab_size"] * m["hidden_size"] * DTYPE_BYTES[m["param_dtype"]]
    return weight_bytes(m) - embed + live_tokens * kv_bytes_per_token(m)
