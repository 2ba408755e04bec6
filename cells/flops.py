"""The yardstick's arithmetic: peaks, parameter counts, operations and bytes.

Copied from ``bench.py`` (``PEAK_FLOPS``, ``peak_flops_per_chip``,
``train_flops_per_step``) so that no later PR can move a utilisation by
editing the program's copy.  Everything here is computed from a
configuration file's ``model`` group (a plain dict) and a cell's shapes;
nothing imports ``ray_tpu`` or ``jax``.
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip.  Unknown kind: an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks on record for device_kind {device_kind!r}; add it "
            f"to cells/peaks.json with its source")
    return table[device_kind]


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_heads"]


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
    embedding lookup is not a matmul), plus causal attention: QK^T and
    PV are 4*s*s*h*hd forward and 8 backward per sequence and layer,
    halved by the causal mask.  Recomputation is not counted.
    """
    n_matmul = num_params(m) - m["vocab_size"] * m["hidden_size"]
    dense = 6 * n_matmul * batch * seq
    attn = (12 * m["num_layers"] * batch * seq * seq * m["num_heads"]
            * head_dim(m) * 0.5)
    return dense + attn


def flash_flops_per_step(m: dict, batch: int, seq: int) -> float:
    """Operations of the flash kernels alone (forward, dQ, dK/dV): the
    attention term of ``train_flops_per_step``."""
    return (12 * m["num_layers"] * batch * seq * seq * m["num_heads"]
            * head_dim(m) * 0.5)


def flash_bytes_per_step(m: dict, batch: int, seq: int) -> float:
    """Least HBM traffic of the three kernels, activations in bf16:
    forward reads q,k,v and writes o; dQ reads q,k,v,o,dO and writes dq;
    dK/dV reads q,k,v,o,dO and writes dk,dv."""
    hd = head_dim(m)
    q = batch * seq * m["num_heads"] * hd * 2
    kv = batch * seq * m["num_kv_heads"] * hd * 2
    fwd = q + 2 * kv + q
    dq = 3 * q + 2 * kv + q
    dkv = 3 * q + 2 * kv + 2 * kv
    return m["num_layers"] * (fwd + dq + dkv)


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
