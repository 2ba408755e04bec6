"""The yardstick's arithmetic that every family shares: the chip's peaks
and the flash kernels' operations and bytes (attention alone, whatever the
layer around it).  Parameter counts, a step's operations and a decode
step's bytes depend on the architecture: ``cells/families/<name>.py``.

Copied from ``bench.py`` (``PEAK_FLOPS``, ``peak_flops_per_chip``) so that
no later PR can move a utilisation by editing the program's copy.
Everything here is computed from a configuration file's ``model`` group
(a plain dict) and a cell's shapes; nothing imports ``ray_tpu`` or ``jax``.
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


def flash_flops_per_step(m: dict, batch: int, seq: int) -> float:
    """Operations of the flash kernels alone (forward, dQ, dK/dV): QK^T
    and PV are 4*s*s*h*hd forward and 8 backward per sequence and layer,
    halved by the causal mask.  The attention term of a family's
    ``train_flops_per_step``."""
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
