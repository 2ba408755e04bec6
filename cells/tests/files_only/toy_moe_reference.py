"""Fixture: the plain reference of the toy expert model (contract:
``cells/reference.py``'s docstring).  The block is ``cells/reference.py``'s
with the MLP replaced by a router and experts: softmax over the router's
logits, the ``experts_per_token`` largest kept and renormalised to sum to
one, each kept expert a SwiGLU.  Every expert is computed for every token
and weighted (by zero where not kept): plain, not fast.  Imports nothing
of the program.
"""

import jax
import jax.numpy as jnp

EPS = 1e-6


def _rms_norm(x, scale):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + EPS) * scale


def _rope(x, theta):
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, model):
    s = x.shape[0]
    nh, nkv, hd = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    theta = model.get("rope_theta", 10000.0)
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    y = _rms_norm(x, lp["attn_norm"])
    q = _rope((y @ lp["wq"]).reshape(s, nh, hd), theta)
    k = _rope((y @ lp["wk"]).reshape(s, nkv, hd), theta)
    v = (y @ lp["wv"]).reshape(s, nkv, hd)
    k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (k, v))
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(s, nh * hd) @ lp["wo"]

    y = _rms_norm(x, lp["mlp_norm"])
    probs = jax.nn.softmax(y @ lp["w_router"], axis=-1)  # [s, E]
    kept = model["experts_per_token"]
    floor = jax.lax.top_k(probs, kept)[0][:, -1:]  # the kept-th largest
    weight = jnp.where(probs >= floor, probs, 0.0)
    weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    for e in range(model["num_experts"]):
        gate = y @ lp["w_gate"][e]
        expert = (jax.nn.sigmoid(gate) * gate * (y @ lp["w_up"][e])) \
            @ lp["w_down"][e]
        x = x + weight[:, e:e + 1] * expert
    return x


def _from_embeddings(params, x, model):
    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(lambda x, lp: (_layer(x, lp, model), None), x,
                            params["layers"])
        x = _rms_norm(x, params["final_norm"].astype(jnp.float32))
        return x @ params["lm_head"].astype(jnp.float32)


def _nll(lg, targets):
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def logits(params, tokens, model):
    return _from_embeddings(
        params, params["embed"][tokens].astype(jnp.float32), model)


def loss(params, tokens, model):
    return _nll(logits(params, tokens[:-1], model), tokens[1:])


def embedding_gradient(params, tokens, model):
    x = params["embed"][tokens[:-1]].astype(jnp.float32)
    return jax.grad(lambda x: _nll(_from_embeddings(params, x, model),
                                   tokens[1:]))(x)
