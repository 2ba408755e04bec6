"""Plain reference: SmallThinker's decoder in ``jax.numpy`` and float32.

Written from the published configuration (``config.json`` of
``PowerInfer/SmallThinker-21BA3B-Instruct``) and the family's published
modelling code, not from ``ray_tpu.models``: it imports nothing of the
program.  No kernel, no cache, no batching, no pools: one sequence, every
query against every key through a masked score matrix (a block of queries
at a time, so that a 14k-position sequence fits a chip), and the expert
layer is a loop over the experts held here, one by one, each computed for
every token and masked.  Matrix multiplications run at
``jax.default_matmul_precision("highest")`` and parameters of a lower
precision are upcast where they are used.

One layer ``l``, ``x`` its input, RMSNorm's epsilon ``rms_norm_eps``::

    p   = softmax(x W_r)           the router reads the LAYER'S INPUT, as it
                                   came (not RMSNorm(x)), before attention
    idx = top_k(p);  w = p[idx] / sum(p[idx])            (norm_topk_prob)
    h   = x + Attn_l(RMSNorm(x))
    out = h + sum_j w_j  W_down[idx_j] (relu(y W_gate[idx_j]) * (y W_up[idx_j])),
          y = RMSNorm(h)

``Attn_l``: grouped queries (``num_heads`` query heads on ``num_kv_heads``
key/value heads, head ``i`` on group ``i // (num_heads / num_kv_heads)``),
scale ``head_dim ** -0.5``, no biases.  ``layer_period[l % len]`` says
which kind the layer is (published ``sliding_window_layout`` =
``rope_layout``: 0 -> ``"full"``, 1 -> ``"window"``):

* ``"full"``: causal over every earlier position, NO rotary embedding;
* ``"window"``: causal over the last ``sliding_window`` positions
  (``q_pos - k_pos < sliding_window``), rotary embedding at ``rope_theta``
  on pairs ``(i, i + head_dim / 2)`` of queries and keys.

No shared expert, no dense layer, an untied head.  **The share**:
``model`` says which experts are held (``first_expert``, ``held_experts``;
None: all) and the parameter tree holds those experts' weights only; the
router keeps its full width and what a picked absent expert would add is
left out.

The parameter tree is the program's own layout, because the comparison is
on the *same* seeded parameters: ``embed [V, H]``; ``layers``, a list of L
layers, each ``{"attn": {norm, w_q [H, nh * hd], w_k, w_v [H, kvh * hd],
w_o [nh * hd, H]}, "router": {w [H, N]}, "ffn_norm", "experts": {w_gate /
w_up [E, H, F], w_down [E, F, H]}}``; ``final_norm [H]``; ``lm_head
[H, V]``.

Assumed where the catalog's ``config`` is silent, as the published
modelling code has them (the configuration file lists each): the router's
input is the un-normalised ``x``; softmax before top-k, then renormalised
over the picks; ReLU gating; rotary pairs ``(i, i + head_dim / 2)``; no
biases; the "secondary experts" of the model's description are not in
``config`` and are not modelled.

The control of the comparison that decides ``correct`` is this file too:
with ``control_dtype`` in ``model`` (tests and the control run only, never
a measured run) every matrix product with a weight rounds both operands to
that 8-bit float first, one scale a tensor; sums stay float32.
"""

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256  # queries a score matrix is made for at a time


def _rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def _mm(a, w, model):
    w = w.astype(jnp.float32)
    dtype = model.get("control_dtype")
    if dtype is None:
        return a @ w

    def rounded(x):
        scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
        return (x / scale).astype(dtype).astype(jnp.float32) * scale
    return rounded(a) @ rounded(w)


def _rope(x, theta):
    """x ``[s, heads, d]``: pairs ``(i, i + d/2)`` rotated by
    ``pos * theta^(-2i/d)``."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _eps(model):
    return model.get("rms_norm_eps", 1e-6)


def kind_of(model, l):
    period = model.get("layer_period") or ["full", "window", "window",
                                           "window"]
    return period[l % len(period)]


def attention(x, ap, kind, model):
    """x ``[s, H]`` (normed) -> ``[s, H]``."""
    s = x.shape[0]
    nh, kvh, hd = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    q = _mm(x, ap["w_q"], model).reshape(s, nh, hd)
    k = _mm(x, ap["w_k"], model).reshape(s, kvh, hd)
    v = _mm(x, ap["w_v"], model).reshape(s, kvh, hd)
    if kind == "window":
        q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    blk = min(QUERY_BLOCK, s)
    pad = (-s) % blk
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    q = q.reshape(-1, blk, kvh, nh // kvh, hd)  # [blocks, blk, g, r, d]
    first = jnp.arange(0, s + pad, blk)
    kpos = jnp.arange(s)

    def block(args):
        qb, q0 = args
        qpos = q0 + jnp.arange(blk)
        scores = jnp.einsum("qgrd,kgd->grqk", qb, k) / jnp.sqrt(
            jnp.float32(hd))
        seen = qpos[:, None] >= kpos[None, :]
        if kind == "window":
            seen &= qpos[:, None] - kpos[None, :] < model["sliding_window"]
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(scores, axis=-1),
                          v)

    out = jax.lax.map(block, (q, first)).reshape(s + pad, nh * hd)[:s]
    return _mm(out, ap["w_o"], model)


def route(x, router, model):
    """x ``[s, H]``, the layer's input -> (picked ``[s, k]``, weight
    ``[s, k]``)."""
    p = jax.nn.softmax(_mm(x, router["w"], model), axis=-1)
    weight, picked = jax.lax.top_k(p, model["experts_per_token"])
    return picked, weight / jnp.sum(weight, axis=-1, keepdims=True)


def experts(y, picked, weight, ep, model):
    """What the held experts add, ``[s, H]``; ``ep`` their leaves."""
    first = model.get("first_expert", 0)

    def one(e, acc):  # every held expert computes every token: plain
        w_e = jnp.sum(jnp.where(picked == first + e, weight, 0.0), axis=-1)
        gate = jnp.maximum(_mm(y, ep["w_gate"][e], model), 0.0)
        return acc + w_e[:, None] * _mm(
            gate * _mm(y, ep["w_up"][e], model), ep["w_down"][e], model)

    return jax.lax.fori_loop(0, ep["w_gate"].shape[0], one,
                             jnp.zeros_like(y))


def layer(x, lp, kind, model):
    eps = _eps(model)
    picked, weight = route(x, lp["router"], model)
    h = x + attention(_rms_norm(x, lp["attn"]["norm"], eps), lp["attn"],
                      kind, model)
    y = _rms_norm(h, lp["ffn_norm"], eps)
    return h + experts(y, picked, weight, lp["experts"], model)


def logits(params, tokens, model):
    """tokens ``[s]`` int32 -> logits ``[s, vocab]`` float32, one
    sequence."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for l, lp in enumerate(params["layers"]):
            x = layer(x, lp, kind_of(model, l), model)
        x = _rms_norm(x, params["final_norm"], _eps(model))
        return _mm(x, params["lm_head"], model)


def loss(params, tokens, model):
    """Mean next-token cross-entropy of one sequence, tokens ``[s + 1]``."""
    logp = jax.nn.log_softmax(logits(params, tokens[:-1], model), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
