"""Plain reference: LongCat-Flash's language model in ``jax.numpy`` and
float32.

Written from the published configuration (``config.json`` of
``meituan-longcat/LongCat-Flash-Omni``) and the family's published
modelling code, not from ``ray_tpu.models``: it imports nothing of the
program.  No kernel, no cache, no batching, no absorbed form: one sequence,
every position attends to every earlier one through a full score matrix of
up-projected keys (one head at a time), and the expert layer is a Python
loop over the experts held here, each computed for every token and masked.
Matrix multiplications run at ``jax.default_matmul_precision("highest")``
and parameters of a lower precision are upcast where they are used.

With ``h`` the residual stream and RMSNorm's epsilon ``rms_norm_eps``:

*Latent attention* ``MLA_i(x)``: ``c_q = RMSNorm(x W_qa) sqrt(H / q_rank)``;
``q = c_q W_qb`` -> heads x (nope | rope); ``[c_kv | k_pe] = x W_kva``;
``c_kv = RMSNorm(c_kv) sqrt(H / kv_rank)``; ``[k_nope | v] = c_kv W_kvb`` ->
heads x (nope | v); rotary on ``q_pe`` and on ``k_pe`` (shared by the
heads, not scaled); scores ``(q_nope.k_nope + q_pe.k_pe) / sqrt(nope +
rope)``, causal softmax, ``concat_heads(P v) W_o``.

*One (double) layer*::

    h1 = h  + MLA_0(norm_in0(h));   y = norm_post0(h1);   s = MoE(y)
    h2 = h1 + FFN_0(y)
    h3 = h2 + MLA_1(norm_in1(h2))
    h' = h3 + FFN_1(norm_post1(h3)) + s

*MoE(y)*: ``p = softmax(y W_r)`` over routed + zero-compute experts; the
``experts_per_token`` largest of ``p + b`` are chosen; a chosen expert's
weight is its ``p`` (no bias, not renormalised) times
``routed_scaling_factor``; a routed expert gives ``SwiGLU_e(y)``, a
zero-compute expert gives ``y``.  **The share**: ``model`` says which routed
experts are held (``first_expert``, ``held_experts``; None: all) and the
parameter tree holds those experts' weights only.  The router keeps its
full width; what a chosen absent expert would add is left out; the
zero-compute experts' part is whole.

The parameter tree is the program's own layout, because the comparison is
on the *same* seeded parameters: ``embed [V, H]``; ``layers``, a list of the
L double layers, each ``{"attn": [2 x {norm, w_qa, q_norm, w_qb, w_kva,
kv_norm, w_kvb, w_o}], "ffn": [2 x {norm, w_gate, w_up, w_down}], "router":
{w [H, N], bias [N]}, "experts": {w_gate / w_up [E, H, F], w_down [E, F,
H]}}``; ``final_norm [H]``; ``lm_head [H, V]``.

Departures from the published model: none in the mathematics.  The rotary
pairs: the published code rotates columns ``(2i, 2i+1)``; the tree stores
those columns de-interleaved (published column ``2i`` at ``i``, ``2i+1`` at
``i + d/2``), so ``_rope`` puts them back in the published order and rotates
the published pairs.  Assumed where the catalog is silent, as the published
modelling code has them: no renormalisation of the chosen weights, no bias
on the router's logits.

The control of the comparison that decides ``correct`` is this file too:
with ``control_dtype`` in ``model`` (tests and the control run only, never
a measured run) every matrix product with a weight rounds both operands to
that 8-bit float first, one scale a tensor; sums stay float32.
"""

import jax
import jax.numpy as jnp


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
    """x ``[s, ..., d]`` as stored (published column 2i at i, 2i+1 at
    i + d/2): back to the published order, pairs ``(2i, 2i+1)`` rotated by
    ``pos * theta^(-2i/d)``."""
    s, d = x.shape[0], x.shape[-1]
    x = jnp.stack([x[..., :d // 2], x[..., d // 2:]], axis=-1)  # [.., i, 2]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape(s, *([1] * (x.ndim - 3)), d // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0], x[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(*out.shape[:-2], d)


def _mla(x, ap, model):
    s, H = x.shape
    nh = model["num_heads"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    qr, kr, eps = model["q_lora_rank"], model["kv_lora_rank"], _eps(model)
    c_q = _rms_norm(_mm(x, ap["w_qa"], model), ap["q_norm"], eps)
    if model.get("mla_scale_q_lora", True):
        c_q = c_q * (H / qr) ** 0.5
    q = _mm(c_q, ap["w_qb"], model).reshape(s, nh, dn + dr)
    kv = _mm(x, ap["w_kva"], model)
    c_kv = _rms_norm(kv[:, :kr], ap["kv_norm"], eps)
    if model.get("mla_scale_kv_lora", True):
        c_kv = c_kv * (H / kr) ** 0.5
    kvb = _mm(c_kv, ap["w_kvb"], model).reshape(s, nh, dn + dv)
    q_pe = _rope(q[..., dn:], model["rope_theta"])
    k_pe = _rope(kv[:, kr:], model["rope_theta"])  # one for all heads
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    def head(args):  # one head's score matrix at a time
        q_nope, q_rot, k_nope, v = args
        scores = (q_nope @ k_nope.T + q_rot @ k_pe.T) / jnp.sqrt(
            jnp.float32(dn + dr))
        scores = jnp.where(causal, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v

    out = jax.lax.map(head, (
        q[..., :dn].transpose(1, 0, 2), q_pe.transpose(1, 0, 2),
        kvb[..., :dn].transpose(1, 0, 2), kvb[..., dn:].transpose(1, 0, 2)))
    return _mm(out.transpose(1, 0, 2).reshape(s, nh * dv), ap["w_o"], model)


def _swiglu(x, w_gate, w_up, w_down, model):
    gate = _mm(x, w_gate, model)
    return _mm(jax.nn.sigmoid(gate) * gate * _mm(x, w_up, model), w_down,
               model)


def _eps(model):
    return model.get("rms_norm_eps", 1e-5)


def route(y, router, model):
    """y ``[s, H]`` -> (chosen ``[s, k]``, weight ``[s, k]``)."""
    p = jax.nn.softmax(_mm(y, router["w"], model), axis=-1)
    kept = model["experts_per_token"]
    _, chosen = jax.lax.top_k(p + router["bias"].astype(jnp.float32), kept)
    weight = jnp.take_along_axis(p, chosen, axis=-1)
    return chosen, weight * model["routed_scaling_factor"]


def moe_parts(y, router, ep, model):
    """(what the held routed experts add, what the zero-compute experts
    add), each ``[s, H]``; ``router`` the router's leaves, ``ep`` the held
    experts'."""
    chosen, weight = route(y, router, model)
    first = model.get("first_expert", 0)
    held = ep["w_gate"].shape[0]
    routed = jnp.zeros_like(y)
    for e in range(held):  # every held expert computes every token: plain
        w_e = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), axis=-1)
        routed += w_e[:, None] * _swiglu(
            y, ep["w_gate"][e], ep["w_up"][e], ep["w_down"][e], model)
    w_zero = jnp.sum(jnp.where(chosen >= model["num_experts"], weight, 0.0),
                     axis=-1)
    return routed, w_zero[:, None] * y  # zero_expert_type: identity


def moe(y, router, ep, model):
    routed, zero = moe_parts(y, router, ep, model)
    return routed + zero


def layer(h, lp, model):
    """One double layer, ``lp`` its leaves."""
    eps = _eps(model)
    at, ff = lp["attn"], lp["ffn"]
    h1 = h + _mla(_rms_norm(h, at[0]["norm"], eps), at[0], model)
    y = _rms_norm(h1, ff[0]["norm"], eps)
    s = moe(y, lp["router"], lp["experts"], model)
    h2 = h1 + _swiglu(y, ff[0]["w_gate"], ff[0]["w_up"], ff[0]["w_down"],
                      model)
    h3 = h2 + _mla(_rms_norm(h2, at[1]["norm"], eps), at[1], model)
    return h3 + _swiglu(_rms_norm(h3, ff[1]["norm"], eps), ff[1]["w_gate"],
                        ff[1]["w_up"], ff[1]["w_down"], model) + s


def logits(params, tokens, model):
    """tokens ``[s]`` int32 -> logits ``[s, vocab]`` float32, one
    sequence."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for lp in params["layers"]:
            x = layer(x, lp, model)
        x = _rms_norm(x, params["final_norm"], _eps(model))
        return _mm(x, params["lm_head"], model)


def loss(params, tokens, model):
    """Mean next-token cross-entropy of one sequence, tokens ``[s + 1]``."""
    logp = jax.nn.log_softmax(logits(params, tokens[:-1], model), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
