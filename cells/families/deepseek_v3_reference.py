"""Plain reference: DeepSeek-V3's family (``model_type`` ``deepseek_v3``;
here GigaChat3.1-702B-A36B) in ``jax.numpy`` and float32.

Written from the published configuration (``config.json`` of
``ai-sage/GigaChat3.1-702B-A36B``) and the family's published modelling
code, not from ``ray_tpu.models``: it imports nothing of the program.  No
kernel, no cache, no batching, no absorbed form: one sequence, every
position attends to every earlier one through score matrices of
up-projected keys, and the expert layer is a Python loop over the experts
held here, each computed for every token and masked.  Matrix
multiplications run at ``jax.default_matmul_precision("highest")`` and
parameters of a lower precision are upcast where they are used.

With ``h`` the residual stream, ``H`` the hidden size and RMSNorm's epsilon
``rms_norm_eps`` (``model`` is the configuration's ``model`` group; its
``rope_scaling`` is the source's own group):

*Latent attention* ``MLA(x)``: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` ->
heads x (nope | rope); ``[kv | k_pe] = x W_kva``; ``c_kv = RMSNorm(kv)``;
``[k_nope | v] = c_kv W_kvb`` -> heads x (nope | v_head_dim); rotary on
``q_pe`` and on ``k_pe`` (shared by the heads); scores ``(q_nope.k_nope +
q_pe.k_pe) * scale``, causal softmax, ``concat_heads(P v) W_o``.  No factor
on the latents (LongCat's ``mla_scale_*`` are its own).

*YaRN* (``rope_scaling``: ``factor``, ``original_max_position_embeddings``,
``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``), over the
``d / 2`` rotary pairs of the ``d = qk_rope_head_dim`` columns: ``f_i =
theta^(-2i/d)``; ``pair(t) = d ln(original / (2 pi t)) / (2 ln theta)``;
``low = floor(pair(beta_fast))``, ``high = ceil(pair(beta_slow))`` (clipped
to ``0 .. d - 1``); ``ramp_i = clip((i - low) / (high - low), 0, 1)``;
``inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i``.  Cos and sin are
scaled by ``m(mscale) / m(mscale_all_dim)`` with ``m(x) = 0.1 x ln(factor) +
1``; the softmax scale is ``(nope + rope)^-0.5 * m(mscale_all_dim)^2``.
Both act at every position.  At the published settings (theta 1e5, factor
64, original 4096, d 64): low 8, high 19, the cos/sin factor 1, the scale
0.144680.

*Router*: ``s = sigmoid(y W_g)`` over all ``num_experts``; ``c = s + b``
(``e_score_correction_bias``); a group (``n_group`` runs of consecutive
experts) scores the sum of its two largest ``c``; the ``topk_group`` best
groups stay, the others' ``c`` become ``-inf``; the ``experts_per_token``
largest ``c`` are the picks; a pick weighs its own ``s`` over the sum of
the picked ``s`` (``norm_topk_prob``), times ``routed_scaling_factor``.

*Expert layer*: ``sum_picks w_e SwiGLU_e(y) + SwiGLU_shared(y)``.  **The
share**: ``model`` says which routed experts are held (``first_expert``;
the tree holds those experts' weights only).  The router keeps its full
width; what a chosen absent expert would add is left out; the shared
expert's part is whole.

*Stack*: ``h += MLA(RMSNorm(h)); h += FFN(RMSNorm(h))``, FFN a dense SwiGLU
in the leading layers (those whose leaves are ``ffn``) and the expert layer
in the others (``moe``); final norm, untied head.  The multi-token-
prediction block (``num_nextn_predict_layers``) is not part of the logits
(the published modelling code drops its weights at load) and is not here.

The parameter tree is the program's own layout, because the comparison is
on the *same* seeded parameters: ``embed [V, H]``; ``layers``, a list, each
``{"attn": {norm, w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, w_o}}`` beside
``"ffn": {norm, w_gate, w_up, w_down}`` or ``"moe": {norm, router: {w [H, N],
bias [N]}, experts: {w_gate / w_up [E, H, F], w_down [E, F, H]}, shared:
{w_gate, w_up, w_down}}``; ``final_norm [H]``; ``lm_head [H, V]``.  (The
program's derived ``w_uk`` / ``w_uv`` are not read.)

Departures from the published code, each marked where it is made: the
rotary columns are stored de-interleaved and put back here; a dropped
group's choices are ``-inf`` (the family's own inference code; the Hugging
Face port writes 0.0, which picks the same wherever ``s + b > 0``);
attention runs a head and a block of queries at a time (the same sums, so
that a 5120-position sample fits a chip beside the weights).

The control of the comparison that decides ``correct`` is this file too:
with ``control_dtype`` in ``model`` (tests and the control run only, never
a measured run) every matrix product with a weight rounds both operands to
that 8-bit float first, one scale a tensor; sums stay float32.
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024  # queries a score matrix is made for at a time


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


# ------------------------------------------------------------------ YaRN

def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_range(d, theta, rs):
    """(low, high): the rotary pairs between which the blend runs."""
    def pair(turns):
        return (d * math.log(rs["original_max_position_embeddings"]
                             / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(pair(rs["beta_fast"])), 0),
            min(math.ceil(pair(rs["beta_slow"])), d - 1))


def yarn_inv_freq(model):
    """``[d / 2]``: each rotary pair's frequency."""
    d, theta = model["qk_rope_head_dim"], model["rope_theta"]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    f = theta ** (-2 * i / d)
    rs = model.get("rope_scaling")
    if not rs:
        return f
    low, high = yarn_range(d, theta, rs)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return f * (1 - ramp) + f / rs["factor"] * ramp


def softmax_scale(model):
    scale = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    rs = model.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        scale *= yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def _rope(x, model):
    """x ``[s, ..., d]`` as stored (published column 2i at i, 2i+1 at
    i + d/2; departure: the program's layout): back to the published order,
    pairs ``(2i, 2i+1)`` rotated by ``pos * inv_freq_i``."""
    s, d = x.shape[0], x.shape[-1]
    x = jnp.stack([x[..., :d // 2], x[..., d // 2:]], axis=-1)  # [.., i, 2]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * yarn_inv_freq(model)
    ang = ang.reshape(s, *([1] * (x.ndim - 3)), d // 2)
    rs = model.get("rope_scaling")
    m = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(
        rs["factor"], rs["mscale_all_dim"]) if rs else 1.0
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    even, odd = x[..., 0], x[..., 1]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(*out.shape[:-2], d)


# -------------------------------------------------------------- attention

def _mla(x, ap, model):
    s = x.shape[0]
    nh = model["num_heads"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    kr, eps = model["kv_lora_rank"], model["rms_norm_eps"]
    c_q = _rms_norm(_mm(x, ap["w_qa"], model), ap["q_norm"], eps)
    q = _mm(c_q, ap["w_qb"], model).reshape(s, nh, dn + dr)
    kv = _mm(x, ap["w_kva"], model)
    c_kv = _rms_norm(kv[:, :kr], ap["kv_norm"], eps)
    kvb = _mm(c_kv, ap["w_kvb"], model).reshape(s, nh, dn + dv)
    q_pe = _rope(q[..., dn:], model)
    k_pe = _rope(kv[:, kr:], model)  # one for all heads
    scale = softmax_scale(model)
    # departure: a block of queries at a time (the same sums)
    qb = QUERY_BLOCK if s > QUERY_BLOCK and s % QUERY_BLOCK == 0 else s
    keys = jnp.arange(s)

    def head(args):  # one head's score matrices, a block of queries each
        q_nope, q_rot, k_nope, v = args

        def block(b):
            first, qn, qr = b
            scores = (qn @ k_nope.T + qr @ k_pe.T) * scale
            causal = (first + jnp.arange(qb))[:, None] >= keys[None, :]
            scores = jnp.where(causal, scores, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ v

        out = jax.lax.map(block, (jnp.arange(0, s, qb),
                                  q_nope.reshape(s // qb, qb, dn),
                                  q_rot.reshape(s // qb, qb, dr)))
        return out.reshape(s, dv)

    out = jax.lax.map(head, (
        q[..., :dn].transpose(1, 0, 2), q_pe.transpose(1, 0, 2),
        kvb[..., :dn].transpose(1, 0, 2), kvb[..., dn:].transpose(1, 0, 2)))
    return _mm(out.transpose(1, 0, 2).reshape(s, nh * dv), ap["w_o"], model)


# ---------------------------------------------------------------- experts

def _swiglu(x, p, model, e=None):
    """``p``'s gate, up and down (expert ``e`` of a stacked triple)."""
    w = (lambda n: p[n]) if e is None else (lambda n: p[n][e])
    gate = _mm(x, w("w_gate"), model)
    return _mm(jax.nn.sigmoid(gate) * gate * _mm(x, w("w_up"), model),
               w("w_down"), model)


def route(y, router, model):
    """y ``[s, H]`` -> (chosen ``[s, k]``, weight ``[s, k]``, kept ``[s,
    n_group]`` bool)."""
    n_group, topk_group = model["n_group"], model["topk_group"]
    score = jax.nn.sigmoid(_mm(y, router["w"], model))
    choice = score + router["bias"].astype(jnp.float32)
    groups = choice.reshape(y.shape[0], n_group, -1)
    group_score = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)
    kept = group_score >= jnp.sort(group_score, axis=-1)[:, -topk_group, None]
    # departure: -inf, not the Hugging Face port's 0.0 (same picks: c > 0)
    choice = jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(
        choice.shape)
    _, chosen = jax.lax.top_k(choice, model["experts_per_token"])
    weight = jnp.take_along_axis(score, chosen, axis=-1)
    if model.get("norm_topk_prob", True):
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return chosen, weight * model["routed_scaling_factor"], kept


def moe_parts(y, mp, model):
    """(what the held routed experts add, what the shared expert adds),
    each ``[s, H]``; ``mp`` an expert layer's ``moe`` leaves."""
    chosen, weight, _ = route(y, mp["router"], model)
    first = model.get("first_expert", 0)
    ep = mp["experts"]
    routed = jnp.zeros_like(y)
    for e in range(ep["w_gate"].shape[0]):  # every held expert, every token
        w_e = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), axis=-1)
        routed += w_e[:, None] * _swiglu(y, ep, model, e)
    return routed, _swiglu(y, mp["shared"], model)


def layer(h, lp, model):
    """One layer, ``lp`` its leaves."""
    eps = model["rms_norm_eps"]
    h = h + _mla(_rms_norm(h, lp["attn"]["norm"], eps), lp["attn"], model)
    if "ffn" in lp:  # a leading dense layer
        return h + _swiglu(_rms_norm(h, lp["ffn"]["norm"], eps), lp["ffn"],
                           model)
    routed, shared = moe_parts(_rms_norm(h, lp["moe"]["norm"], eps),
                               lp["moe"], model)
    return h + routed + shared


def logits(params, tokens, model):
    """tokens ``[s]`` int32 -> logits ``[s, vocab]`` float32, one
    sequence."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for lp in params["layers"]:
            x = layer(x, lp, model)
        x = _rms_norm(x, params["final_norm"], model["rms_norm_eps"])
        return _mm(x, params["lm_head"], model)


def loss(params, tokens, model):
    """Mean next-token cross-entropy of one sequence, tokens ``[s + 1]``."""
    logp = jax.nn.log_softmax(logits(params, tokens[:-1], model), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
