"""Plain reference: GigaChat3.5's family (``model_type`` ``gigachat3_5``;
here GigaChat3.5-432B-A28B) in ``jax.numpy`` and float32.

Written from the published configuration (``config.json`` of
``ai-sage/GigaChat3.5-432B-A28B``), the gated delta rule's paper (Yang et
al., arXiv:2412.06464) and the projection layout of Qwen3-Next's published
modelling code, not from ``ray_tpu.models``: it imports nothing of the
program.  No kernel, no cache, no batching, no chunked scan, no absorbed
form: one sequence, the linear-attention recurrence position by position,
every position of a latent block attending to every earlier one through
score matrices of up-projected keys, and the expert layer a Python loop over
the experts held here, each computed for every token and masked.  Matrix
multiplications run at ``jax.default_matmul_precision("highest")`` and
parameters of a lower precision are upcast where they are used.

``x`` is the residual stream (width ``H``), ``model`` the configuration's
``model`` group (``rope_scaling`` the source's own group), layer ``l`` of
``hidden_layers``:

*Layer.*  ``x += N_post(Mixer_l(N_pre(x)))``, then ``x += N'_post(FFN_l(
N'_pre(x)))``.  ``Mixer_l`` is the latent block for ``l`` in
``full_attention_layers`` and Gated DeltaNet otherwise; ``FFN_l`` is the
dense SwiGLU (``ffn_dim``) for ``l < dense_layers``, else the expert layer.
A final norm, an untied head.

*Norm*: ``N(x) = x / rms(x; rms_norm_eps) * g sigmoid(w)``, ``g`` =
``norm_gate_scale`` (2), ``w`` a vector that is 0 at initialisation.

*Gated DeltaNet* (``Hk = linear_key_heads``, ``Hv = linear_value_heads``,
``d`` the head width, ``u = N_pre(x)``): ``[q~ k~ v~] = u W_qkv`` (``Hk d``,
``Hk d``, ``Hv d`` columns), ``z = u W_z``, ``[b a] = u W_ba``.  ``[q^ k^
v^] = SiLU(causal depthwise convolution, linear_conv_kernel taps, no bias,
over the channels of [q~ k~ v~])``.  A head: ``q = q^ / ||q^|| / sqrt(d)``,
``k = k^ / ||k^||``; key head ``j`` serves value heads ``j Hv/Hk .. (j + 1)
Hv/Hk - 1``.  ``beta_t = sigmoid(b_t)``, ``alpha_t = exp(-exp(A_log)
softplus(a_t + dt_bias))`` a value head.  State ``S [d, d]`` a value head,
zero at the sequence's start: ``S'_t = alpha_t S_{t-1}``; ``S_t = S'_t + k_t
(beta_t (v_t - S'_t^T k_t))^T``; ``o_t = S_t^T q_t``.  Output: ``y_t = o_t /
rms(o_t over d; linear_norm_eps) * g' sigmoid(w_o) * g' sigmoid(z_t)``, ``g'``
= ``linear_gate_scale`` (2); ``Mixer = y W_o``.

*Latent block*: DeepSeek-V3's (``cells/families/deepseek_v3_reference.py``
writes it out, YaRN and its softmax scale included; its helpers are used
here) with a gate: ``out = (attn * sigmoid(u W_g)) W_o``.

*Expert layer*: DeepSeek-V3's without a group limit: ``s = sigmoid(y
W_r)``, the ``experts_per_token`` largest ``s + bias`` are the picks, a pick
weighs its own ``s`` over the sum of the picked (``norm_topk_prob``) times
``routed_scaling_factor``; ``sum_picks w_e SwiGLU_e(y) + SwiGLU_shared(y)``.
**The share**: ``model`` says which routed experts are held
(``first_expert``; the tree holds those experts' weights only); what a
chosen absent expert would add is left out; the shared expert's part is
whole.  *Every SwiGLU*: ``silu(min(g, L)) * clip(u, -L, L)``, then down,
``L`` = ``swiglu_limit`` (10).

**Assumed readings** (six keys of the source name element-wise parts whose
text is in the model's modelling file, which the catalog does not hold; each
is read as its name and value fix it, and the configuration lists the same
under ``assumed``):

1. ``layernorm_type: pre_post``: four norms a layer, as *Layer* above.
2. ``norm_type: ZeroCenteredGatedNorm`` with ``layernorm_gating_weight`` 2:
   *Norm* above (the norms on the two latents inside the latent block stay
   DeepSeek-V3's plain RMSNorm with a scale).
3. ``gated_attention: true``: Qwen3-Next's form, a sigmoid gate of the
   block's input on the heads' outputs before ``W_o``.
4. ``linear_gating_type: gated_rmsnorm_sigmoid_zero_centered`` with
   ``linear_sigmoid_gate_scale`` 2 and ``linear_attn_o_norm_eps``: the
   output rule of *Gated DeltaNet* above.
5. ``swiglu_limit: 10``: the clamp of *Every SwiGLU* above.
6. ``use_mla_scaling_factor: true``: DeepSeek-V3's softmax scale, ``(nope +
   rope)^-0.5 * (0.1 mscale_all_dim ln(factor) + 1)^2``.

The multi-token-prediction modules (``num_nextn_predict_layers``) are not
part of the logits and are not here.

The parameter tree is the program's own layout, because the comparison is on
the *same* seeded parameters: ``embed [V, H]``; ``layers``, a list, each
``{"norms": {pre_mix, post_mix, pre_ffn, post_ffn}}`` beside ``"attn":
{w_qa, q_norm, w_qb, w_kva, kv_norm, w_kvb, w_g, w_o}`` or ``"gdn": {w_qkv,
w_z, w_ba, conv_w [K, C], A_log, dt_bias, o_norm, w_o}`` and ``"ffn":
{w_gate, w_up, w_down}`` or ``"moe": {router: {w, bias}, experts, shared}``;
``final_norm``; ``lm_head [H, V]``.  (The program's derived ``w_uk`` /
``w_uv`` are not read.)  Departures, as DeepSeek-V3's reference: the rotary
columns are stored de-interleaved and put back; attention runs a head and a
block of queries at a time (the same sums).

The control of the comparison that decides ``correct`` is this file too:
with ``control_dtype`` in ``model`` (tests and the control run only, never
a measured run) every matrix product with a weight rounds both operands to
that 8-bit float first, one scale a tensor; sums, and the state, stay
float32.
"""

import jax
import jax.numpy as jnp

from cells.families.deepseek_v3_reference import (QUERY_BLOCK, _mm,
                                                  _rms_norm, _rope,
                                                  softmax_scale)


def _norm(x, w, eps, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (
        scale * jax.nn.sigmoid(w.astype(jnp.float32)))


# ------------------------------------------------------------ DeltaNet

def _conv(x, w):
    """Causal depthwise convolution from a zero history: x ``[s, C]``, w
    ``[K, C]`` (tap ``K - 1`` on the current position)."""
    s, K = x.shape[0], w.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x])
    return sum(xp[j:j + s] * w[j].astype(jnp.float32) for j in range(K))


def delta_rule(q, k, v, alpha, beta):
    """The recurrence, position by position from a zero state: q, k ``[s,
    Hv, d]``; v ``[s, Hv, d]``; alpha, beta ``[s, Hv]``.  Returns (o ``[s,
    Hv, d]``, the last state ``[Hv, d, d]``)."""
    def step(S, args):
        qt, kt, vt, at, bt = args
        S = at[:, None, None] * S
        u = jnp.einsum("hkv,hk->hv", S, kt)
        S = S + jnp.einsum("hk,hv->hkv", kt, bt[:, None] * (vt - u))
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    Hv, d = v.shape[1:]
    S, o = jax.lax.scan(step, jnp.zeros((Hv, k.shape[-1], d), jnp.float32),
                        (q, k, v, alpha, beta))
    return o, S


def _delta_net(u, gp, model):
    s = u.shape[0]
    Hk, Hv = model["linear_key_heads"], model["linear_value_heads"]
    dk = model["linear_key_head_dim"]
    x = jax.nn.silu(_conv(_mm(u, gp["w_qkv"], model), gp["conv_w"]))
    q, k, v = jnp.split(x, [Hk * dk, 2 * Hk * dk], axis=-1)
    q, k = q.reshape(s, Hk, dk), k.reshape(s, Hk, dk)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / dk ** 0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q, k = (jnp.repeat(a, Hv // Hk, axis=1) for a in (q, k))
    v = v.reshape(s, Hv, -1)
    ba = _mm(u, gp["w_ba"], model)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    alpha = jnp.exp(-jnp.exp(gp["A_log"]) * jax.nn.softplus(
        ba[:, Hv:] + gp["dt_bias"]))
    o, _ = delta_rule(q, k, v, alpha, beta)
    g = model["linear_gate_scale"]
    z = _mm(u, gp["w_z"], model).reshape(s, Hv, -1)
    y = _norm(o, gp["o_norm"], model["linear_norm_eps"], g) * (
        g * jax.nn.sigmoid(z))
    return _mm(y.reshape(s, -1), gp["w_o"], model)


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
    out = out.transpose(1, 0, 2).reshape(s, nh * dv)
    gate = jax.nn.sigmoid(_mm(x, ap["w_g"], model))  # assumed reading 3
    return _mm(out * gate, ap["w_o"], model)


# ---------------------------------------------------------------- experts

def _swiglu(x, p, model, e=None):
    """``p``'s gate, up and down (expert ``e`` of a stacked triple), the
    clamp between (assumed reading 5)."""
    w = (lambda n: p[n]) if e is None else (lambda n: p[n][e])
    L = model["swiglu_limit"]
    gate = jnp.minimum(_mm(x, w("w_gate"), model), L)
    up = jnp.clip(_mm(x, w("w_up"), model), -L, L)
    return _mm(jax.nn.sigmoid(gate) * gate * up, w("w_down"), model)


def route(y, router, model):
    """y ``[s, H]`` -> (chosen ``[s, k]``, weight ``[s, k]``)."""
    score = jax.nn.sigmoid(_mm(y, router["w"], model))
    _, chosen = jax.lax.top_k(score + router["bias"].astype(jnp.float32),
                              model["experts_per_token"])
    weight = jnp.take_along_axis(score, chosen, axis=-1)
    if model.get("norm_topk_prob", True):
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return chosen, weight * model["routed_scaling_factor"]


def moe_parts(y, mp, model):
    """(what the held routed experts add, what the shared expert adds),
    each ``[s, H]``; ``mp`` an expert layer's ``moe`` leaves."""
    chosen, weight = route(y, mp["router"], model)
    first = model.get("first_expert", 0)
    ep = mp["experts"]
    routed = jnp.zeros_like(y)
    for e in range(ep["w_gate"].shape[0]):  # every held expert, every token
        w_e = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), axis=-1)
        routed += w_e[:, None] * _swiglu(y, ep, model, e)
    return routed, _swiglu(y, mp["shared"], model)


def layer(h, lp, model):
    """One layer, ``lp`` its leaves (assumed readings 1 and 2)."""
    eps, g = model["rms_norm_eps"], model["norm_gate_scale"]
    n = lp["norms"]
    u = _norm(h, n["pre_mix"], eps, g)
    mixed = _mla(u, lp["attn"], model) if "attn" in lp \
        else _delta_net(u, lp["gdn"], model)
    h = h + _norm(mixed, n["post_mix"], eps, g)
    y = _norm(h, n["pre_ffn"], eps, g)
    if "ffn" in lp:  # a leading dense layer
        out = _swiglu(y, lp["ffn"], model)
    else:
        routed, shared = moe_parts(y, lp["moe"], model)
        out = routed + shared
    return h + _norm(out, n["post_ffn"], eps, g)


def logits(params, tokens, model):
    """tokens ``[s]`` int32 -> logits ``[s, vocab]`` float32, one
    sequence."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        for lp in params["layers"]:
            x = layer(x, lp, model)
        x = _norm(x, params["final_norm"], model["rms_norm_eps"],
                  model["norm_gate_scale"])
        return _mm(x, params["lm_head"], model)


def loss(params, tokens, model):
    """Mean next-token cross-entropy of one sequence, tokens ``[s + 1]``."""
    logp = jax.nn.log_softmax(logits(params, tokens[:-1], model), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
