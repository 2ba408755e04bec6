"""Plain reference: Phi-4-mini-flash-reasoning's decoder (``phi4flash``, the
SambaY decoder-hybrid-decoder) in ``jax.numpy`` and float32.

Written from the published configuration (``config.json`` of
``microsoft/Phi-4-mini-flash-reasoning``) and the family's published
configuration class and modelling code, not from ``ray_tpu.models``: it
imports nothing of the program.  No kernel, no cache, no state records, no
batching: one sequence; the state-space recurrence position by position;
every query against every key through a masked score matrix (a block of
queries at a time) and the head a block of the vocabulary at a time, so
that 4112 positions fit a chip beside the weights.  Matrix multiplications
run at ``jax.default_matmul_precision("highest")`` and parameters of a lower
precision are upcast where they are used.

Every layer ``l`` of ``L``, ``x`` its input, LayerNorm with mean and bias,
epsilon ``layer_norm_eps``::

    h   = x + Mix_l(LayerNorm(x))
    out = h + W_down (silu(gate) * up),   [gate, up] = LayerNorm(h) W_gate_up

then a final LayerNorm and ``logits = . embed^T`` (the head is the
embedding table).  The first ``L/2 + 2`` layers are the self-decoder, the
rest the cross-decoder; ``W = L // 4`` pairs (state-space, window
attention), one pair (state-space, full attention), ``L/2 - 1 - W`` pairs
(gated memory unit, cross-attention): at 32 layers 8, 1 and 7.  ``Mix_l``
of the normed input ``u``:

* **state-space** (even ``l`` of the self-decoder; Mamba-1):
  ``[x', z] = u W_in``; ``x' = silu(conv(x'))``, ``conv`` causal, depthwise,
  ``d_conv`` taps, a bias; ``[dt, B, C] = x' W_x``;
  ``D_t = softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``s_t = exp(D_t A) * s_{t-1} + (D_t x'_t) (x) B_t``,
  ``y_t = s_t C_t + D * x'_t``; ``Mix = (y * silu(z)) W_out``.  The LAST
  state-space layer also publishes ``m_t = y_t`` (before the gate);
* **differential attention** (odd ``l`` of the self-decoder: over the last
  ``sliding_window`` positions, the last of them over every earlier
  position): ``[q, k, v] = u W_qkv + b``; heads pair up ``(2p, 2p + 1)``,
  query pair ``p`` on key/value pair ``g = p // (query pairs / kv pairs)``;
  ``A1 = softmax(q_{2p} k_{2g}^T / sqrt(head_dim))``,
  ``A2 = softmax(q_{2p+1} k_{2g+1}^T / sqrt(head_dim))`` under the causal
  (and window) mask, ``V_g = [v_{2g}, v_{2g+1}]``;
  ``o_p = (A1 - lam A2) V_g``, then ``RMSNorm(o_p; subln, 1e-5) * (1 -
  lam_init)``; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``,
  ``lam_init = 0.8 - 0.6 exp(-0.3 l)``; ``Mix = concat_p(o_p) W_out + b``.
  No rotary embedding anywhere;
* **gated memory unit** (even ``l`` of the cross-decoder):
  ``Mix = (silu(u W_in) * m) W_out``, ``m`` the published ``m_t`` of the
  same position;
* **cross-attention** (odd ``l`` of the cross-decoder): ``q = u W_q + b``
  only; keys and values are the FULL attention layer's, every position up
  to the current one; the same differential form with this layer's own
  ``lam``, ``subln``, ``W_out``.

The parameter tree is the program's own layout, because the comparison is
on the *same* seeded parameters: ``embed [V, H]``; ``layers``, a list of L
layers, each ``{"norm", "mlp_norm": {scale, bias}, "mlp": {w_gate_up
[H, 2F], w_down [F, H]}, "mix": ...}`` with ``mix`` by kind: state-space
``{w_in [H, 2I], conv_w [K, I], conv_b [I], w_x [I, R + 2N], w_dt [R, I],
b_dt [I], A_log [N, I], D [I], w_out [I, H]}`` (``A_log`` with the channels
last, the transpose of the published ``[I, N]``); attention ``{w_qkv [H,
(nh + 2 kvh) hd], b_qkv, w_out [nh hd / 1, H], b_out, lambda_q1, lambda_k1,
lambda_q2, lambda_k2 [hd] float32, subln [2 hd]}``; memory unit ``{w_in
[H, I], w_out [I, H]}``; cross-attention as attention with ``w_q [H, nh
hd]``, ``b_q`` in place of ``w_qkv``, ``b_qkv``; ``final_norm``.

Departures from the published code, each one arithmetic and none a
mechanism: the four attention products ``(q1, k1, v1) (q1, k1, v2) (q2, k2,
v1) (q2, k2, v2)`` are written as two softmaxes over a value of twice the
head's width, which is the same numbers; the state is ``[N, I]``.

The control of the comparison that decides ``correct`` is this file too:
with ``control_dtype`` in ``model`` (tests and the control run only, never
a measured run) every matrix product with a weight rounds both operands to
that 8-bit float first, one scale a tensor; sums stay float32.
"""

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256  # queries a score matrix is made for at a time
VOCAB_BLOCKS = 16  # pieces the head's product is made in


def kinds(model):
    """The kind of every layer, by depth."""
    L = model["num_layers"]
    pairs = L // 4
    out = []
    for l in range(L):
        if l < L // 2 + 2:
            out.append("ssm" if l % 2 == 0
                       else "window" if l < 2 * pairs else "full")
        else:
            out.append("gmu" if l % 2 == 0 else "cross")
    return out


def _layer_norm(x, p, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def _rounded(x, dtype, scale=None):
    if scale is None:
        scale = jnp.max(jnp.abs(x)).astype(jnp.float32) / float(
            jnp.finfo(dtype).max)
    return (x.astype(jnp.float32) / scale).astype(dtype).astype(
        jnp.float32) * scale


def _mm(a, w, model):
    w = w.astype(jnp.float32)
    dtype = model.get("control_dtype")
    if dtype is None:
        return a @ w
    return _rounded(a, dtype) @ _rounded(w, dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def state_space(u, p, model):
    """u ``[s, H]`` (normed) -> (Mix ``[s, H]``, y ``[s, I]`` before the
    gate)."""
    s = u.shape[0]
    N, K, R = (model["mamba_d_state"], model["mamba_d_conv"],
               model["mamba_dt_rank"])
    xz = _mm(u, p["w_in"], model)
    I = xz.shape[1] // 2
    x, z = xz[:, :I], xz[:, I:]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    conv = p["conv_b"].astype(jnp.float32)
    for j in range(K):  # tap K - 1 multiplies the current position
        conv = conv + xp[j:j + s] * p["conv_w"][j].astype(jnp.float32)
    x = _silu(conv)
    dbc = _mm(x, p["w_x"], model)
    dt, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    delta = jax.nn.softplus(_mm(dt, p["w_dt"], model)
                            + p["b_dt"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))  # [N, I]

    def step(state, args):  # position by position
        x_t, d_t, B_t, C_t = args
        state = jnp.exp(d_t[None, :] * A) * state \
            + (d_t * x_t)[None, :] * B_t[:, None]
        return state, jnp.sum(state * C_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((N, I), jnp.float32),
                        (x, delta, B, C))
    y = y + p["D"].astype(jnp.float32) * x
    return _mm(y * _silu(z), p["w_out"], model), y


def _lambda(p, l):
    init = 0.8 - 0.6 * math.exp(-0.3 * l)
    f = lambda a: a.astype(jnp.float32)  # noqa: E731
    lam = (jnp.exp(jnp.sum(f(p["lambda_q1"]) * f(p["lambda_k1"])))
           - jnp.exp(jnp.sum(f(p["lambda_q2"]) * f(p["lambda_k2"]))) + init)
    return lam, init


def differential(q, k, v, p, l, window, model):
    """q ``[s, nh, hd]``, k, v ``[s, kvh, hd]`` -> ``[s, nh * hd]`` after
    the norm over each pair's value, before the output product."""
    s, nh, hd = q.shape
    kvh = k.shape[1]
    qp, gp = nh // 2, kvh // 2  # query pairs, key/value pairs
    lam, init = _lambda(p, l)
    q = q.reshape(s, gp, qp // gp, 2, hd)  # [s, g, r, which, d]
    k = k.reshape(s, gp, 2, hd)
    v = v.reshape(s, gp, 2 * hd)  # [v_2g, v_2g+1]
    blk = min(QUERY_BLOCK, s)
    pad = (-s) % blk
    q = jnp.pad(q, ((0, pad),) + ((0, 0),) * 4)
    q = q.reshape(-1, blk, *q.shape[1:])
    first = jnp.arange(0, s + pad, blk)
    kpos = jnp.arange(s)

    def block(args):
        qb, q0 = args
        qpos = q0 + jnp.arange(blk)
        scores = jnp.einsum("qgrwd,kgwd->grwqk", qb, k) / math.sqrt(hd)
        seen = qpos[:, None] >= kpos[None, :]
        if window is not None:
            seen &= qpos[:, None] - kpos[None, :] < window
        a = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        diff = a[:, :, 0] - lam * a[:, :, 1]  # [g, r, q, k]
        return jnp.einsum("grqk,kgd->qgrd", diff, v)

    o = jax.lax.map(block, (q, first)).reshape(s + pad, qp, 2 * hd)[:s]
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + 1e-5)
    o = o * p["subln"].astype(jnp.float32) * (1.0 - init)
    return o.reshape(s, nh * hd)


def attention(u, p, l, window, model):
    """u ``[s, H]`` (normed) -> (Mix ``[s, H]``, (k, v) of the layer)."""
    s = u.shape[0]
    nh, kvh, hd = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    qkv = _mm(u, p["w_qkv"], model) + p["b_qkv"].astype(jnp.float32)
    q = qkv[:, :nh * hd].reshape(s, nh, hd)
    k = qkv[:, nh * hd:(nh + kvh) * hd].reshape(s, kvh, hd)
    v = qkv[:, (nh + kvh) * hd:].reshape(s, kvh, hd)
    o = differential(q, k, v, p, l, window, model)
    return (_mm(o, p["w_out"], model) + p["b_out"].astype(jnp.float32),
            (k, v))


def cross_attention(u, p, l, kv, model):
    s = u.shape[0]
    q = _mm(u, p["w_q"], model) + p["b_q"].astype(jnp.float32)
    q = q.reshape(s, model["num_heads"], model["head_dim"])
    o = differential(q, kv[0], kv[1], p, l, None, model)
    return _mm(o, p["w_out"], model) + p["b_out"].astype(jnp.float32)


def memory_unit(u, p, m, model):
    return _mm(_silu(_mm(u, p["w_in"], model)) * m, p["w_out"], model)


def mlp(y, p, model):
    gu = _mm(y, p["w_gate_up"], model)
    F = gu.shape[1] // 2
    return _mm(_silu(gu[:, :F]) * gu[:, F:], p["w_down"], model)


def head(x, embed, model):
    """``x [s, H] . embed^T`` a block of the vocabulary at a time: the whole
    table in float32 would be 2 GB beside the 3.3 GB of logits."""
    V = embed.shape[0]
    nb = VOCAB_BLOCKS if V % VOCAB_BLOCKS == 0 and V > 4096 else 1
    dtype = model.get("control_dtype")
    if dtype is not None:
        x = _rounded(x, dtype)
        scale = jnp.max(jnp.abs(embed)).astype(jnp.float32) / float(
            jnp.finfo(dtype).max)
    vb = V // nb

    def piece(j, out):
        w = jax.lax.dynamic_slice_in_dim(embed, j * vb, vb).astype(
            jnp.float32)
        if dtype is not None:
            w = _rounded(w, dtype, scale)
        return jax.lax.dynamic_update_slice_in_dim(out, x @ w.T, j * vb, 1)

    return jax.lax.fori_loop(0, nb, piece,
                             jnp.zeros((x.shape[0], V), jnp.float32))


def logits(params, tokens, model):
    """tokens ``[s]`` int32 -> logits ``[s, vocab]`` float32, one
    sequence."""
    eps = model.get("layer_norm_eps", 1e-5)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(jnp.float32)
        m = kv = None
        for l, (kind, lp) in enumerate(zip(kinds(model), params["layers"])):
            u = _layer_norm(x, lp["norm"], eps)
            if kind == "ssm":
                mix, m = state_space(u, lp["mix"], model)
            elif kind in ("window", "full"):
                mix, kv = attention(
                    u, lp["mix"], l,
                    model["sliding_window"] if kind == "window" else None,
                    model)
            elif kind == "gmu":
                mix = memory_unit(u, lp["mix"], m, model)
            else:
                mix = cross_attention(u, lp["mix"], l, kv, model)
            h = x + mix
            x = h + mlp(_layer_norm(h, lp["mlp_norm"], eps), lp["mlp"],
                        model)
        x = _layer_norm(x, params["final_norm"], eps)
        return head(x, params["embed"], model)


def loss(params, tokens, model):
    """Mean next-token cross-entropy of one sequence, tokens ``[s + 1]``."""
    logp = jax.nn.log_softmax(logits(params, tokens[:-1], model), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
