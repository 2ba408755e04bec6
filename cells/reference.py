"""Plain reference: a dense decoder in ``jax.numpy`` and float32.

Written from the architecture (Mistral-7B-v0.3's ``config.json`` and the
Llama/Mistral block: pre-RMSNorm, rotary embedding on half-split head
dimensions as in Hugging Face's ``rotate_half``, grouped-query attention,
SwiGLU, untied output head), not from ``ray_tpu.models``: it imports
nothing of the program.  No kernel, no cache, no batching: one sequence,
every position attends to every earlier one through a full score matrix.
Matrix multiplications run at ``jax.default_matmul_precision("highest")``
(on a TPU a float32 product is otherwise rounded to bf16 passes), and
parameters of a lower precision are upcast layer by layer.

The parameter tree is the program's own layout, because the comparison is
on the *same* seeded parameters: ``embed [V, H]``, ``layers`` with each
leaf stacked over depth (``wq [L, H, heads*hd]`` ... ``w_down [L, M, H]``,
norms ``[L, H]``), ``final_norm [H]``, ``lm_head [H, V]``.

The control of the comparison that decides ``correct`` is this file too:
with ``control_dtype`` in ``model`` (tests and ``tools/control.py`` only,
never a measured run) every matrix product with a weight rounds both
operands to that 8-bit float first, with one scale a tensor, as an fp8
path of the program would; sums stay float32.

Departures from the published model: none in the mathematics; RMSNorm's
epsilon is 1e-6 as the program's (the published ``rms_norm_eps`` is 1e-5;
both are far below the activations' mean square of order 1).
"""

import jax
import jax.numpy as jnp

EPS = 1e-6


def _rms_norm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + EPS) * scale


def _mm(a, b, model):
    dtype = model.get("control_dtype")
    if dtype is None:
        return a @ b

    def rounded(x):  # the gradient passes the rounding straight through
        scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max)
        return x + jax.lax.stop_gradient(
            (x / scale).astype(dtype).astype(jnp.float32) * scale - x)
    return rounded(a) @ rounded(b)


def _rope(x, theta):
    """x [s, heads, hd]: rotate pairs (i, i + hd/2) by pos * theta^(-2i/hd)."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, model):
    s = x.shape[0]
    nh, nkv = model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or model["hidden_size"] // nh
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    y = _rms_norm(x, lp["attn_norm"])
    q = _rope(_mm(y, lp["wq"], model).reshape(s, nh, hd),
              model["rope_theta"])
    k = _rope(_mm(y, lp["wk"], model).reshape(s, nkv, hd),
              model["rope_theta"])
    v = _mm(y, lp["wv"], model).reshape(s, nkv, hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    group = nh // nkv

    @jax.checkpoint  # a gradient recomputes one group's scores at a time
    def attend(qg, kg, vg):
        scores = jnp.einsum("qhd,kd->hqk", qg, kg) / jnp.sqrt(
            jnp.float32(hd))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(scores, axis=-1), vg)

    # one kv head and its query heads at a time
    outs = [attend(q[:, g * group:(g + 1) * group], k[:, g], v[:, g])
            for g in range(nkv)]
    attn = jnp.concatenate(outs, axis=1).reshape(s, nh * hd)
    x = x + _mm(attn, lp["wo"], model)
    y = _rms_norm(x, lp["mlp_norm"])
    gate = _mm(y, lp["w_gate"], model)
    x = x + _mm(jax.nn.sigmoid(gate) * gate * _mm(y, lp["w_up"], model),
                lp["w_down"], model)
    return x


def _from_embeddings(params, x, model):
    """x [s, hidden] float32 (the embedded tokens) -> logits [s, vocab]."""
    with jax.default_matmul_precision("highest"):
        # layer by layer under jax.checkpoint, so that a gradient keeps
        # one layer's score matrices at a time; the values are the same
        body = jax.checkpoint(lambda x, lp: (_layer(x, lp, model), None))
        x, _ = jax.lax.scan(body, x, params["layers"])
        x = _rms_norm(x, params["final_norm"].astype(jnp.float32))
        return _mm(x, params["lm_head"].astype(jnp.float32), model)


def _nll(lg, targets):
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def logits(params, tokens, model):
    """tokens [s] int32 -> logits [s, vocab] float32, one sequence."""
    return _from_embeddings(
        params, params["embed"][tokens].astype(jnp.float32), model)


def loss(params, tokens, model):
    """Mean next-token cross-entropy of one sequence, tokens [s + 1]."""
    return _nll(logits(params, tokens[:-1], model), tokens[1:])


def embedding_gradient(params, tokens, model):
    """Gradient of ``loss`` with respect to the embedded tokens, [s,
    hidden], by autodiff through every layer, the head and the loss: row j
    is what position j contributes to the gradient of its token's row of
    the embedding table."""
    x = params["embed"][tokens[:-1]].astype(jnp.float32)
    return jax.grad(lambda x: _nll(_from_embeddings(params, x, model),
                                   tokens[1:]))(x)
