"""Phi-4-mini-flash-reasoning's decoder (``phi4flash``, the SambaY
decoder-hybrid-decoder), pure-functional JAX, as ``LLMEngine`` serves it.

Written from the published configuration (``config.json`` of
``microsoft/Phi-4-mini-flash-reasoning``) and the family's published
configuration class and modelling code.  What sets it apart from the other
served models:

* **The stack is three runs, not one repeated period** (``layer_kinds``):
  ``W`` pairs (state-space, window attention), one pair (state-space, full
  attention), ``X`` pairs (gated memory unit, cross-attention); 8, 1 and 7
  at the published 32 layers.  The first ``2 W + 2`` layers are the
  self-decoder, the rest the cross-decoder.
* **State-space layers (Mamba-1)** keep, for a sequence, a state ``[N, I]``
  in float32 and the last ``d_conv - 1`` inputs of a causal depthwise
  convolution: a record of fixed size that does not grow with the position
  (``ops/ssm.py``).  The last state-space layer also publishes its scan's
  output before the gate, ``m``, to every gated memory unit.
* **Differential attention**: heads pair up, two softmaxes a pair, one
  value of twice the head's width, an RMSNorm over it.  No rotary embedding
  anywhere.  The window layers see the last ``sliding_window`` positions;
  the one full layer every earlier position.
* **The cross-decoder owns no cache.**  A gated memory unit gates ``m``; a
  cross-attention layer has queries only and reads the FULL layer's keys
  and values.  So one stored cache is read by ``1 + X`` layers
  (``layer_types``' ``readers``), and a prompt's prefill runs the
  cross-decoder for its LAST position only: nothing of it is ever read
  again, and the prefill is linear in the prompt.
* LayerNorm (mean subtracted, a bias), a SwiGLU MLP in every layer, the
  head tied to the embedding.

One layer, ``x`` its input::

    h   = x + Mix(LayerNorm(x))
    out = h + W_down (silu(gate) * up),   [gate, up] = LayerNorm(h) W_gate_up

**Differential attention as one attention call.**  Query pair ``p`` (heads
``2p``, ``2p + 1``) attends key/value pair ``g = p // 2`` (heads ``2g``,
``2g + 1``): ``o_p = (softmax(q_2p k_2g^T / 8) - lam softmax(q_2p+1
k_2g+1^T / 8)) [v_2g, v_2g+1]``.  The cache keeps a pair as ONE head of
width 128, ``[k_2g, k_2g+1]`` and ``[v_2g, v_2g+1]`` (a view of the 20
heads of 64), and a query is zero-padded to that width, ``[q_2p, 0]`` and
``[0, q_2p+1]``: 40 queries of 128 on 10 key/value heads of 128, four to a
head, scores scaled by ``64 ** -0.5``.  Every kernel of the repo then runs
at the width it was written for, and a cached position is read ONCE for
both softmaxes and both halves of the value (5120 B a storing layer); as the
published code's four products ``(q1, k1, v1) (q1, k1, v2) (q2, k2, v1)
(q2, k2, v2)`` over heads of 64 it would be read twice (10 240 B).  The zero
halves double the scores' multiplications, which a decode step, bound by
the cache's bytes, does not see.

**The cache is three pools**, one a layer type (``layer_types``):
``{"full": {"k", "v": [1, NB, bs * 10, 128]}, "window": {"k", "v": [W, NB,
bs * 10, 128]}, "state": {"ssm": [W + 1, R, N, I] float32, "conv": [W + 1,
R, (d_conv - 1) * I]}}``.  A block of positions is stored as the page the
paged kernel reads, a matrix of ``(position, pair)`` rows: ten pairs are no
multiple of a TPU tile's sublanes, so ``[.., bs, 10, 128]`` has no unpadded
layout on the chip, and XLA copied the whole pool a layer to reshape it
(compiled for the v5e in the sandbox, PR 39).  The state type's axis 1 is a RECORD, one a
request (record 0 the scratch one), and its table is one entry a slot.  A
decode step updates a layer's records IN PLACE, all ``R`` of them in one
elementwise pass (the step's inputs are carried from slots to records and
its outputs back, both small): no state is gathered or scattered.  A record
no slot holds is updated with some slot's inputs and is garbage, as the
scratch block is; a prefill overwrites its record from a zero state, so
nothing is left over from the request that held it before.

A prompt is prefilled whole, from position 0 (the recurrence starts from a
zero state): no cached prefix, no chunks.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import tracing
from ray_tpu.models import paged_generation as pg
from ray_tpu.ops.attention import dot_product_attention, sliding_window_mask
from ray_tpu.ops.layers import rms_norm, swiglu
from ray_tpu.ops.ssm import (causal_conv1d, layer_norm, selective_scan,
                             selective_update, silu)

FULL, WINDOW, STATE = "full", "window", "state"
SUBLN_EPS = 1e-5


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    num_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    head_dim: int = 64
    intermediate_size: int = 10240
    sliding_window: int = 512
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    layer_norm_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def __post_init__(self):
        if self.num_layers % 4 or self.num_layers < 8:
            raise ValueError(
                f"num_layers {self.num_layers}: a multiple of 4, at least 8 "
                f"(pairs of layers; one window pair, the full pair and one "
                f"cross pair at the least)")
        if self.num_heads % 4 or self.num_kv_heads * 2 != self.num_heads:
            raise ValueError(
                f"heads {self.num_heads} on {self.num_kv_heads}: query "
                f"heads pair up and two query pairs share a key/value pair")

    @property
    def inner_size(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def window_pairs(self) -> int:
        return self.num_layers // 4

    @property
    def cross_pairs(self) -> int:
        return self.num_layers // 2 - 1 - self.window_pairs

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The kind of every layer, by depth: ``ssm`` / ``window`` /
        ``full`` / ``gmu`` / ``cross``."""
        return (("ssm", WINDOW) * self.window_pairs + ("ssm", FULL)
                + ("gmu", "cross") * self.cross_pairs)

    @staticmethod
    def tiny(**kw) -> "Phi4FlashConfig":
        """Test-scale model (CPU, float32): 2 window pairs, the full pair,
        1 cross pair; a window of two blocks of 4."""
        defaults = dict(
            vocab_size=256, hidden_size=64, num_layers=8, num_heads=8,
            num_kv_heads=4, head_dim=16, intermediate_size=128,
            sliding_window=8, mamba_d_state=4, mamba_dt_rank=8,
            max_seq_len=128, dtype=jnp.float32, param_dtype=jnp.float32)
        defaults.update(kw)
        return Phi4FlashConfig(**defaults)


def layer_types(cfg: Phi4FlashConfig) -> Dict[str, Dict[str, Any]]:
    """What ``LLMEngine`` builds a pool, a manager and a table for.  The
    full type stores one layer and is read by ``1 + X``; the state type is
    one record a request."""
    return {FULL: {"layers": 1, "window": None,
                   "readers": 1 + cfg.cross_pairs},
            WINDOW: {"layers": cfg.window_pairs,
                     "window": cfg.sliding_window},
            STATE: {"layers": cfg.window_pairs + 1, "window": None,
                    "state": True}}


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


# ------------------------------------------------------------------ params

@functools.partial(jax.jit, static_argnames=("cfg",))
def phi4flash_init(key: jax.Array, cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """Seeded parameters, ONE program.  ``layers`` is a list of L layers,
    every weight a leaf of its own (``models/longcat.py``'s
    ``longcat_init`` says why).  The state-space leaves start where the
    published code starts them: ``A_log = log(1..N)``, ``D = 1``, a step
    size ``softplus(b_dt)`` log-uniform in [1e-3, 1e-1]."""
    H, F, I = cfg.hidden_size, cfg.intermediate_size, cfg.inner_size
    nh, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    N, K, R = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 1 + 12 * cfg.num_layers))

    def w(*shape, std=0.02, dtype=pd):
        return jax.random.normal(next(keys), shape, dtype) * std

    def norm():
        return {"scale": jnp.ones((H,), pd), "bias": jnp.zeros((H,), pd)}

    def ssm():
        dt = jnp.exp(jax.random.uniform(next(keys), (I,), jnp.float32)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return {"w_in": w(H, 2 * I), "conv_w": w(K, I, std=K ** -0.5),
                "conv_b": jnp.zeros((I,), pd), "w_x": w(I, R + 2 * N),
                "w_dt": w(R, I, std=R ** -0.5),
                "b_dt": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
                "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, N + 1, dtype=jnp.float32))[:, None], (N, I)).astype(
                        jnp.float32),
                "D": jnp.ones((I,), pd), "w_out": w(I, H)}

    def differential():
        return {"w_out": w(nh * hd, H), "b_out": jnp.zeros((H,), pd),
                **{f"lambda_{n}": w(hd, std=0.1, dtype=jnp.float32)
                   for n in ("q1", "k1", "q2", "k2")},
                "subln": jnp.ones((2 * hd,), pd)}

    def mix(kind):
        if kind == "ssm":
            return ssm()
        if kind == "gmu":
            return {"w_in": w(H, I), "w_out": w(I, H)}
        if kind == "cross":
            return {"w_q": w(H, nh * hd), "b_q": jnp.zeros((nh * hd,), pd),
                    **differential()}
        return {"w_qkv": w(H, (nh + 2 * kvh) * hd),
                "b_qkv": jnp.zeros(((nh + 2 * kvh) * hd,), pd),
                **differential()}

    return {"embed": w(cfg.vocab_size, H),
            "layers": [{"norm": norm(), "mix": mix(kind),
                        "mlp_norm": norm(),
                        "mlp": {"w_gate_up": w(H, 2 * F), "w_down": w(F, H)}}
                       for kind in cfg.layer_kinds],
            "final_norm": norm()}


# ------------------------------------------------------------------ blocks

def _norm(x, p, cfg):
    return layer_norm(x, p["scale"], p["bias"], cfg.layer_norm_eps)


def _mlp(h, lp, cfg):
    with tracing.scope("ffn"):
        y = _norm(h, lp["mlp_norm"], cfg)
        gu = y @ lp["mlp"]["w_gate_up"].astype(cfg.dtype)
        F = cfg.intermediate_size
        return h + swiglu(gu[..., :F], gu[..., F:]) @ lp["mlp"][
            "w_down"].astype(cfg.dtype)


def _ssm_inputs(x, mp, cfg):
    """After the convolution: x ``[..., I]`` -> (dt after softplus float32
    ``[..., I]``, B, C ``[..., N]``)."""
    N, R = cfg.mamba_d_state, cfg.mamba_dt_rank
    dbc = x @ mp["w_x"].astype(cfg.dtype)
    dt = dbc[..., :R] @ mp["w_dt"].astype(cfg.dtype)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + mp["b_dt"].astype(jnp.float32))
    return dt, dbc[..., R:R + N], dbc[..., R + N:]


def _pad_queries(q, cfg):
    """q ``[..., nh * hd]`` -> ``[..., nh, 2 hd]``: head ``2p`` as ``[q, 0]``,
    head ``2p + 1`` as ``[0, q]`` (the module's docstring)."""
    nh, hd = cfg.num_heads, cfg.head_dim
    q = q.reshape(*q.shape[:-1], nh // 2, 2, hd)
    zero = jnp.zeros_like(q[..., 0, :])
    return jnp.stack(
        [jnp.concatenate([q[..., 0, :], zero], -1),
         jnp.concatenate([zero, q[..., 1, :]], -1)], axis=-2).reshape(
             *q.shape[:-3], nh, 2 * hd)


def _pairs(kv, cfg):
    """k or v ``[..., kvh * hd]`` -> ``[..., kvh / 2, 2 hd]``: a pair of
    heads as one head of twice the width."""
    return kv.reshape(*kv.shape[:-1], cfg.num_kv_heads // 2,
                      2 * cfg.head_dim)


def _differential(o, mp, layer: int, cfg):
    """o ``[..., nh, 2 hd]`` (both softmaxes' products with the pair's
    value) -> ``[..., nh * hd]`` after the difference and its norm."""
    with tracing.scope("diff"):
        f = lambda n: mp[f"lambda_{n}"].astype(jnp.float32)  # noqa: E731
        init = lambda_init(layer)
        lam = (jnp.exp(jnp.sum(f("q1") * f("k1")))
               - jnp.exp(jnp.sum(f("q2") * f("k2"))) + init)
        o = o.astype(jnp.float32).reshape(*o.shape[:-2], cfg.num_heads // 2,
                                          2, 2 * cfg.head_dim)
        o = (o[..., 0, :] - lam * o[..., 1, :]).astype(cfg.dtype)
        o = rms_norm(o, mp["subln"], SUBLN_EPS) * jnp.asarray(
            1.0 - init, cfg.dtype)
        return o.reshape(*o.shape[:-2], cfg.num_heads * cfg.head_dim)


def _qkv(u, mp, cfg):
    """The normed input's queries (padded) and the key and value pairs."""
    nh, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    qkv = u @ mp["w_qkv"].astype(cfg.dtype) + mp["b_qkv"].astype(cfg.dtype)
    return (_pad_queries(qkv[..., :nh * hd], cfg),
            _pairs(qkv[..., nh * hd:(nh + kvh) * hd], cfg),
            _pairs(qkv[..., (nh + kvh) * hd:], cfg))


def _attn_out(h, o, mp, cfg):
    with tracing.scope("attn.out"):
        return h + (o @ mp["w_out"].astype(cfg.dtype)
                    + mp["b_out"].astype(cfg.dtype))


def _scale(cfg) -> float:
    return cfg.head_dim ** -0.5


def _prompt_attention(kind, q, k, v, cfg):
    """A whole prompt's attention, causal and (a window layer) windowed: on
    a TPU the flash kernel, which skips the K blocks before a window."""
    with tracing.scope("attn.core"):
        return dot_product_attention(
            q, k, v, causal=True, scale=_scale(cfg),
            window=cfg.sliding_window if kind == WINDOW else None)


def _masked_attention(q, k, v, mask, cfg):
    """q ``[b, s, nh, 2 hd]``, k, v ``[b, t, kvh / 2, 2 hd]``, mask
    ``[b, s, t]`` -> ``[b, s, nh, 2 hd]``: the plain path (a gathered
    cache, one query against a prompt)."""
    b, s, nh, d = q.shape
    g = k.shape[2]
    scores = jnp.einsum("bsgrd,btgd->bgrst", q.reshape(b, s, g, nh // g, d),
                        k, preferred_element_type=jnp.float32)
    scores = jnp.where(mask[:, None, None], scores * _scale(cfg), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    return jnp.einsum("bgrst,btgd->bsgrd", probs, v,
                      preferred_element_type=jnp.float32).astype(
                          cfg.dtype).reshape(b, s, nh, d)


def _ssm_sequence(x, lp, cfg, length, publish: bool):
    """A state-space layer over a whole sequence from a zero state:
    x ``[b, s, H]`` -> (out, (state ``[b, N, I]``, tail ``[b, (K - 1) I]``),
    ``m`` ``[b, s, I]`` where ``publish``)."""
    mp = lp["mix"]
    b, s, _ = x.shape
    I, N, K = cfg.inner_size, cfg.mamba_d_state, cfg.mamba_d_conv
    with tracing.scope("attn.proj"):
        xz = _norm(x, lp["norm"], cfg) @ mp["w_in"].astype(cfg.dtype)
        xs, z = xz[..., :I], xz[..., I:]
    with tracing.scope("attn.core"):
        with tracing.scope("ssm.conv"):
            xs, tail = causal_conv1d(
                xs, mp["conv_w"], mp["conv_b"],
                jnp.zeros((b, K - 1, I), cfg.dtype), length)
            xs = silu(xs)
    with tracing.scope("attn.proj"):
        dt, B, C = _ssm_inputs(xs, mp, cfg)
    with tracing.scope("attn.core"):
        with tracing.scope("ssm.scan"):
            y, state = selective_scan(
                xs, dt, -jnp.exp(mp["A_log"]), B, C, mp["D"],
                jnp.zeros((b, N, I), jnp.float32), length)
            y = y.astype(cfg.dtype)
            gated = y * silu(z)
    with tracing.scope("attn.out"):
        h = x + gated @ mp["w_out"].astype(cfg.dtype)
    return (_mlp(h, lp, cfg), (state, tail.reshape(b, (K - 1) * I)),
            y if publish else None)


def _gmu(x, lp, m, cfg):
    mp = lp["mix"]
    with tracing.scope("attn.proj"):
        g = _norm(x, lp["norm"], cfg) @ mp["w_in"].astype(cfg.dtype)
    with tracing.scope("attn.core"):
        with tracing.scope("gmu"):
            g = silu(g) * m
    with tracing.scope("attn.out"):
        h = x + g @ mp["w_out"].astype(cfg.dtype)
    return _mlp(h, lp, cfg)


def _cross(x, lp, layer, attend, cfg):
    """A cross-attention layer: its own queries on the full layer's cache
    (``attend(q) -> [..., nh, 2 hd]``)."""
    mp = lp["mix"]
    with tracing.scope("attn.proj"):
        q = _pad_queries(_norm(x, lp["norm"], cfg) @ mp["w_q"].astype(
            cfg.dtype) + mp["b_q"].astype(cfg.dtype), cfg)
    with tracing.scope("attn.core"):
        o = _differential(attend(q), mp, layer, cfg)
    return _mlp(_attn_out(x, o, mp, cfg), lp, cfg)


def _self_attention(x, lp, layer, attend, cfg):
    """A window or full layer; ``attend(q, k, v) -> [..., nh, 2 hd]`` opens
    ``attn.cache`` and ``attn.core`` itself (the cache is the caller's)."""
    mp = lp["mix"]
    with tracing.scope("attn.proj"):
        q, k, v = _qkv(_norm(x, lp["norm"], cfg), mp, cfg)
    o = attend(q, k, v)
    with tracing.scope("attn.core"):
        o = _differential(o, mp, layer, cfg)
    return _mlp(_attn_out(x, o, mp, cfg), lp, cfg)


def _lm_head(params, cfg, x):
    with tracing.scope("head"):
        x = _norm(x, params["final_norm"], cfg)
        return jnp.einsum("bsh,vh->bsv", x, params["embed"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def _self_decoder(params, x, cfg, ssm_layer, attend):
    """Layers ``0 .. 2 W + 1``, a pair at a time: ``ssm_layer(a, x, lp,
    publish) -> (x, m)`` the ``a``-th state-space layer, ``attend(kind, a,
    q, k, v)`` the ``a``-th attention layer of its kind.  Returns (x, the
    last state-space layer's ``m``)."""
    layers, m = params["layers"], None
    for pair in range(cfg.window_pairs + 1):
        last = pair == cfg.window_pairs
        x, m = ssm_layer(pair, x, layers[2 * pair], last)
        kind, a = (FULL, 0) if last else (WINDOW, pair)
        x = _self_attention(x, layers[2 * pair + 1], 2 * pair + 1,
                            functools.partial(attend, kind, a), cfg)
    return x, m


def _cross_decoder(params, x, m, cfg, attend):
    """Layers ``2 W + 2 .. L - 1``, a pair at a time; ``attend(q)`` reads
    the full layer's keys and values."""
    first = 2 * cfg.window_pairs + 2
    for pair in range(cfg.cross_pairs):
        l = first + 2 * pair
        x = _gmu(x, params["layers"][l], m, cfg)
        x = _cross(x, params["layers"][l + 1], l + 1, attend, cfg)
    return x


# ---------------------------------------------------------------- programs

def phi4flash_apply(params, tokens, cfg: Phi4FlashConfig, *, mesh=None):
    """tokens ``[b, s]`` -> logits ``[b, s, vocab]`` float32: the plain
    causal forward, no cache, every layer at every position."""
    if mesh is not None:
        raise NotImplementedError("phi4flash has no sharded forward yet")
    kept = {}

    def ssm_layer(a, x, lp, publish):
        x, _, m = _ssm_sequence(x, lp, cfg, None, publish)
        return x, m

    def attend(kind, a, q, k, v):
        if kind == FULL:
            kept["kv"] = (k, v)
        return _prompt_attention(kind, q, k, v, cfg)

    x, m = _self_decoder(params, pg.embed_tokens(params, tokens, cfg.dtype),
                         cfg, ssm_layer, attend)
    x = _cross_decoder(
        params, x, m, cfg,
        lambda q: dot_product_attention(q, *kept["kv"], causal=True,
                                        scale=_scale(cfg)))
    return _lm_head(params, cfg, x)


def init_pools(cfg: Phi4FlashConfig, num_blocks: Dict[str, int],
               block_size: int, kv_dtype: str | None = None):
    """The three pools of the module's docstring; block 0 / record 0 of each
    is its scratch one."""
    if kv_dtype not in (None, "auto"):
        raise ValueError(
            f"the pools are stored in the model's dtype: kv_dtype "
            f"{kv_dtype!r} is not supported for phi4flash (None/'auto')")
    types = layer_types(cfg)
    I, K = cfg.inner_size, cfg.mamba_d_conv
    pools = {t: {name: jnp.zeros(
        (types[t]["layers"], num_blocks[t],
         block_size * (cfg.num_kv_heads // 2), 2 * cfg.head_dim), cfg.dtype)
        for name in ("k", "v")} for t in (FULL, WINDOW)}
    L, R = types[STATE]["layers"], num_blocks[STATE]
    pools[STATE] = {
        "ssm": jnp.zeros((L, R, cfg.mamba_d_state, I), jnp.float32),
        "conv": jnp.zeros((L, R, (K - 1) * I), cfg.dtype)}
    return pools


def store_slabs(pages, layer, blocks, offsets, slabs):
    """A token's keys (or values) into a pool stored as pages, ONE update a
    token: pages ``[L, NB, bs * g, W]``, slabs ``[n, g, W]``, token ``i``
    to rows ``offsets[i] * g .. + g`` of page ``blocks[i]`` of ``layer``.

    XLA:TPU's scatter takes a window of whole trailing dimensions; a window
    inside the page's rows it unrolls into a loop of small fusions, and
    single rows (``[n, g]`` updates of ``[W]``) cost ~70 ns each against
    ``g * W`` bytes of work.  So the page is viewed ``[bs, g / r, r, W]``,
    ``r`` the rows a 32-bit sublane packs (2 in bf16): the same bytes in
    the same order on the chip (a packed sublane is contiguous and a page's
    sublanes are in order), a bitcast both ways, and the slab is the
    window.  The coordinates are the engine's and in bounds by
    construction; idle slots and pad lanes share the scratch block, so
    they are not unique."""
    L, NB, rows, W = pages.shape
    n, g, _ = slabs.shape
    r = max(1, 4 // pages.dtype.itemsize)
    r = r if g % r == 0 else 1
    view = pages.reshape(L, NB, rows // g, g // r, r, W)
    view = view.at[layer, blocks, offsets].set(
        slabs.reshape(n, g // r, r, W), mode="promise_in_bounds")
    return view.reshape(pages.shape)


def decode_attention_path(pool, *, mesh=None) -> str:
    """``paged_generation.decode_attention_path``'s rule for the two pools
    of positions, whose blocks are pages already: the paged kernel on one
    TPU device where a page's rows and width are tile-aligned, the gathered
    cache everywhere else."""
    rows, width = pool[FULL]["k"].shape[2:]
    off_kernel = mesh is not None or jax.default_backend() != "tpu"
    return ("gather" if off_kernel or width % 128 or rows % 16
            else "paged_kernel")


def gather_prefix(pool, blocks, cfg: Phi4FlashConfig):
    """No prefix is ever cached for this model: the empty pair."""
    if blocks.shape[0]:
        raise NotImplementedError(
            "phi4flash takes no prefix hits: the recurrent state at the "
            "hit is not kept (docs/llm_serving.md)")
    empty = jnp.zeros((1, 0, cfg.num_kv_heads // 2, 2 * cfg.head_dim),
                      cfg.dtype)
    return empty, empty


def prefill_suffix(params, tokens, length, start_pos, prefix_k, prefix_v,
                   prefix_len, dst_blocks, dst_offsets, pool,
                   cfg: Phi4FlashConfig):
    """b=1 prefill of a whole prompt: ``paged_generation.prefill_suffix``'s
    contract with an empty prefix and ``dst_blocks`` by layer type:
    ``{"full", "window": [S]}`` block coordinates (a window layer's keys
    that no later step can see have the scratch block for theirs) and
    ``{"state": [1]}`` the request's record.

    The self-decoder runs over the prompt and writes the three pools (the
    scan stops at ``length`` inside the bucket); the cross-decoder runs for
    position ``length - 1`` ALONE, on that position's ``m`` and the full
    layer's keys and values, which are in hand.  Returns ``(logits_at_last
    [1, vocab], pools, int32[2]: positions the self-decoder and the
    cross-decoder computed)``."""
    if prefix_k.shape[1]:
        raise NotImplementedError(
            "phi4flash prefills a prompt whole: no cached prefix "
            "(docs/llm_serving.md)")
    _, S = tokens.shape
    pool = {t: dict(p) for t, p in pool.items()}
    rec = dst_blocks[STATE][0]
    kept = {}

    def ssm_layer(a, x, lp, publish):
        x, (state, tail), m = _ssm_sequence(x, lp, cfg, length, publish)
        with tracing.scope("attn.cache"):
            p = pool[STATE]
            p["ssm"] = p["ssm"].at[a, rec].set(state[0])
            p["conv"] = p["conv"].at[a, rec].set(tail[0])
        return x, m

    def attend(kind, a, q, k, v):
        p = pool[kind]  # pad lanes and unseen keys land in the scratch block
        with tracing.scope("attn.cache"):
            for name, slabs in (("k", k[0]), ("v", v[0])):
                p[name] = store_slabs(p[name], a, dst_blocks[kind],
                                      dst_offsets, slabs)
        if kind == FULL:
            kept["kv"] = (k, v)
        # the pad tail lies after every true position: causal hides it
        return _prompt_attention(kind, q, k, v, cfg)

    x, m = _self_decoder(params, pg.embed_tokens(params, tokens, cfg.dtype),
                         cfg, ssm_layer, attend)
    with tracing.scope("attn.cache"):  # the one position the rest runs for
        last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
        m = jax.lax.dynamic_slice_in_dim(m, length - 1, 1, axis=1)
    seen = (jnp.arange(S) < length)[None, None, :]
    x = _cross_decoder(
        params, last, m, cfg,
        lambda q: _masked_attention(q, *kept["kv"], seen, cfg))
    counts = jnp.stack([length, jnp.ones_like(length)]).astype(jnp.int32)
    return _lm_head(params, cfg, x)[:, 0], pool, counts


def decode_step(params, token, cur_len, block_tables, pool,
                cfg: Phi4FlashConfig, attn: str | None = None):
    """One token for every slot: ``paged_generation.paged_decode_step``'s
    contract with ``block_tables`` and ``pool`` by layer type
    (``block_tables["state"]`` ``[b, 1]``: each slot's record).  Returns
    ``(logits [b, vocab], pools, int32[2]: live slots, twice)``; a slot
    whose FULL table row is all scratch holds no request."""
    if attn is None:
        attn = decode_attention_path(pool)
    b = token.shape[0]
    MB = block_tables[FULL].shape[1]
    g = cfg.num_kv_heads // 2
    bs = pool[FULL]["k"].shape[2] // g
    I, K = cfg.inner_size, cfg.mamba_d_conv
    R = pool[STATE]["ssm"].shape[1]
    with tracing.scope("attn.cache"):  # where the step's rows go
        rows = jnp.arange(b)
        off = cur_len % bs
        blk = {t: block_tables[t][rows, cur_len // bs]
               for t in (FULL, WINDOW)}
        live = block_tables[FULL][:, 0] != 0
        lengths = jnp.where(live, cur_len + 1, 0)
        rec = block_tables[STATE][:, 0]
        # a record's slot: the records are updated in place, all of them,
        # and a record no slot holds takes slot 0's inputs (garbage that
        # nothing reads, as the scratch block's)
        slot_of = jnp.zeros((R,), jnp.int32).at[rec].set(rows)
    idx = jnp.arange(MB * bs)
    seen = idx[None, :] <= cur_len[:, None]  # [b, MB * bs]
    pool = {t: dict(p) for t, p in pool.items()}

    def ssm_layer(a, x, lp, publish):
        mp, p = lp["mix"], pool[STATE]
        with tracing.scope("attn.proj"):
            xz = _norm(x, lp["norm"], cfg) @ mp["w_in"].astype(cfg.dtype)
            xs, z = xz[:, 0, :I][slot_of], xz[..., I:]  # xs by record
        # the records are rewritten where they lie: the write IS the
        # update, and is scoped with it (a prefill's record write, a
        # scatter of its own, is under attn.cache)
        with tracing.scope("attn.core"):
            with tracing.scope("ssm.conv"):
                xs, tail = causal_conv1d(
                    xs[:, None], mp["conv_w"], mp["conv_b"],
                    p["conv"][a].reshape(R, K - 1, I))
                p["conv"] = p["conv"].at[a].set(
                    tail.reshape(R, (K - 1) * I))
                xs = silu(xs[:, 0])
        with tracing.scope("attn.proj"):
            dt, B, C = _ssm_inputs(xs, mp, cfg)
        with tracing.scope("attn.core"):
            with tracing.scope("ssm.update"):
                y, state = selective_update(
                    xs, dt, -jnp.exp(mp["A_log"]), B, C, mp["D"],
                    p["ssm"][a])
                p["ssm"] = p["ssm"].at[a].set(state)
                y = y.astype(cfg.dtype)[rec][:, None]  # back by slot
                gated = y * silu(z)
        with tracing.scope("attn.out"):
            h = x + gated @ mp["w_out"].astype(cfg.dtype)
        return _mlp(h, lp, cfg), y if publish else None

    def read(kind, a, q):
        p, tab = pool[kind], block_tables[kind]
        window = cfg.sliding_window if kind == WINDOW else None
        if attn == "paged_kernel":
            from ray_tpu.ops.pallas.paged_attention import paged_attention

            return paged_attention(
                q[:, 0], p["k"], p["v"], tab, lengths, layer=a,
                window=window, scale=_scale(cfg), kv_heads=g)[:, None]
        mask = seen
        if window is not None:
            mask = mask & sliding_window_mask(cur_len[:, None], idx[None, :],
                                              window)
        gk = p["k"][a][tab].reshape(b, MB * bs, g, -1)
        gv = p["v"][a][tab].reshape(b, MB * bs, g, -1)
        return _masked_attention(q, gk, gv, mask[:, None], cfg)

    def attend(kind, a, q, k, v):
        p = pool[kind]
        with tracing.scope("attn.cache"):
            # the new keys and values first: the token attends to itself
            for name, slabs in (("k", k[:, 0]), ("v", v[:, 0])):
                p[name] = store_slabs(p[name], a, blk[kind], off, slabs)
        with tracing.scope("attn.core"):
            return read(kind, a, q)

    x = pg.embed_tokens(params, token, cfg.dtype)[:, None]
    x, m = _self_decoder(params, x, cfg, ssm_layer, attend)
    x = _cross_decoder(params, x, m, cfg,
                       functools.partial(read, FULL, 0))
    n = jnp.sum(live).astype(jnp.int32)
    return _lm_head(params, cfg, x)[:, 0], pool, jnp.stack([n, n])


def decode_sample(params, token, cur_len, block_tables, pool, key, temps,
                  cfg: Phi4FlashConfig, attn: str | None = None):
    """``paged_generation.paged_decode_sample``'s contract, plus the step's
    two counters."""
    ML = block_tables[FULL].shape[1] * (
        pool[FULL]["k"].shape[2] // (cfg.num_kv_heads // 2))
    safe_cur = jnp.minimum(cur_len, ML - 1)
    logits, pool, counts = decode_step(params, token, safe_cur, block_tables,
                                       pool, cfg=cfg, attn=attn)
    nxt, key = pg.sample_next(logits, key, temps)
    return nxt, cur_len + 1, key, pool, counts
