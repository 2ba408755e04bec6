"""GigaChat3.5's family (``model_type`` ``gigachat3_5``: GigaChat3.5-432B-A28B),
pure-functional JAX, as ``LLMEngine`` serves it.

Written from the published configuration (``config.json`` of
``ai-sage/GigaChat3.5-432B-A28B``); the linear-attention layer names itself
(``linear_attention_type: GigaChat35GatedDeltaNet``): the gated delta rule
(``ops/delta.py``) in the projection layout of Qwen3-Next's published
modelling code.  Six keys of the configuration name small element-wise parts
whose text is not in it; each is read as its name and value fix it, and the
readings are marked ASSUMED below (the benchmark's configuration and
reference list the same).  What sets the model apart from the other served
ones:

* **Two kinds of mixer, two kinds of feed-forward, walked by two lists.**
  Layer ``l`` mixes through a latent-attention block where ``l`` is in
  ``full_attention_layers`` (every fourth) and through a Gated DeltaNet layer
  elsewhere; its feed-forward is a dense SwiGLU for ``l < dense_layers`` and
  the expert layer after.  The two vary independently.
* **Gated DeltaNet**: ``H_k`` key heads serve ``H_v = 2 H_k`` value heads.
  ``[q k v] = silu(causal depthwise conv (4 taps, no bias) of u [W_q W_k
  W_v])``, ``q`` and ``k`` of unit length a head (``q`` also ``/ sqrt(d)``),
  ``beta = sigmoid(u W_b)``, ``alpha = exp(-exp(A_log) softplus(u W_a +
  dt_bias))`` a value head.  A value head keeps a float32 matrix ``S [d, d]``
  that does not grow with the position (4 MiB a layer and request at the
  published widths) and the convolution its last three inputs: a RECORD a
  request.  The output is ``o / rms(o) * 2 sigmoid(w_o) * 2 sigmoid(u W_z)``
  a head (ASSUMED: ``linear_gating_type`` ``gated_rmsnorm_sigmoid_zero_centered``,
  ``linear_sigmoid_gate_scale`` 2), then ``W_o``.
* **The latent block** is ``models/mla.py``'s (DeepSeek-V3's, YaRN, rotary
  pairs held de-interleaved) with values as wide as the nope half and a
  gate on the heads' outputs, ``(attn * sigmoid(u W_g)) W_o`` (ASSUMED:
  ``gated_attention`` in Qwen3-Next's form).  ``use_mla_scaling_factor`` is
  read as DeepSeek-V3's softmax scale, ``(nope + rope)^-0.5 *
  yarn_mscale(factor, mscale_all_dim)^2`` (ASSUMED).
* **Four norms a layer** (ASSUMED: ``layernorm_type`` ``pre_post``): ``x +=
  N_post(Mixer(N_pre(x)))``, ``x += N'_post(FFN(N'_pre(x)))``, and a final
  one.  A norm is ``x / rms(x) * 2 sigmoid(w)`` with ``w`` zero at
  initialisation (ASSUMED: ``norm_type`` ``ZeroCenteredGatedNorm``,
  ``layernorm_gating_weight`` 2).  The norms on the two latents inside the
  latent block stay the family's plain RMSNorm (``models/mla.py``).
* **The expert layer** is DeepSeek-V3's (``models/deepseek_v3.py``): sigmoid
  scores with a selection bias, ``experts_per_token`` of ``num_experts``
  with no group limit (``n_group`` 1), the picks renormalised and times
  ``routed_scaling_factor``, one ungated shared expert; the model is told
  which routed experts it holds.  **Every SwiGLU** (dense, shared, routed)
  is clamped (ASSUMED: ``swiglu_limit`` 10): ``silu(min(g, 10)) * clip(u,
  -10, 10)``.

Not here: the multi-token-prediction modules (``num_nextn_predict_layers``).
The logits do not depend on them and the engine's step yields one token a
slot.

**The cache is two pools**, one a layer type (``layer_types``):
``{"latent": {"kv": [A, NB, bs, W]}`` (``models/mla.py``'s pool of rows, its
decode arm ``latent_paged_attention``) ``, "state": {"s": [D, R, Hv, d, d]
float32, "conv": [D, R, 3 P, d] }}`` with ``A`` latent blocks, ``D`` DeltaNet
layers and the convolution's ``C = P d`` channels held a row a head (a
record's tail is whole tiles, which a kernel's block can name).  The state
type's axis 1 is a RECORD, one a request (record 0 the scratch one), and its
table is one entry a slot.  A decode step hands a layer's ``qkv``, gates and
both pools to ONE operation (``ops/delta.py:delta_update_records``), which
moves the live slots' records and tails where they lie and touches no
other; a prefill scans the prompt from a zero state (``chunked_delta_scan``)
and writes its one record.  A prompt is prefilled whole, from position 0: no
cached prefix, no chunks.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import tracing
from ray_tpu.models import mla
from ray_tpu.models.mla import prefill_attention_path
from ray_tpu.models.paged_generation import (decode_attention_path as
                                             _pool_attention_path,
                                             embed_tokens, sample_next)
from ray_tpu.ops import delta
from ray_tpu.ops.experts import held_experts_ffn, route_top_k
from ray_tpu.ops.layers import heads_projection, rms_norm, swiglu
from ray_tpu.ops.ssm import causal_conv1d

LATENT, STATE = "latent", "state"
# what the programs return beside the rest, in this order: LongCat's names
# (a pick of a zero-compute expert never happens: the model has none)
COUNTERS = ("moe_pairs_held", "moe_experts_hit", "moe_zero_picks")


@dataclasses.dataclass(frozen=True)
class GigaChat35Config(mla.YarnLatentWidths):
    """GigaChat3.5-432B-A28B's published values."""
    vocab_size: int = 128256
    hidden_size: int = 7168
    num_layers: int = 40
    dense_layers: int = 3           # first_k_dense_replace
    # the layers whose mixer is the latent block; the others' is DeltaNet
    full_attention_layers: Tuple[int, ...] = tuple(range(3, 40, 4))
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    linear_key_heads: int = 32
    linear_value_heads: int = 64
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel: int = 4
    linear_gate_scale: float = 2.0  # linear_sigmoid_gate_scale
    linear_norm_eps: float = 1e-6   # linear_attn_o_norm_eps
    ffn_dim: int = 18432            # a leading layer's dense SwiGLU
    expert_ffn_dim: int = 2048
    num_experts: int = 256          # routed experts the router knows
    shared_experts: int = 1
    experts_per_token: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    swiglu_limit: float = 10.0
    # the routed experts held here: ``held_experts`` of them from
    # ``first_expert`` on (None: all of them)
    first_expert: int = 0
    held_experts: Optional[int] = None
    rms_norm_eps: float = 1e-6
    norm_gate_scale: float = 2.0    # layernorm_gating_weight
    rope_theta: float = 1e5
    rope_factor: float = 8.0
    rope_original_max_len: int = 32768
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    gated_attention = True  # mla.LatentWidths: the gate before W_o

    def __post_init__(self):
        object.__setattr__(self, "full_attention_layers",
                           tuple(self.full_attention_layers))
        full = self.full_attention_layers
        if not full or len(set(full)) != len(full) or not all(
                0 <= l < self.num_layers for l in full):
            raise ValueError(
                f"full_attention_layers {full}: distinct layers of the "
                f"{self.num_layers}, at least one (the first layer type "
                f"keeps every position)")
        if len(full) == self.num_layers:
            raise ValueError("no Gated DeltaNet layer: this is "
                             "models/deepseek_v3.py's model")
        if self.linear_value_heads % self.linear_key_heads:
            raise ValueError(
                f"{self.linear_value_heads} value heads on "
                f"{self.linear_key_heads} key heads: a key head serves a "
                f"whole number of value heads")
        if self.conv_channels % self.linear_value_head_dim:
            raise ValueError(
                f"{self.conv_channels} convolution channels in rows of "
                f"{self.linear_value_head_dim}: the tails' pool holds them "
                f"a row a value head wide")

    @property
    def num_held(self) -> int:
        return self.num_experts if self.held_experts is None \
            else self.held_experts

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.dense_layers

    @property
    def attention_blocks(self) -> int:
        """What the latent pool stacks."""
        return len(self.full_attention_layers)

    @property
    def delta_layers(self) -> int:
        """What the state pool stacks."""
        return self.num_layers - self.attention_blocks

    @property
    def conv_channels(self) -> int:
        return (2 * self.linear_key_heads * self.linear_key_head_dim
                + self.linear_value_heads * self.linear_value_head_dim)

    @staticmethod
    def tiny(**kw) -> "GigaChat35Config":
        """Test-scale model (CPU, float32): one dense layer, then a period
        DeltaNet, DeltaNet, latent with 16 experts."""
        defaults = dict(
            vocab_size=256, hidden_size=64, num_layers=4, dense_layers=1,
            full_attention_layers=(3,), num_heads=4, q_lora_rank=32,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, linear_key_heads=2, linear_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16, ffn_dim=128,
            expert_ffn_dim=32, num_experts=16, experts_per_token=3,
            max_seq_len=128, rope_theta=1e4, rope_factor=4.0,
            rope_original_max_len=32, dtype=jnp.float32,
            param_dtype=jnp.float32)
        defaults.update(kw)
        return GigaChat35Config(**defaults)


def layer_types(cfg: GigaChat35Config) -> Dict[str, Dict[str, Any]]:
    """``ServedModel.layer_types``: the latent blocks' pool of positions
    (read by ``latent_paged_attention``), then the DeltaNet layers' pool of
    records."""
    return {LATENT: {"layers": cfg.attention_blocks, "window": None},
            STATE: {"layers": cfg.delta_layers, "window": None,
                    "state": True}}


# ------------------------------------------------------------------ params

@functools.partial(jax.jit, static_argnames=("cfg",))
def gigachat3_5_init(key: jax.Array, cfg: GigaChat35Config) -> Dict[str, Any]:
    """Seeded parameters, ONE program (``models/longcat.py:longcat_init``
    says why).  ``layers`` is a list of L layers: ``{"norms": {pre_mix,
    post_mix, pre_ffn, post_ffn: [H] float32, zeros}, "attn":
    mla.init_block's (with ``w_g``, without its ``norm``) | "gdn": {w_qkv
    [H, C], w_z [H, Hv d], w_ba [H, 2 Hv], conv_w [K, C], A_log / dt_bias
    [Hv] float32, o_norm [d] float32 zeros, w_o [Hv d, H]}, "ffn": {w_gate,
    w_up, w_down} | "moe": models/deepseek_v3.py's}``.  ``w_qkv``'s columns
    are the convolution's channels, ``[q | k | v]`` with the heads leading;
    ``w_ba``'s are ``[b | a]``."""
    L, H = cfg.num_layers, cfg.hidden_size
    Fe, E, N = cfg.expert_ffn_dim, cfg.num_held, cfg.num_experts
    Hv, d = cfg.linear_value_heads, cfg.linear_value_head_dim
    pd = cfg.param_dtype
    keys = iter(jax.random.split(key, 2 + 16 * L))

    def w(*shape):
        return jax.random.normal(next(keys), shape, pd) * 0.02

    def ones(*shape):
        return jnp.ones(shape, pd)

    def zeros(*shape):
        return jnp.zeros(shape, jnp.float32)

    def mlp(F):
        return {"w_gate": w(H, F), "w_up": w(H, F), "w_down": w(F, H)}

    def centred(w_o):
        # silu gives q, k and v a positive mean, so every token's output
        # carries one direction in common; through random routers that
        # direction makes a few experts hot (16 of 256 took 42% of the
        # picks at the published widths, which 16 the seed's to say: the
        # held range's load, and the step's time, then follow the seed).
        # A trained router's selection bias balances the loads; seeded
        # weights have W_o's rows sum to zero over a head's channels
        # instead, which keeps a head's mean out of the residual stream.
        w_o = w_o.reshape(Hv, d, H)
        return (w_o - jnp.mean(w_o, axis=1, keepdims=True)).reshape(Hv * d, H)

    def gdn():
        # a decay of exp(-A dt) a position, A in [1, 16) and dt in
        # [0.001, 0.1) at u W_a = 0 (the family's initialisation)
        A = jax.random.uniform(next(keys), (Hv,), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(
            next(keys), (Hv,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
        return {"w_qkv": w(H, cfg.conv_channels), "w_z": w(H, Hv * d),
                "w_ba": w(H, 2 * Hv),
                "conv_w": w(cfg.linear_conv_kernel, cfg.conv_channels) * 10,
                "A_log": jnp.log(A), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "o_norm": zeros(d), "w_o": centred(w(Hv * d, H))}

    def layer(i):
        lp = {"norms": {n: zeros(H) for n in (
            "pre_mix", "post_mix", "pre_ffn", "post_ffn")}}
        if i in cfg.full_attention_layers:
            lp["attn"] = mla.init_block(w, ones, cfg)
            del lp["attn"]["norm"]  # the layer's pre_mix norm is its own
        else:
            lp["gdn"] = gdn()
        if i < cfg.dense_layers:
            lp["ffn"] = mlp(cfg.ffn_dim)
        else:
            lp["moe"] = {
                "router": {"w": w(H, N),
                           "bias": jnp.zeros((N,), jnp.float32)},
                "experts": {"w_gate": w(E, H, Fe), "w_up": w(E, H, Fe),
                            "w_down": w(E, Fe, H)},
                "shared": mlp(Fe * cfg.shared_experts)}
        return lp

    return {"embed": w(cfg.vocab_size, H),
            "layers": [layer(i) for i in range(L)],
            "final_norm": zeros(H),
            "lm_head": w(H, cfg.vocab_size)}


# ------------------------------------------------------------------ blocks

def gated_norm(x, w, eps: float, scale: float):
    """``x / rms(x) * scale sigmoid(w)`` over the last axis in float32,
    output in x.dtype: the zero-centred gated norm (``w = 0`` is a scale of
    ``scale / 2``)."""
    return rms_norm(x, scale * jax.nn.sigmoid(w.astype(jnp.float32)), eps)


def _norm(x, w, cfg):
    return gated_norm(x, w, cfg.rms_norm_eps, cfg.norm_gate_scale)


def clamped_swiglu(limit: float):
    """``activation(gate, up) = silu(min(gate, limit)) * clip(up, -limit,
    limit)``: what ``ops/experts.py``'s two paths take as ``activation``."""
    def activation(gate, up):
        return swiglu(jnp.minimum(gate, limit), jnp.clip(up, -limit, limit))
    return activation


_rope_table = mla.yarn_rope_table


def _mlp(x, fp, cfg: GigaChat35Config):
    dt = cfg.dtype
    act = clamped_swiglu(cfg.swiglu_limit)(x @ fp["w_gate"].astype(dt),
                                           x @ fp["w_up"].astype(dt))
    return act @ fp["w_down"].astype(dt)


def _moe(y, mp, cfg: GigaChat35Config, live):
    """The expert layer (``models/deepseek_v3.py:_moe`` without a group
    limit, every SwiGLU clamped).  y ``[b, s, H]`` (normed), live ``[b, s]``
    bool.  Returns (``[b, s, H]``, int32 ``[3]``: pairs on held experts,
    held experts hit, 0; of live tokens only)."""
    b, s, H = y.shape
    yf, lf = y.reshape(b * s, H), live.reshape(b * s)
    router, ep = mp["router"], mp["experts"]
    with tracing.scope("router"):
        idx, weight = route_top_k(
            yf, router["w"], router["bias"], cfg.experts_per_token,
            cfg.routed_scaling_factor, renormalise=cfg.norm_topk_prob,
            score="sigmoid")
    with tracing.scope("experts"):
        out, pairs, hit = held_experts_ffn(
            yf, idx, weight, ep["w_gate"], ep["w_up"], ep["w_down"],
            first=cfg.first_expert, live=lf,
            activation=clamped_swiglu(cfg.swiglu_limit))
        with tracing.scope("experts.shared"):  # every token's own chip
            out = out + _mlp(yf, mp["shared"], cfg).astype(jnp.float32)
        zero = jnp.zeros((), jnp.int32)
        return (out.astype(cfg.dtype).reshape(b, s, H),
                jnp.stack([pairs, hit, zero]).astype(jnp.int32))


def _gdn_inputs(u, gp, cfg: GigaChat35Config):
    """u ``[..., H]`` (normed) -> the convolution's input ``[..., C]``, the
    output gate's ``z [..., Hv, d]``, ``beta [..., Hv]`` and ``log alpha
    [..., Hv]`` (float32)."""
    dt, Hv = cfg.dtype, cfg.linear_value_heads
    with tracing.scope("attn.proj"):
        qkv = u @ gp["w_qkv"].astype(dt)
        # through heads_projection: folded with the reshape onto the heads,
        # XLA:TPU transposed the whole W_z every decode step
        z = heads_projection(u, gp["w_z"].astype(dt), Hv)
        ba = (u @ gp["w_ba"].astype(dt)).astype(jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :Hv])
        g = delta.log_decay(ba[..., Hv:], gp["A_log"], gp["dt_bias"])
        return qkv, z, beta, g


def _gdn_out(o, z, gp, cfg: GigaChat35Config):
    """o ``[..., Hv, d]`` float32, z the same in the model's dtype -> the
    mixer's output ``[..., H]``: the zero-centred gated norm a head, the
    sigmoid gate, ``W_o``."""
    with tracing.scope("attn.core"):
        y = gated_norm(o, gp["o_norm"], cfg.linear_norm_eps,
                       cfg.linear_gate_scale)
        y = y * (cfg.linear_gate_scale
                 * jax.nn.sigmoid(z.astype(jnp.float32)))
        y = y.astype(cfg.dtype).reshape(*o.shape[:-2], -1)
    with tracing.scope("attn.out"):
        return y @ gp["w_o"].astype(cfg.dtype)


def _gdn_sequence(u, gp, cfg: GigaChat35Config, length):
    """A DeltaNet layer over a whole sequence from a zero state: u ``[b, s,
    H]`` (normed) -> (out ``[b, s, H]``, state ``[b, Hv, d, d]`` float32,
    tail ``[b, (K - 1) C]``)."""
    b = u.shape[0]
    K, C = cfg.linear_conv_kernel, cfg.conv_channels
    Hv, d = cfg.linear_value_heads, cfg.linear_value_head_dim
    qkv, z, beta, g = _gdn_inputs(u, gp, cfg)
    with tracing.scope("attn.core"):
        with tracing.scope("gdn.conv"):
            qkv, tail = causal_conv1d(
                qkv, gp["conv_w"], jnp.zeros((C,), jnp.float32),
                jnp.zeros((b, K - 1, C), cfg.dtype), length)
            q, k, v = delta.delta_heads_of(
                qkv, cfg.linear_key_heads, Hv, cfg.linear_key_head_dim)
        with tracing.scope("gdn.scan"):
            o, state = delta.chunked_delta_scan(
                q, k, v, g, beta,
                jnp.zeros((b, Hv, cfg.linear_key_head_dim, d), jnp.float32),
                length)
    return _gdn_out(o, z, gp, cfg), state, tail.reshape(b, (K - 1) * C)


def _layers(params, x, cfg: GigaChat35Config, attend, mix, live):
    """The stack.  ``attend(u, ap) -> [b, s, H]``: a latent block's output
    (``mla.SuffixAttend`` / ``StepAttend`` / the plain form: projection,
    cache and ``W_o`` its own), called once a latent layer in the pool's
    order; ``mix(a, u, gp) -> [b, s, H]``: the ``a``-th DeltaNet layer's."""
    stats = jnp.zeros(len(COUNTERS), jnp.int32)
    a = 0
    for l, lp in enumerate(params["layers"]):
        norms = lp["norms"]
        with tracing.scope("attn.proj"):
            u = _norm(x, norms["pre_mix"], cfg)
        if "attn" in lp:
            o = attend(u, lp["attn"])  # opens attn.cache / .core / .out
        else:
            o = mix(a, u, lp["gdn"])
            a += 1
        with tracing.scope("attn.out"):
            x = x + _norm(o, norms["post_mix"], cfg)
        if "ffn" in lp:  # a leading dense layer
            with tracing.scope("ffn"):
                x = x + _norm(_mlp(_norm(x, norms["pre_ffn"], cfg),
                                   lp["ffn"], cfg), norms["post_ffn"], cfg)
            continue
        with tracing.scope("experts"):
            y = _norm(x, norms["pre_ffn"], cfg)
        out, st = _moe(y, lp["moe"], cfg, live)
        with tracing.scope("experts"):
            x = x + _norm(out, norms["post_ffn"], cfg)
        stats += st
    return x, stats


def _lm_head(params, cfg: GigaChat35Config, x):
    with tracing.scope("head"):
        x = _norm(x, params["final_norm"], cfg)
        return jnp.einsum("bsh,hv->bsv", x,
                          params["lm_head"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- programs

def gigachat3_5_apply(params, tokens, cfg: GigaChat35Config, *, mesh=None,
                      return_stats: bool = False):
    """tokens ``[b, s]`` -> logits ``[b, s, vocab]`` float32: the plain
    causal forward, no cache."""
    if mesh is not None:
        raise NotImplementedError("gigachat3_5 has no sharded forward yet")
    b, s = tokens.shape
    cos, sin = _rope_table(cfg, s)
    mask = jnp.broadcast_to(
        jnp.arange(s)[None, :, None] >= jnp.arange(s)[None, None, :],
        (b, s, s))
    path = prefill_attention_path(s, 0)

    def attend(u, ap):
        return mla.plain(*mla.project(u, ap, cfg, cos, sin, None), mask,
                         ap, cfg, path, gate=mla.output_gate(u, ap, cfg))

    def mix(a, u, gp):
        return _gdn_sequence(u, gp, cfg, None)[0]

    x, stats = _layers(params, embed_tokens(params, tokens, cfg.dtype),
                       cfg, attend, mix, jnp.ones((b, s), bool))
    logits = _lm_head(params, cfg, x)
    return (logits, stats) if return_stats else logits


def init_pools(cfg: GigaChat35Config, num_blocks: Dict[str, int],
               block_size: int, kv_dtype: str | None = None):
    """The two pools of the module's docstring; block 0 / record 0 of each
    is its scratch one."""
    pools = {LATENT: mla.init_latent_pool(cfg, num_blocks[LATENT],
                                          block_size, kv_dtype)}
    D, R = cfg.delta_layers, num_blocks[STATE]
    pools[STATE] = {
        "s": jnp.zeros((D, R, cfg.linear_value_heads,
                        cfg.linear_key_head_dim, cfg.linear_value_head_dim),
                       jnp.float32),
        "conv": jnp.zeros((D, R, (cfg.linear_conv_kernel - 1)
                           * cfg.conv_channels // cfg.linear_value_head_dim,
                           cfg.linear_value_head_dim), cfg.dtype)}
    return pools


def decode_attention_path(pool, *, mesh=None) -> str:
    """``paged_generation.decode_attention_path``'s rule for the pool of
    latent rows."""
    return _pool_attention_path(pool[LATENT], mesh=mesh)


def gather_prefix(pool, blocks, cfg: GigaChat35Config):
    """No prefix is ever cached for this model: the empty pair."""
    if blocks.shape[0]:
        raise NotImplementedError(
            "gigachat3_5 takes no prefix hits: the DeltaNet layers' state "
            "at the hit is not kept (docs/llm_serving.md)")
    A = cfg.attention_blocks
    return (jnp.zeros((A, 0, cfg.kv_lora_rank), cfg.dtype),
            jnp.zeros((A, 0, cfg.qk_rope_head_dim), cfg.dtype))


def prefill_suffix(params, tokens, length, start_pos, prefix_ckv,
                   prefix_kpe, prefix_len, dst_blocks, dst_offsets, pool,
                   cfg: GigaChat35Config, attn_impl: str = "auto"):
    """b=1 prefill of a whole prompt: ``paged_generation.prefill_suffix``'s
    contract with an empty prefix and ``dst_blocks`` by layer type:
    ``{"latent": [S]}`` block coordinates and ``{"state": [1]}`` the
    request's record, which is written from a zero state (the scan stops
    at ``length`` inside the bucket).  Returns ``(logits_at_last [1,
    vocab], pools, stats int32[3])``."""
    if prefix_ckv.shape[1]:
        raise NotImplementedError(
            "gigachat3_5 prefills a prompt whole: no cached prefix "
            "(docs/llm_serving.md)")
    _, S = tokens.shape
    attend = mla.SuffixAttend(
        pool[LATENT], cfg, *_rope_table(cfg, S), S, length, start_pos,
        prefix_ckv, prefix_kpe, prefix_len, dst_blocks[LATENT], dst_offsets,
        prefill_attention_path(S, 0, attn_impl))
    state = dict(pool[STATE])
    rec = dst_blocks[STATE][0]

    def mix(a, u, gp):
        out, s, tail = _gdn_sequence(u, gp, cfg, length)
        with tracing.scope("attn.cache"):  # the request's one record
            state["s"] = state["s"].at[a, rec].set(s[0])
            state["conv"] = state["conv"].at[a, rec].set(
                tail[0].reshape(state["conv"].shape[2:]))
        return out

    x, stats = _layers(params, embed_tokens(params, tokens, cfg.dtype),
                       cfg, attend, mix, attend.live)
    with tracing.scope("head"):  # the head for the last true position only
        last = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    return (_lm_head(params, cfg, last)[:, 0],
            {LATENT: {"kv": attend.kv}, STATE: state}, stats)


def decode_step(params, token, cur_len, block_tables, pool,
                cfg: GigaChat35Config, attn: str | None = None):
    """One token for every slot: ``paged_generation.paged_decode_step``'s
    contract with ``block_tables`` and ``pool`` by layer type
    (``block_tables["state"]`` ``[b, 1]``: each slot's record).  Returns
    ``(logits [b, vocab], pools, stats int32[3])``; a slot whose LATENT
    table row is all scratch holds no request: it attends over nothing, is
    routed to no expert, moves no record and is not counted."""
    if attn is None:
        attn = decode_attention_path(pool)
    ML = block_tables[LATENT].shape[1] * pool[LATENT]["kv"].shape[2]
    with tracing.scope("attn.proj"):  # the rotary table
        cos, sin = _rope_table(cfg, ML)
    attend = mla.StepAttend(pool[LATENT], cfg, cos, sin, cur_len,
                            block_tables[LATENT], attn)
    state = dict(pool[STATE])
    with tracing.scope("attn.cache"):
        rec = jnp.where(attend.live, block_tables[STATE][:, 0], 0)
    update = delta.delta_update_path(state)

    def mix(a, u, gp):
        qkv, z, beta, g = _gdn_inputs(u[:, 0], gp, cfg)
        with tracing.scope("attn.core"):
            # the convolution, the heads' vectors and the rule, on the live
            # slots' tails and records where they lie
            o, new = delta.delta_update_records(
                qkv, jnp.exp(g), beta, gp["conv_w"], state, a, rec, update)
        state.update(new)
        return _gdn_out(o, z, gp, cfg)[:, None]

    x, stats = _layers(params,
                       embed_tokens(params, token, cfg.dtype)[:, None], cfg,
                       attend, mix, attend.live[:, None])
    return (_lm_head(params, cfg, x)[:, 0],
            {LATENT: {"kv": attend.kv}, STATE: state}, stats)


def decode_sample(params, token, cur_len, block_tables, pool, key, temps,
                  cfg: GigaChat35Config, attn: str | None = None):
    """``paged_generation.paged_decode_sample``'s contract (on-device
    sampling, every output the next step needs a device array), plus the
    step's three expert counters."""
    ML = block_tables[LATENT].shape[1] * pool[LATENT]["kv"].shape[2]
    safe_cur = jnp.minimum(cur_len, ML - 1)
    logits, pool, stats = decode_step(
        params, token, safe_cur, block_tables, pool, cfg=cfg, attn=attn)
    nxt, key = sample_next(logits, key, temps)
    return nxt, cur_len + 1, key, pool, stats
