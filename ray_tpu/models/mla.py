"""Latent attention (MLA) with a paged cache of latent rows: the attention
block that ``models/longcat.py`` (LongCat-Flash) and ``models/deepseek_v3.py``
(DeepSeek-V3's family) share.

Queries go through a rank-``q_lora_rank`` bottleneck; keys and values are
up-projections of ONE latent row a token, ``c_kv`` (``kv_lora_rank`` wide,
normed) beside a rotated ``k_pe`` (``qk_rope_head_dim`` wide) that all heads
share.  **The cache holds that row** and nothing per head.  A prefill
up-projects the rows it attends over (the cached prefix's too) and runs the
non-absorbed form (``plain``): keys ``[k_nope | k_pe]`` of
``qk_nope_head_dim + qk_rope_head_dim`` beside values of ``v_head_dim``,
through the flash kernel where ``prefill_attention_path`` says so and by
float32 scores a group of heads at a time elsewhere.  A decode step absorbs
``W_kvb`` (``absorbed``): ``q_nope W_kvb,k^T`` is scored against ``c_kv``
itself, the probabilities weight ``c_kv``, and ``W_kvb,v`` then ``W_o``
follow; it reads the two halves as leaves of their own (``w_uk``, ``w_uv``:
``absorbed_pair``), laid out for those two products.

What the two models differ in is read off the configuration a function is
handed (``LatentWidths`` says what it has to offer): the block's widths
(``v_head_dim`` 128 beside 192), whether the two latents are scaled after
their norms (``mla_scale_q_lora`` / ``mla_scale_kv_lora``: LongCat's own),
the softmax scale (``softmax_scale``: ``(nope + rope)^-0.5``, times YaRN's
temperature where the model stretches its context), whether the heads'
outputs are gated before ``W_o`` (``gated_attention``) and how many
attention blocks the pool stacks (``attention_blocks``).  The rotary table is the
caller's (``cos``, ``sin``).  The programs around the block (a prefill of a
suffix, a decode step) are each model's own, because they walk its own
stack; what such a program does an attention block (``SuffixAttend``,
``StepAttend``: project, write the cache, attend) is here.

The paged latent pool is ``{"kv": [A, NB, bs, W]}``: one row a token and
attention block, ``[c_kv | k_pe | 0]``, ``W`` the row padded to whole
128-lane tiles (576 -> 640: a TPU array's minor dimension is tiled by 128
in HBM whether or not the program says so, and the kernel copies whole
pages).  A decode step reads it through ``ops/pallas/paged_attention.py``'s
latent arm where ``decode_attention_path`` says so, and by a gather of the
whole table elsewhere (the CPU, the tests' reference): ``attend_rows``.

An attention block's leaves: ``norm [H]``, ``w_qa [H, qr]``, ``q_norm
[qr]``, ``w_qb [qr, nh * (dn + dr)]``, ``w_kva [H, kr + dr]``, ``kv_norm
[kr]``, ``w_kvb [kr, nh * (dn + dv)]``, ``w_uk`` / ``w_uv`` (derived), ``w_o
[nh * dv, H]``, and ``w_g [H, nh * dv]`` where the model gates the block's
output (``gated_attention``: ``output_gate``).  The rope columns of ``w_qb`` and ``w_kva`` are held
de-interleaved (published column ``2i`` at ``i``, ``2i+1`` at ``i + d/2``:
``ops/layers.apply_rope`` rotates ``(i, i + d/2)`` where the published code
rotates ``(2i, 2i+1)``; a score is a dot product and does not see the
order).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu._private import tracing
from ray_tpu.ops.attention import attention_impl, dot_product_attention
from ray_tpu.ops.layers import (apply_rope, heads_projection, rms_norm,
                                yarn_mscale, yarn_rope_frequencies)

_LANES = 128


class LatentWidths:
    """What this module reads of a model's configuration beside its fields
    (``hidden_size``, ``num_heads``, ``q_lora_rank``, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``rms_norm_eps``, ``dtype``, ``attention_blocks``): the defaults a
    configuration overrides where its model departs from them."""

    # the latents scaled by sqrt(H / rank) after their norms
    mla_scale_q_lora = False
    mla_scale_kv_lora = False
    # a gate on the block's output, before W_o (``output_gate``)
    gated_attention = False

    @property
    def latent_width(self) -> int:
        """A cached row, padded to whole lane tiles."""
        w = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-w // _LANES) * _LANES

    @property
    def softmax_scale(self) -> float:
        return float(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


class YarnLatentWidths(LatentWidths):
    """A model that stretches its context by YaRN as DeepSeek-V3's family
    publishes it (fields ``rope_theta``, ``rope_factor``,
    ``rope_original_max_len``, ``rope_beta_fast``, ``rope_beta_slow``,
    ``rope_mscale``, ``rope_mscale_all_dim``): the table is
    ``yarn_rope_table``'s, and the temperature enters the softmax scale
    squared, at every position."""

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return float(self.qk_nope_head_dim
                     + self.qk_rope_head_dim) ** -0.5 * m * m


def yarn_rope_table(cfg: YarnLatentWidths, positions: int):
    """(cos, sin) over ``positions`` for the block's rope columns; at a
    factor of 1 the plain table."""
    return yarn_rope_frequencies(
        cfg.qk_rope_head_dim, positions, cfg.rope_theta,
        factor=cfg.rope_factor,
        original_max_len=cfg.rope_original_max_len,
        beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow,
        mscale=cfg.rope_mscale, mscale_all_dim=cfg.rope_mscale_all_dim)


# ------------------------------------------------------------------ params

def absorbed_pair(w_kvb, cfg):
    """``w_kvb [kr, nh * (dn + dv)]`` (the published ``kv_b_proj``) ->
    ``(w_uk [nh, dn, kr], w_uv [nh, kr, dv])``: its keys' half and its
    values' half with the heads leading, each laid out as the decode
    step's absorbed product streams it (``absorbed``).  A slice and a
    transposition, no arithmetic: every element of ``w_kvb`` is in exactly
    one of the two, bit for bit.

    The pair is DERIVED, not trained: a model's ``init`` makes it here from
    the ``w_kvb`` it has just drawn, and whoever else writes ``w_kvb`` (a
    checkpoint loader after reading ``kv_b_proj``, an update of the
    weights) calls this again, or the decode step keeps multiplying by the
    old matrix while the prefill uses the new one."""
    kr, nh, dn = cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim
    w = w_kvb.reshape(kr, nh, -1)
    return (jnp.transpose(w[..., :dn], (1, 2, 0)),
            jnp.transpose(w[..., dn:], (1, 0, 2)))


def init_block(w, ones, cfg):
    """One attention block's leaves; ``w(*shape)`` draws a weight,
    ``ones(*shape)`` a norm's scale.  ``kv_b_proj`` is THREE leaves:
    ``w_kvb [kr, nh * (dn + dv)]``, the published matrix, which the prefill
    (``plain``), the plain forward and the benchmark's reference read and
    through which alone a gradient flows; and ``w_uk [nh, dn, kr]`` /
    ``w_uv [nh, kr, dv]``, its two halves as the decode step's absorbed
    products read them (``absorbed``, which never touches ``w_kvb``), made
    from it here by ``absorbed_pair`` and by nobody else.  The second copy
    costs ``kr * nh * (dn + dv)`` parameters a block: 16.8 MB in bf16 at
    LongCat's widths, 21.0 MB at 192-wide values."""
    H, nh = cfg.hidden_size, cfg.num_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    w_kvb = w(kr, nh * (dn + dv))
    w_uk, w_uv = absorbed_pair(w_kvb, cfg)
    gate = {"w_g": w(H, nh * dv)} if cfg.gated_attention else {}
    return {"norm": ones(H), "w_qa": w(H, qr), "q_norm": ones(qr),
            "w_qb": w(qr, nh * (dn + dr)), "w_kva": w(H, kr + dr),
            "kv_norm": ones(kr), "w_kvb": w_kvb, "w_uk": w_uk,
            "w_uv": w_uv, **gate, "w_o": w(nh * dv, H)}


# ------------------------------------------------------------------ blocks

def project(x, ap, cfg, cos, sin, positions):
    """x ``[b, s, H]`` -> q_nope ``[b, s, nh, dn]``, q_pe ``[b, s, nh, dr]``
    (rotated), c_kv ``[b, s, kr]`` (normed, and scaled where the model
    does: what the cache holds), k_pe ``[b, s, dr]`` (rotated, shared by
    the heads)."""
    H = x.shape[-1]
    dt = cfg.dtype
    nh, dn = cfg.num_heads, cfg.qk_nope_head_dim
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    with tracing.scope("attn.proj"):
        c_q = rms_norm(x @ ap["w_qa"].astype(dt), ap["q_norm"],
                       cfg.rms_norm_eps)
        if cfg.mla_scale_q_lora:
            c_q = c_q * (H / qr) ** 0.5
        q = heads_projection(c_q, ap["w_qb"].astype(dt), nh)
        kv = x @ ap["w_kva"].astype(dt)
        c_kv = rms_norm(kv[..., :kr], ap["kv_norm"], cfg.rms_norm_eps)
        if cfg.mla_scale_kv_lora:
            c_kv = c_kv * (H / kr) ** 0.5
        q_pe = apply_rope(q[..., dn:], cos, sin, positions)
        k_pe = apply_rope(kv[..., kr:][:, :, None], cos, sin,
                          positions)[:, :, 0]
        return q[..., :dn], q_pe, c_kv, k_pe


def output_gate(x, ap, cfg):
    """x ``[..., H]`` (the block's normed input) -> ``sigmoid(x W_g) [...,
    nh, dv]`` where the model gates its attention block's output (``out =
    (attn * sigmoid(x W_g)) W_o``, a head's own columns of ``W_g`` on its
    own values), None where it does not: ``plain`` and ``absorbed`` take
    it as ``gate``.  The product goes through ``heads_projection``: folded
    with the reshape onto the heads, XLA:TPU transposed the whole ``W_g``
    (117 MB) every decode step (my chip run, PR 52)."""
    if not cfg.gated_attention:
        return None
    with tracing.scope("attn.proj"):
        g = heads_projection(x, ap["w_g"].astype(cfg.dtype), cfg.num_heads)
        return jax.nn.sigmoid(g.astype(jnp.float32)).astype(cfg.dtype)


def _gated(out, gate):
    """``out [..., nh, dv]`` times its gate."""
    if gate is None:
        return out
    with tracing.scope("attn.core"), tracing.scope("attn.gate"):
        return out * gate


def prefill_attention_path(seq: int, prefix: int, impl: str = "auto",
                           rule=attention_impl) -> str:
    """Which form ``seq`` queries attend through after ``prefix`` cached rows
    (the padded counts a program is traced at): ``"flash"`` | ``"plain"``.
    Flash where the keys are exactly the queries' positions (no prefix: the
    mask is then the causal one for every live query, which is the only mask
    the kernel builds) and ``dot_product_attention``'s own rule
    (``rule``: ``attention_impl``, handed in by a model so that a test can
    steer the model's own) picks the kernel: one TPU device, 256 queries or
    more.  ``impl="flash"`` stands in for that rule (the tests' interpreter,
    a compile for the chip from the CPU); a prefix keeps the plain form
    whatever it says."""
    if prefix == 0 and (impl == "flash" or impl == "auto"
                        and rule(seq) == "flash"):
        return "flash"
    return "plain"


# heads a score matrix is made for at a time where it is large: a 2048-token
# prefill's float32 scores are 16 MB a head, 1 GB for all 64 at once
_HEAD_GROUP = 16


def plain(q_nope, q_pe, c_kv, k_pe, mask, ap, cfg, path: str, gate=None):
    """The non-absorbed form over rows ``c_kv [b, t, kr]`` / ``k_pe
    [b, t, dr]`` (up-projected here); mask ``[b, s, t]``; ``path`` what the
    caller's ``prefill_attention_path(s, t - s)`` gave.  With ``t == s``
    the callers' mask is causal for every live query, and the flash kernel
    takes it from there: all heads in one call, keys ``[k_nope | k_pe]``
    beside values of their own width, no score matrix in HBM.  ``gate``:
    ``output_gate``'s, on the heads' outputs before ``W_o``."""
    b, s, nh, dn = q_nope.shape
    t = c_kv.shape[1]
    dt, dv, scale = cfg.dtype, cfg.v_head_dim, cfg.softmax_scale
    with tracing.scope("attn.proj"):  # the cached rows' up-projection
        kvb = (c_kv @ ap["w_kvb"].astype(dt)).reshape(b, t, nh, dn + dv)

    def heads(args):
        qn, qr, kn, v = args  # [b, s|t, g, d]: one group of heads
        scores = (jnp.einsum("bshd,bthd->bhst", qn, kn,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshr,btr->bhst", qr, k_pe,
                               preferred_element_type=jnp.float32))
        scores = jnp.where(mask[:, None], scores * scale, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        return jnp.einsum("bhst,bthd->bshd", probs, v,
                          preferred_element_type=jnp.float32).astype(dt)

    parts = (q_nope, q_pe, kvb[..., :dn], kvb[..., dn:])
    g = _HEAD_GROUP
    with tracing.scope("attn.core"):
        if path == "flash":
            pe = jnp.broadcast_to(k_pe[:, :, None],
                                  (b, t, nh, k_pe.shape[-1]))
            out = dot_product_attention(
                jnp.concatenate([q_nope, q_pe], -1),
                jnp.concatenate([kvb[..., :dn], pe], -1), kvb[..., dn:],
                causal=True, impl="flash", scale=scale)
        elif nh <= g or nh % g:
            out = heads(parts)
        else:  # one group of heads after another
            split = lambda a: jnp.moveaxis(  # noqa: E731
                a.reshape(*a.shape[:2], nh // g, g, a.shape[-1]), 2, 0)
            out = jax.lax.map(heads, tuple(split(a) for a in parts))
            out = jnp.moveaxis(out, 0, 2).reshape(b, s, nh, dv)
    out = _gated(out, gate)
    with tracing.scope("attn.out"):
        return out.reshape(b, s, nh * dv) @ ap["w_o"].astype(dt)


def absorbed(q_nope, q_pe, ap, cfg, attend_rows, gate=None):
    """One query token a slot, ``W_kvb`` absorbed.  q_nope ``[b, nh, dn]``,
    q_pe ``[b, nh, dr]``; ``attend_rows(q [b, nh, W]) -> [b, nh, kr]``
    scores the query against the cached rows and returns the weighted
    ``c_kv``.  ``gate``: ``output_gate``'s, which multiplies after
    ``w_uv`` (the heads' values are not there before it).

    Both products are batched over the heads and read the block's derived
    pair (``absorbed_pair``), never ``w_kvb``: as strided halves of that one
    leaf, laid out for the prefill's ``c_kv @ w_kvb``, XLA:TPU fetched the
    whole matrix transposed into fast memory in every block of every step.
    The way in is spelt with the heads leading on both sides and each swap
    held apart from the product by a barrier: left to itself XLA multiplies
    with the slots minor (``[nh, kr, b]``) and transposes the result back
    for the kernel, which takes ``[b, nh, W]``; held apart, the product
    emits ``[nh, b, kr]`` and the swap is a permutation of whole rows.
    Either alone buys nothing (the pair under the plain spelling 0.04 ms of
    a 14.6 ms step, the spelling over ``w_kvb``'s halves none), together
    0.56 ms: PERF.md section 6, PR 45;
    ``tests/test_flash_compile_v5e.py`` holds the compiled program to it."""
    b, nh, dn = q_nope.shape
    dt, kr, dv = cfg.dtype, cfg.kv_lora_rank, cfg.v_head_dim
    barrier = jax.lax.optimization_barrier
    with tracing.scope("attn.proj"):  # the query into the latent space
        q_lat = jnp.einsum("hbd,hdk->hbk",
                           barrier(jnp.swapaxes(q_nope, 0, 1)),
                           ap["w_uk"].astype(dt),
                           preferred_element_type=jnp.float32).astype(dt)
        q_lat = jnp.swapaxes(barrier(q_lat), 0, 1)
        pad = cfg.latent_width - kr - q_pe.shape[-1]
        q = jnp.concatenate(
            [q_lat, q_pe, jnp.zeros((b, nh, pad), dt)], axis=-1)
    with tracing.scope("attn.core"):
        o_lat = attend_rows(q)
    with tracing.scope("attn.out"):  # out of it again, then W_o
        out = jnp.einsum("bhk,hkd->bhd", o_lat, ap["w_uv"].astype(dt),
                         preferred_element_type=jnp.float32).astype(dt)
    out = _gated(out, gate)
    with tracing.scope("attn.out"):
        return out.reshape(b, nh * dv) @ ap["w_o"].astype(dt)


def pack_rows(c_kv, k_pe, cfg):
    """``[..., kr]``, ``[..., dr]`` -> the cached row ``[..., W]``."""
    pad = cfg.latent_width - c_kv.shape[-1] - k_pe.shape[-1]
    return jnp.concatenate(
        [c_kv, k_pe, jnp.zeros((*c_kv.shape[:-1], pad), c_kv.dtype)], -1)


# ------------------------------------------------------------------ the pool

def init_latent_pool(cfg, num_blocks: int, block_size: int,
                     kv_dtype: str | None = None):
    """``{"kv": [A, NB, bs, W]}``, ``A`` the model's attention blocks;
    block 0 is the scratch block."""
    if kv_dtype not in (None, "auto"):
        raise ValueError(
            f"the latent pool is stored in the model's dtype: kv_dtype "
            f"{kv_dtype!r} is not supported for a latent-attention model "
            f"(None/'auto')")
    return {"kv": jnp.zeros((cfg.attention_blocks, num_blocks, block_size,
                             cfg.latent_width), cfg.dtype)}


def gather_latent_prefix(pool, blocks, cfg):
    """The cached rows of a block list ``[P]``: (c_kv ``[A, P*bs, kr]``,
    k_pe ``[A, P*bs, dr]``)."""
    A, _, bs, W = pool["kv"].shape
    rows = pool["kv"][:, blocks].reshape(A, blocks.shape[0] * bs, W)
    kr = cfg.kv_lora_rank
    return rows[..., :kr], rows[..., kr:kr + cfg.qk_rope_head_dim]


def prefill_masks(S: int, P: int, length, prefix_len):
    """A suffix of ``S`` padded tokens (``length`` live) after ``P`` padded
    cached rows (``prefix_len`` live): (mask ``[1, S, P + S]``, live
    ``[1, S]``)."""
    sfx = jnp.arange(S)
    pmask = jnp.arange(P)[None, None, :] < prefix_len
    smask = (sfx[None, None, :] <= sfx[None, :, None]) & (
        sfx[None, None, :] < length)
    mask = jnp.concatenate(
        [jnp.broadcast_to(pmask, (1, S, P)), smask], axis=-1)
    return mask, (sfx < length)[None, :]


def attend_rows(kv, block: int, block_tables, cur_len, lengths, cfg,
                attn: str):
    """``absorbed``'s ``attend_rows`` over attention block ``block`` of the
    stacked pool ``kv``: the latent arm of the paged kernel
    (``attn == "latent_kernel"``: each slot's live blocks, ``lengths``) or a
    gather of the whole table under a mask (``cur_len``)."""
    b, MB = block_tables.shape
    bs = kv.shape[2]
    dt, kr, scale = cfg.dtype, cfg.kv_lora_rank, cfg.softmax_scale

    def kernel(q):
        from ray_tpu.ops.pallas.paged_attention import latent_paged_attention

        return latent_paged_attention(
            q, kv, block_tables, lengths, layer=block, value_width=kr,
            scale=scale)

    def gather(q):
        mask = jnp.arange(MB * bs)[None, None, :] <= cur_len[:, None, None]
        g = kv[block, block_tables].reshape(b, MB * bs, -1)
        scores = jnp.einsum("bhw,btw->bht", q, g,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
        return jnp.einsum("bht,btk->bhk", probs, g[..., :kr],
                          preferred_element_type=jnp.float32).astype(dt)

    return kernel if attn == "latent_kernel" else gather


# ------------------------------------------------- a program's attention

def _gate_of(xn, ap, cfg) -> dict:
    """``plain`` / ``absorbed``'s ``gate`` argument, only where the model
    has one: a model without it calls them as it always did."""
    gate = output_gate(xn, ap, cfg)
    return {} if gate is None else {"gate": gate}


class SuffixAttend:
    """``attend(x_normed [1, S, H], ap) -> [1, S, H]`` of a b=1 prefill of a
    prompt *suffix* (``S`` padded tokens, ``length`` live, from position
    ``start_pos``) against a cached prefix (``gather_latent_prefix``'s pair,
    ``prefix_len`` rows live): a model's layer loop calls it once an
    attention block, in the pool's order.  It projects, writes the
    suffix's rows at ``(dst_blocks, dst_offsets)`` (pad lanes land in the
    scratch block), up-projects the prefix's rows with the suffix's and
    runs ``plain`` over both (``path``: the caller's
    ``prefill_attention_path``).  ``kv`` is the pool's array after the
    blocks called so far, ``live [1, S]`` the tokens that are no padding."""

    def __init__(self, pool, cfg, cos, sin, S: int, length, start_pos,
                 prefix_ckv, prefix_kpe, prefix_len, dst_blocks,
                 dst_offsets, path: str):
        self.kv, self.block = pool["kv"], 0
        self.cfg, self.rope, self.path = cfg, (cos, sin), path
        self.positions = start_pos + jnp.arange(S)[None, :]
        self.prefix = (prefix_ckv, prefix_kpe)
        self.dst = (dst_blocks, dst_offsets)
        self.mask, self.live = prefill_masks(S, prefix_ckv.shape[1], length,
                                             prefix_len)

    def __call__(self, xn, ap):
        cfg, a = self.cfg, self.block
        dt = cfg.dtype
        q_nope, q_pe, c_kv, k_pe = project(xn, ap, cfg, *self.rope,
                                           self.positions)
        with tracing.scope("attn.cache"):
            self.kv = self.kv.at[a, self.dst[0], self.dst[1]].set(
                pack_rows(c_kv[0], k_pe[0], cfg))
            c_all = jnp.concatenate(
                [self.prefix[0][a][None].astype(dt), c_kv], 1)
            pe_all = jnp.concatenate(
                [self.prefix[1][a][None].astype(dt), k_pe], 1)
        self.block += 1
        return plain(q_nope, q_pe, c_all, pe_all, self.mask, ap, cfg,
                     self.path, **_gate_of(xn, ap, cfg))


class StepAttend:
    """``attend(x_normed [b, 1, H], ap) -> [b, 1, H]`` of a decode step:
    one token a slot at position ``cur_len`` against block-table caches of
    latent rows.  It projects, writes the new row first (so that the token
    attends to itself) and runs ``absorbed`` (the caller's name for it)
    over ``attend_rows`` (``attn``: ``decode_attention_path``'s answer).
    ``kv`` is the pool's array after the blocks called so far; ``live
    [b]``: a slot whose table row is all scratch holds no request and
    attends over nothing."""

    def __init__(self, pool, cfg, cos, sin, cur_len, block_tables,
                 attn: str, absorbed_form=None):
        self.kv, self.block = pool["kv"], 0
        self.cfg, self.rope, self.attn = cfg, (cos, sin), attn
        self.absorbed = absorbed_form or absorbed
        self.cur_len, self.tables = cur_len, block_tables
        bs = self.kv.shape[2]
        with tracing.scope("attn.cache"):  # where the step's rows go
            self.blk = block_tables[jnp.arange(cur_len.shape[0]),
                                    cur_len // bs]
            self.off = cur_len % bs
            self.live = block_tables[:, 0] != 0
            self.lengths = jnp.where(self.live, cur_len + 1, 0)

    def __call__(self, xn, ap):
        cfg = self.cfg
        q_nope, q_pe, c_kv, k_pe = project(xn, ap, cfg, *self.rope,
                                           self.cur_len[:, None])
        with tracing.scope("attn.cache"):
            self.kv = self.kv.at[self.block, self.blk, self.off].set(
                pack_rows(c_kv[:, 0], k_pe[:, 0], cfg))
        rows = attend_rows(self.kv, self.block, self.tables, self.cur_len,
                           self.lengths, cfg, self.attn)
        self.block += 1
        with tracing.scope("attn.proj"):
            q_nope, q_pe = q_nope[:, 0], q_pe[:, 0]
        return self.absorbed(q_nope, q_pe, ap, cfg, rows,
                             **_gate_of(xn[:, 0], ap, cfg))[:, None]
