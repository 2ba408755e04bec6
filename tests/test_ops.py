"""Tests for ray_tpu.ops: attention kernels, norms, rope."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# JAX-compile-heavy tier: deselect with -m 'not slow' for fast runs
pytestmark = pytest.mark.slow

from ray_tpu.ops.attention import (
    dot_product_attention,
    reference_attention,
    ring_attention,
)
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies, swiglu
from ray_tpu.ops.pallas.flash_attention import flash_attention
from ray_tpu.parallel import MeshConfig, create_mesh


def _qkv(b=2, s=128, h=4, kvh=2, d=64, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kvh, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kvh, d), dtype)
    return q, k, v


class TestReferenceAttention:
    def test_causal_masks_future(self):
        q, k, v = _qkv(s=16)
        out = reference_attention(q, k, v, causal=True)
        # Row 0 attends only to position 0 → equals v[:, 0] (GQA-expanded).
        expected = jnp.repeat(v[:, 0], 2, axis=1)
        np.testing.assert_allclose(out[:, 0], expected, rtol=1e-5)

    def test_matches_jax_builtin(self):
        q, k, v = _qkv(h=4, kvh=4)
        ours = reference_attention(q, k, v, causal=True)
        jaxs = jax.nn.dot_product_attention(q, k, v, is_causal=True)
        np.testing.assert_allclose(ours, jaxs, atol=1e-5)


def _pallas_calls(jaxpr, path=""):
    """(path of enclosing primitives, number of results) of every
    ``pallas_call`` in a jaxpr and the jaxprs nested in it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((path, len(eqn.outvars)))
        for sub in eqn.params.values():
            for inner in sub if isinstance(sub, (list, tuple)) else [sub]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found += _pallas_calls(
                        inner, f"{path}/{eqn.primitive.name}")
    return found


# (causal, heads, kv heads, sq, sk, block).  Block 32: today's cases, no
# strips (a block under two vector tiles is not cut).  Block 256 is cut in
# strips of 128: 768 meets whole blocks below the diagonal, blocks on it
# and the part of them above it; 700 pads the last block; 100 is shorter
# than a block; 300 against 600 has unequal lengths.
_GRAD_CASES = [
    pytest.param(True, 4, 2, 64, 64, 32, id="s64-block32"),
    pytest.param(True, 4, 2, 48, 48, 32, id="s48-block32-padded"),
] + [
    pytest.param(causal, 4, kvh, sq, sk, 256,
                 id=f"{'causal' if causal else 'full'}-rep{4 // kvh}-{name}")
    for causal in (True, False)
    for kvh in (4, 1)
    for name, sq, sk in (("multiple", 768, 768), ("padded", 700, 700),
                         ("short", 100, 100), ("unequal", 300, 600))
]


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        ref = reference_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
        # Interpret mode emulates MXU bf16 matmul precision.
        np.testing.assert_allclose(out, ref, atol=2e-2)

    @pytest.mark.parametrize("causal,h,kvh,sq,sk,block", _GRAD_CASES)
    def test_grad_matches_reference(self, causal, h, kvh, sq, sk, block):
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (2, sq, h, 32))
        k = jax.random.normal(ks[1], (2, sk, kvh, 32))
        v = jax.random.normal(ks[2], (2, sk, kvh, 32))
        # a random cotangent: under loss = sum(out) dO is constant and a
        # transposed or mis-indexed dO block would go unnoticed
        w = jax.random.normal(ks[3], (2, sq, h, 32))

        def grads(attn):
            return jax.grad(
                lambda *a: (attn(*a, causal=causal) * w).sum(),
                argnums=(0, 1, 2))(q, k, v)

        g = grads(functools.partial(
            flash_attention, block_q=block, block_k=block))
        gr = grads(reference_attention)
        for name, a, b in zip(("dq", "dk", "dv"), g, gr):
            np.testing.assert_allclose(a, b, atol=2e-3, err_msg=name)

    # (d, dv, heads, kv heads, length, block, scale): values narrower and
    # wider than the keys (latent attention's non-absorbed form is 192
    # beside 128), causal; 300 of block 256 pads the last block and cuts
    # the diagonal block in strips, 100 is shorter than a block
    @pytest.mark.parametrize("d,dv,h,kvh,s,block,scale", [
        pytest.param(48, 32, 4, 4, 256, 128, None, id="narrower-values"),
        pytest.param(32, 64, 4, 4, 256, 128, None, id="wider-values"),
        pytest.param(48, 32, 4, 4, 300, 256, None, id="narrower-padded"),
        pytest.param(32, 64, 4, 1, 300, 256, None, id="wider-padded-gqa"),
        pytest.param(48, 32, 4, 2, 100, 256, None, id="narrower-short-gqa"),
        pytest.param(48, 32, 4, 2, 300, 256, 0.3, id="narrower-own-scale"),
        pytest.param(32, 64, 4, 4, 256, 128, 0.3, id="wider-own-scale"),
    ])
    def test_unequal_widths_match_reference(self, d, dv, h, kvh, s, block,
                                            scale):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (2, s, h, d))
        k = jax.random.normal(ks[1], (2, s, kvh, d))
        v = jax.random.normal(ks[2], (2, s, kvh, dv))
        ref = reference_attention(q, k, v, causal=True, scale=scale)
        out = flash_attention(q, k, v, causal=True, block_q=block,
                              block_k=block, scale=scale)
        assert out.shape == (2, s, h, dv)
        np.testing.assert_allclose(out, ref, atol=2e-2)
        # the default scale is that of the keys' width: a kernel that
        # took the values' would be off by far more than the tolerance
        if scale is None:
            other = reference_attention(q, k, v, causal=True,
                                        scale=dv ** -0.5)
            assert float(jnp.abs(other - ref).max()) > 0.05

    @pytest.mark.parametrize("dv,scale", [(32, None), (64, None),
                                          (48, 0.3)],
                             ids=["narrower", "wider", "own-scale"])
    def test_forward_only_calls_refuse_a_gradient_by_name(self, dv, scale):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (1, 64, 2, 48))
        k = jax.random.normal(ks[1], (1, 64, 2, 48))
        v = jax.random.normal(ks[2], (1, 64, 2, dv))
        with pytest.raises(NotImplementedError, match="forward only"):
            jax.grad(lambda q: flash_attention(
                q, k, v, block_q=32, block_k=32, scale=scale).sum())(q)

    def test_forward_only_calls_share_one_lowered_kernel(self):
        """Two calls of a program at the same shapes are one function of
        its module, called twice (a model's attention blocks: lowering the
        kernel is Python work no compile cache saves)."""
        q = jnp.zeros((1, 64, 2, 48))
        v = jnp.zeros((1, 64, 2, 32))

        def two(q, v):
            o = flash_attention(q, q, v, block_q=32, block_k=32)
            return flash_attention(q + o.sum(), q, v, block_q=32, block_k=32)

        text = jax.jit(two).lower(q, v).as_text()
        assert text.count("call @_flash_forward_only") == 2
        assert text.count("func.func private @_flash_forward_only") == 1

    def test_sharded_call_refuses_unequal_widths(self):
        from ray_tpu.ops.attention import dot_product_attention

        mesh = create_mesh(MeshConfig(dp=8, fsdp=1, tp=1, sp=1))
        q = jnp.zeros((8, 64, 2, 48))
        with pytest.raises(NotImplementedError, match="one device only"):
            dot_product_attention(q, q, q[..., :32], impl="flash", mesh=mesh)

    def test_one_forward_and_one_backward_kernel_a_layer(self):
        """Under ``save_attn`` the gradient of a scanned model holds one
        ``pallas_call`` in the forward layer body (the forward kernel: out
        and lse) and one in the backward body (the one backward kernel:
        dq, dk, dv): no forward replay, no second backward kernel."""
        from ray_tpu.models.llama import LlamaConfig, llama_init, llama_loss

        def kernels(policy):
            cfg = LlamaConfig.tiny(attention_impl="flash",
                                   remat_policy=policy)
            params = llama_init(jax.random.PRNGKey(0), cfg)
            batch = {"tokens": jnp.zeros((1, 33), jnp.int32)}
            jaxpr = jax.make_jaxpr(jax.grad(
                lambda p: llama_loss(p, batch, cfg)))(params)
            return sorted(_pallas_calls(jaxpr.jaxpr))

        assert kernels("save_attn") == [("/scan", 2), ("/scan/remat2", 3)]
        # the control: with nothing saved the forward kernel is replayed
        assert kernels("full") == [
            ("/scan", 2), ("/scan/remat2", 2), ("/scan/remat2", 3)]


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference_sp4(self, causal):
        mesh = create_mesh(MeshConfig(dp=2, fsdp=1, tp=1, sp=4))
        q, k, v = _qkv(b=2, s=64, h=4, kvh=2, d=32)
        ref = reference_attention(q, k, v, causal=causal)
        out = ring_attention(q, k, v, mesh=mesh, causal=causal)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_under_jit_with_tp(self):
        mesh = create_mesh(MeshConfig(dp=1, fsdp=1, tp=2, sp=4))
        q, k, v = _qkv(b=2, s=64, h=4, kvh=4, d=32)
        ref = reference_attention(q, k, v, causal=True)
        out = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh=mesh, causal=True)
        )(q, k, v)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_grad_flows(self):
        mesh = create_mesh(MeshConfig(dp=1, fsdp=1, tp=1, sp=8))
        q, k, v = _qkv(b=1, s=64, h=2, kvh=2, d=16)
        def f(q, k, v):
            return ring_attention(q, k, v, mesh=mesh, causal=True).sum()
        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(
            lambda *a: reference_attention(*a, causal=True).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(a, b, atol=1e-4)


class TestSlidingWindow:
    def test_window_matches_manual_mask(self):
        q, k, v = _qkv(s=32, h=4, kvh=4)
        W = 8
        out = reference_attention(q, k, v, causal=True, window=W)
        # manual: causal AND within-window softmax
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
        i = jnp.arange(32)
        m = (i[:, None] >= i[None, :]) & (i[:, None] - i[None, :] < W)
        logits = jnp.where(m[None, None], logits.astype(jnp.float32), -1e30)
        expect = jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1).astype(v.dtype), v)
        np.testing.assert_allclose(out, expect, atol=1e-5)

    def test_window_geq_seq_equals_full(self):
        q, k, v = _qkv(s=16)
        full = reference_attention(q, k, v, causal=True)
        win = reference_attention(q, k, v, causal=True, window=64)
        np.testing.assert_allclose(win, full, atol=1e-6)

    def test_ring_window_matches_reference(self):
        mesh = create_mesh(MeshConfig(dp=2, fsdp=1, tp=1, sp=4))
        q, k, v = _qkv(b=2, s=64, h=4, kvh=2, d=32)
        ref = reference_attention(q, k, v, causal=True, window=10)
        out = ring_attention(q, k, v, mesh=mesh, causal=True, window=10)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_flash_rejects_window(self):
        from ray_tpu.ops.attention import dot_product_attention

        q, k, v = _qkv(s=16)
        with pytest.raises(ValueError, match="flash"):
            dot_product_attention(q, k, v, impl="flash", window=4)


class TestDispatch:
    def test_auto_picks_ring_on_sp_mesh(self):
        mesh = create_mesh(MeshConfig(dp=1, fsdp=1, tp=1, sp=8))
        q, k, v = _qkv(b=1, s=64, h=2, kvh=2, d=16)
        out = dot_product_attention(q, k, v, mesh=mesh)
        ref = reference_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=1e-5)


class TestLayers:
    def test_rms_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 8))
        out = rms_norm(x, jnp.ones(8))
        rms = jnp.sqrt(jnp.mean(out**2, axis=-1))
        np.testing.assert_allclose(rms, jnp.ones(4), rtol=1e-3)

    def test_rope_preserves_norm_and_relative(self):
        cos, sin = rope_frequencies(16, 32)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 2, 16))
        out = apply_rope(x, cos, sin)
        np.testing.assert_allclose(
            jnp.linalg.norm(out, axis=-1), jnp.linalg.norm(x, axis=-1),
            rtol=1e-5,
        )
        # Position 0 is the identity rotation.
        np.testing.assert_allclose(out[:, 0], x[:, 0], atol=1e-6)

    def test_rope_positions_arg(self):
        cos, sin = rope_frequencies(8, 64)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 1, 8))
        pos = jnp.array([[5, 6, 7, 8]])
        shifted = apply_rope(x, cos, sin, positions=pos)
        full = apply_rope(
            jnp.pad(x, ((0, 0), (5, 0), (0, 0), (0, 0))), cos, sin
        )[:, 5:]
        np.testing.assert_allclose(shifted, full, atol=1e-5)

    def test_swiglu(self):
        g = jnp.array([0.0, 1.0, -1.0])
        u = jnp.array([2.0, 2.0, 2.0])
        out = swiglu(g, u)
        np.testing.assert_allclose(out[0], 0.0, atol=1e-6)
        assert out[1] > 0 and out[2] < 0


class TestFlashPadding:
    def test_non_divisible_seq(self):
        """Seq lengths not divisible by block size are padded and masked."""
        q, k, v = _qkv(s=95)
        ref = reference_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        np.testing.assert_allclose(out, ref, atol=2e-2)

    def test_odd_seq_4095_style(self):
        q, k, v = _qkv(b=1, s=63, h=2, kvh=1, d=32)
        ref = reference_attention(q, k, v, causal=False)
        out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
        np.testing.assert_allclose(out, ref, atol=2e-2)


class TestShardedFlash:
    def test_flash_under_mesh_shard_map(self):
        """impl='flash' with a mesh runs per-shard under shard_map."""
        mesh = create_mesh(MeshConfig(dp=4, fsdp=1, tp=2, sp=1))
        q, k, v = _qkv(b=4, s=64, h=4, kvh=2, d=32)
        ref = reference_attention(q, k, v, causal=True)
        out = jax.jit(
            lambda q, k, v: dot_product_attention(
                q, k, v, causal=True, impl="flash", mesh=mesh
            )
        )(q, k, v)
        np.testing.assert_allclose(out, ref, atol=2e-2)


class TestHybridMesh:
    def test_shape_and_axis_layout(self):
        from ray_tpu.parallel import create_hybrid_mesh

        mesh = create_hybrid_mesh(
            ici_config=MeshConfig(dp=1, fsdp=2, tp=2, sp=1), num_slices=2
        )
        assert dict(mesh.shape) == {
            "dp": 2, "fsdp": 2, "pp": 1, "tp": 2, "sp": 1
        }
