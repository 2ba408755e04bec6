"""Tests for the model zoo + sharded trainer on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# JAX-compile-heavy tier: deselect with -m 'not slow' for fast runs
pytestmark = pytest.mark.slow

from ray_tpu.models.llama import (
    LlamaConfig,
    llama_apply,
    llama_init,
    llama_loss,
    llama_param_specs,
)
from ray_tpu.models.training import make_llama_trainer
from ray_tpu.parallel import MeshConfig, create_mesh
from ray_tpu.parallel.sharding import logical_to_pspec, spec_tree_to_shardings


def _batch(b=8, s=33, vocab=256):
    return {
        "tokens": jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, vocab)
    }


class TestLlamaModel:
    def test_forward_shapes(self):
        cfg = LlamaConfig.tiny()
        params = llama_init(jax.random.PRNGKey(0), cfg)
        tokens = _batch()["tokens"]
        logits = llama_apply(params, tokens, cfg)
        assert logits.shape == (8, 33, cfg.vocab_size)
        assert logits.dtype == jnp.float32

    def test_param_count_matches_config(self):
        cfg = LlamaConfig.tiny()
        params = llama_init(jax.random.PRNGKey(0), cfg)
        n = sum(x.size for x in jax.tree.leaves(params))
        assert n == cfg.num_params()

    def test_spec_tree_structure_matches_params(self):
        cfg = LlamaConfig.tiny()
        params = llama_init(jax.random.PRNGKey(0), cfg)
        specs = llama_param_specs(cfg)
        assert jax.tree.structure(
            jax.tree.map(lambda _: 0, params)
        ) == jax.tree.structure(
            jax.tree.map(lambda _: 0, specs, is_leaf=lambda x: isinstance(x, tuple))
        )

    def test_scan_matches_unrolled(self):
        cfg_s = LlamaConfig.tiny(scan_layers=True)
        cfg_u = LlamaConfig.tiny(scan_layers=False)
        params_s = llama_init(jax.random.PRNGKey(0), cfg_s)
        # Unstack scanned layers into the unrolled layout.
        layers = [
            jax.tree.map(lambda x: x[i], params_s["layers"])
            for i in range(cfg_u.num_layers)
        ]
        params_u = dict(params_s, layers=layers)
        tokens = _batch()["tokens"]
        np.testing.assert_allclose(
            llama_apply(params_s, tokens, cfg_s),
            llama_apply(params_u, tokens, cfg_u),
            atol=1e-5,
        )

    def test_loss_decreases(self):
        from ray_tpu.models.training import default_optimizer

        cfg = LlamaConfig.tiny()
        mesh = create_mesh(MeshConfig(dp=-1))
        tr = make_llama_trainer(
            cfg, mesh, optimizer=default_optimizer(lr=1e-2, warmup=2)
        )
        state = tr.init_state(jax.random.PRNGKey(0))
        batch = tr.shard_batch(_batch())
        first = None
        for _ in range(20):
            state, m = tr.step(state, batch)
            if first is None:
                first = float(m["loss"])
        assert float(m["loss"]) < first - 0.5

    def test_causality(self):
        """Changing a future token must not change past logits."""
        cfg = LlamaConfig.tiny()
        params = llama_init(jax.random.PRNGKey(0), cfg)
        tokens = _batch(b=1)["tokens"]
        logits1 = llama_apply(params, tokens, cfg)
        tokens2 = tokens.at[0, -1].set((tokens[0, -1] + 1) % 256)
        logits2 = llama_apply(params, tokens2, cfg)
        np.testing.assert_allclose(
            logits1[:, :-1], logits2[:, :-1], atol=1e-5
        )

    def test_tied_embeddings(self):
        cfg = LlamaConfig.tiny(tie_embeddings=True)
        params = llama_init(jax.random.PRNGKey(0), cfg)
        assert "lm_head" not in params
        logits = llama_apply(params, _batch(b=2)["tokens"], cfg)
        assert logits.shape[-1] == cfg.vocab_size


class TestShardedTraining:
    @pytest.mark.parametrize(
        "mc",
        [
            MeshConfig(dp=8),
            MeshConfig(dp=2, fsdp=2, tp=2),
            MeshConfig(dp=1, fsdp=2, tp=2, sp=2),
        ],
        ids=["dp8", "dp2-fsdp2-tp2", "fsdp2-tp2-sp2"],
    )
    def test_train_step_parallelism_equivalence(self, mc):
        """All parallelism layouts compute the same loss trajectory."""
        cfg = LlamaConfig.tiny()
        mesh = create_mesh(mc)
        tr = make_llama_trainer(cfg, mesh)
        state = tr.init_state(jax.random.PRNGKey(0))
        batch = tr.shard_batch(_batch())
        for _ in range(2):
            state, m = tr.step(state, batch)
        # Golden value from the dp8 layout; all layouts must agree.
        assert m["loss"].shape == ()
        np.testing.assert_allclose(float(m["loss"]), 5.5432, atol=5e-3)

    def test_params_actually_sharded(self):
        cfg = LlamaConfig.tiny()
        mesh = create_mesh(MeshConfig(dp=1, fsdp=4, tp=2))
        tr = make_llama_trainer(cfg, mesh)
        state = tr.init_state(jax.random.PRNGKey(0))
        wq = state["params"]["layers"]["wq"]
        # wq [layers, embed, heads*hd]: embed sharded over fsdp(4), heads
        # over tp(2) → each shard holds 1/8 of the array.
        shard = wq.addressable_shards[0]
        assert shard.data.size == wq.size // 8

    def test_opt_state_sharded_like_params(self):
        cfg = LlamaConfig.tiny()
        mesh = create_mesh(MeshConfig(dp=1, fsdp=4, tp=2))
        tr = make_llama_trainer(cfg, mesh)
        state = tr.init_state(jax.random.PRNGKey(0))
        leaves = jax.tree.leaves(state["opt_state"])
        big = [x for x in leaves if hasattr(x, "sharding") and x.size > 1000]
        assert big, "expected adam moments in opt state"
        assert all(not x.sharding.is_fully_replicated for x in big)


class TestTrainerLevers:
    """The trainer's levers (accumulation, remat policies): correctness
    on CPU; what each is worth on the chip is not measured."""

    def test_grad_accumulation_matches_full_batch(self):
        """accum_steps=k over the SAME effective batch must produce the
        same loss and (numerically) the same update as one full step —
        grads are summed across microbatches and averaged."""
        import dataclasses

        cfg = LlamaConfig.tiny(num_layers=2)
        mesh = create_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
        tok = jax.random.randint(
            jax.random.PRNGKey(1), (8, 17), 0, cfg.vocab_size)
        from ray_tpu.models.training import default_optimizer

        losses = {}
        params = {}
        for accum in (1, 2, 4):
            tr = make_llama_trainer(
                cfg, mesh,
                optimizer=default_optimizer(warmup=1, decay_steps=10),
                accum_steps=accum)
            st = tr.init_state(jax.random.PRNGKey(0))
            st, m = tr.step(st, tr.shard_batch({"tokens": tok}))
            losses[accum] = float(m["loss"])
            params[accum] = jax.device_get(
                jax.tree.leaves(st["params"])[0])
        assert abs(losses[1] - losses[2]) < 1e-2, losses
        assert abs(losses[1] - losses[4]) < 1e-2, losses
        np.testing.assert_allclose(params[1], params[2], atol=1e-2)

    def test_save_attn_mlp_remat_matches(self):
        import dataclasses

        cfg = LlamaConfig.tiny(num_layers=2)
        cfg2 = dataclasses.replace(cfg, remat_policy="save_attn_mlp")
        mesh = create_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
        tok = jax.random.randint(
            jax.random.PRNGKey(1), (4, 17), 0, cfg.vocab_size)
        outs = []
        for c in (cfg, cfg2):
            tr = make_llama_trainer(c, mesh)
            st = tr.init_state(jax.random.PRNGKey(0))
            _, m = tr.step(st, tr.shard_batch({"tokens": tok}))
            outs.append(float(m["loss"]))
        assert abs(outs[0] - outs[1]) < 1e-4, outs


class TestShardingRules:
    def test_logical_to_pspec_dedup(self):
        # "batch"→(dp,fsdp) then "embed"→fsdp conflicts; embed replicated.
        spec = logical_to_pspec(("batch", "embed"))
        assert spec[0] == ("dp", "fsdp")
        assert len(spec) < 2 or spec[1] is None

    def test_mesh_filtering(self):
        """Axes absent from the mesh are dropped (e.g. a dp-only mesh)."""
        import jax as _jax
        from jax.sharding import Mesh
        import numpy as _np

        mesh = Mesh(_np.asarray(_jax.devices()), ("dp",))
        spec = logical_to_pspec(("batch", "mlp"), mesh=mesh)
        assert spec[0] == "dp"
        assert len(spec) == 1


class TestMoE:
    """Mixtral-style MoE: routing math + EP sharding (reference has no EP
    at all — SURVEY.md §2.4)."""

    def test_forward_shapes_and_aux(self):
        from ray_tpu.models.moe import MoEConfig, moe_apply, moe_init

        cfg = MoEConfig.tiny_moe()
        params = moe_init(jax.random.PRNGKey(0), cfg)
        tokens = _batch(vocab=cfg.vocab_size)["tokens"]
        logits, aux = moe_apply(params, tokens, cfg)
        assert logits.shape == (*tokens.shape, cfg.vocab_size)
        # balanced-routing lower bound: aux >= 1 (equality iff uniform)
        assert float(aux) >= 1.0 * cfg.num_layers * 0.99

    def test_param_count_matches_config(self):
        from ray_tpu.models.moe import MoEConfig, moe_init
        import numpy as np

        cfg = MoEConfig.tiny_moe()
        params = moe_init(jax.random.PRNGKey(0), cfg)
        n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        assert n == cfg.num_params()

    def test_top_k_routing_selects_k_experts(self):
        from ray_tpu.models.moe import MoEConfig, moe_block, moe_init

        cfg = MoEConfig.tiny_moe(num_layers=1)
        params = moe_init(jax.random.PRNGKey(0), cfg)
        lp = jax.tree.map(lambda a: a[0], params["layers"])
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 5, cfg.hidden_size))
        out, aux = moe_block(x.astype(cfg.dtype), lp, cfg)
        assert out.shape == x.shape
        assert jnp.isfinite(out).all()

    def test_moe_loss_decreases_and_ep_sharding(self):
        from ray_tpu.models.moe import (
            MoEConfig,
            make_moe_trainer,
            moe_param_specs,
        )
        from ray_tpu.models.training import default_optimizer

        mesh = create_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        cfg = MoEConfig.tiny_moe()
        tr = make_moe_trainer(
            cfg, mesh, optimizer=default_optimizer(lr=1e-2, warmup=1,
                                                   decay_steps=50))
        state = tr.init_state(jax.random.PRNGKey(0))
        # expert-stacked weights shard over the expert->tp rule
        wg = state["params"]["layers"]["w_gate"]
        spec = wg.sharding.spec
        assert "tp" in str(spec), f"experts not sharded: {spec}"
        batch = tr.shard_batch(_batch(b=8, s=17, vocab=cfg.vocab_size))
        losses = []
        for _ in range(8):
            state, m = tr.step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses


class TestViT:
    def test_forward_shapes_and_param_count(self):
        from ray_tpu.models.vit import ViTConfig, vit_apply, vit_init
        import numpy as np

        cfg = ViTConfig.tiny()
        params = vit_init(jax.random.PRNGKey(0), cfg)
        n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        assert n == cfg.num_params()
        images = jax.random.uniform(jax.random.PRNGKey(1), (4, 32, 32, 3))
        logits = vit_apply(params, images, cfg)
        assert logits.shape == (4, cfg.num_classes)

    def test_patchify_roundtrip(self):
        from ray_tpu.models.vit import ViTConfig, _patchify
        import numpy as np

        cfg = ViTConfig.tiny()
        img = jnp.arange(32 * 32 * 3, dtype=jnp.float32).reshape(1, 32, 32, 3)
        patches = _patchify(img, cfg)
        assert patches.shape == (1, cfg.num_patches, cfg.patch_dim)
        # first patch is the top-left 8x8 block
        np.testing.assert_array_equal(
            np.asarray(patches[0, 0]).reshape(8, 8, 3),
            np.asarray(img[0, :8, :8, :]))

    def test_vit_trains_sharded(self):
        from ray_tpu.models.vit import ViTConfig, make_vit_trainer
        from ray_tpu.models.training import default_optimizer

        mesh = create_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
        cfg = ViTConfig.tiny()
        tr = make_vit_trainer(cfg, mesh, optimizer=default_optimizer(
            lr=3e-3, warmup=1, decay_steps=50))
        state = tr.init_state(jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        images = jax.random.uniform(key, (8, 32, 32, 3))
        labels = jax.random.randint(key, (8,), 0, cfg.num_classes)
        batch = tr.shard_batch({"images": images, "labels": labels})
        losses = []
        for _ in range(8):
            state, m = tr.step(state, batch)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses


def test_remat_policies_same_loss():
    """All remat policies compute identical losses (they only trade
    recompute for memory)."""
    import jax

    from ray_tpu.models.llama import LlamaConfig, llama_init, llama_loss

    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 256)
    batch = {"tokens": tokens}
    losses = []
    for policy in ("full", "save_attn", "save_dots"):
        cfg = LlamaConfig.tiny(remat_policy=policy)
        params = llama_init(jax.random.PRNGKey(0), cfg)
        loss, grads = jax.value_and_grad(
            lambda p: llama_loss(p, batch, cfg))(params)
        losses.append(float(loss))
    assert losses[0] == pytest.approx(losses[1], rel=1e-6)
    assert losses[0] == pytest.approx(losses[2], rel=1e-6)

    with pytest.raises(ValueError, match="remat_policy"):
        cfg = LlamaConfig.tiny(remat_policy="bogus")
        llama_loss(llama_init(jax.random.PRNGKey(0), cfg),
                   batch, cfg)
