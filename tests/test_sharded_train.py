"""Sharded (GSPMD) train-path tests — tier-1, CPU mesh, no hardware.

Covers the mesh-in-the-trainer-path feature set:
- ``MeshConfig.resolve`` axis-named errors + ``clamp_to`` degradation
  (unit-tested on 1/2/4/8 devices);
- every ``ScalingConfig`` mesh preset resolves on {1, 2, 4, 8} devices
  (tooling guard);
- every logical axis name used by ``models/`` spec trees has an explicit
  entry in ``DEFAULT_RULES`` (silent replication of a shardable axis
  fails the guard);
- worker-side session API: ``train.get_mesh()`` / ``shard_params()`` /
  ``shard_inputs()``;
- the mesh request threads trainer → controller → worker group →
  session;
- the CPU-mesh MULTI-PROCESS smoke: 2 processes × 2 ``JAX_PLATFORMS=cpu``
  devices join one ``jax.distributed`` mesh through ``JaxTrainer`` end
  to end, and the sharded train-step update matches the single-process
  full-batch update.
"""

import math

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.parallel import (
    MESH_PRESETS,
    MeshConfig,
    create_mesh,
    resolve_mesh_config,
)


# ---------------------------------------------------------------------------
# MeshConfig.resolve / clamp_to units
# ---------------------------------------------------------------------------


class TestMeshConfigResolve:
    def test_error_names_offending_infer_axis(self):
        with pytest.raises(ValueError, match=r"cannot infer mesh axis 'dp'"):
            MeshConfig(dp=-1, tp=3).resolve(8)

    def test_error_names_axis_sizes_on_mismatch(self):
        with pytest.raises(ValueError, match=r"dp=2.*tp=4"):
            MeshConfig(dp=2, tp=4).resolve(4)

    def test_error_names_invalid_axis(self):
        with pytest.raises(ValueError, match=r"mesh axis 'fsdp'=0"):
            MeshConfig(dp=1, fsdp=0).resolve(4)

    def test_error_names_double_infer(self):
        with pytest.raises(ValueError, match=r"dp=-1, tp=-1"):
            MeshConfig(dp=-1, tp=-1).resolve(8)

    @pytest.mark.parametrize("n", [1, 2, 4, 8])
    def test_clamp_to_always_resolves(self, n):
        requests = [
            MeshConfig(dp=-1),
            MeshConfig(dp=1, fsdp=-1),
            MeshConfig(fsdp=4, tp=2),
            MeshConfig(dp=2, fsdp=2, tp=2),
            MeshConfig(dp=1, fsdp=2, pp=2, tp=2, sp=2),
            MeshConfig(dp=-1, tp=16),
        ]
        for req in requests:
            shape = req.clamp_to(n).resolve(n)
            assert math.prod(shape) == n, (req, n, shape)

    def test_clamp_prefers_model_axes(self):
        # tp survives the shrink; fsdp absorbs it
        c = MeshConfig(fsdp=4, tp=2).clamp_to(4)
        assert (c.fsdp, c.tp) == (2, 2)
        c = MeshConfig(fsdp=4, tp=2).clamp_to(2)
        assert (c.fsdp, c.tp) == (1, 2)
        c = MeshConfig(fsdp=4, tp=2).clamp_to(1)
        assert (c.fsdp, c.tp) == (1, 1)

    def test_clamp_folds_leftover_into_dp(self):
        # all axes fixed and product < n: dp absorbs so every device is used
        c = MeshConfig(dp=2, tp=2).clamp_to(8)
        assert (c.dp, c.tp) == (4, 2)

    def test_clamp_rejects_zero_devices(self):
        with pytest.raises(ValueError):
            MeshConfig().clamp_to(0)


class TestMeshPresets:
    """CI guard: every named preset must form a valid mesh on any of the
    device counts elastic training can land on."""

    @pytest.mark.parametrize("name", sorted(MESH_PRESETS))
    @pytest.mark.parametrize("n", [1, 2, 4, 8])
    def test_preset_resolves(self, name, n):
        shape = MESH_PRESETS[name].clamp_to(n).resolve(n)
        assert math.prod(shape) == n, (name, n, shape)

    def test_resolve_mesh_config(self):
        assert resolve_mesh_config(None) is None
        assert resolve_mesh_config("fsdp") == MESH_PRESETS["fsdp"]
        mc = MeshConfig(tp=2)
        assert resolve_mesh_config(mc) is mc
        with pytest.raises(ValueError, match="unknown mesh preset"):
            resolve_mesh_config("fdsp")  # typo'd preset names the options
        with pytest.raises(TypeError):
            resolve_mesh_config(4)

    def test_unknown_preset_fails_at_trainer_construction(self):
        with pytest.raises(ValueError, match="unknown mesh preset"):
            train.DataParallelTrainer(
                lambda: None,
                scaling_config=train.ScalingConfig(mesh="no-such-preset"))


# ---------------------------------------------------------------------------
# Logical-axis rule-table guard
# ---------------------------------------------------------------------------


def _collect_axis_names(spec_tree, out):
    import jax

    def visit(leaf):
        out.update(a for a in leaf if a is not None)

    jax.tree.map(
        visit, spec_tree,
        is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(e, (str, type(None))) for e in x))


class TestLogicalAxisRulesGuard:
    """Every logical axis a models/ pytree annotates must have an entry
    in DEFAULT_RULES — an explicit None records a deliberate replication
    decision; a MISSING name is silent replication of a possibly
    shardable axis and fails here."""

    def test_every_model_axis_has_a_rule(self):
        from ray_tpu.models.llama import LlamaConfig, llama_param_specs
        from ray_tpu.models.moe import MoEConfig, moe_param_specs
        from ray_tpu.models.vit import ViTConfig, vit_param_specs
        from ray_tpu.parallel.sharding import DEFAULT_RULES

        used = set()
        _collect_axis_names(llama_param_specs(LlamaConfig.tiny()), used)
        _collect_axis_names(
            llama_param_specs(LlamaConfig.tiny(scan_layers=False)), used)
        _collect_axis_names(moe_param_specs(MoEConfig.tiny_moe()), used)
        _collect_axis_names(vit_param_specs(ViTConfig.tiny()), used)
        missing = sorted(used - set(DEFAULT_RULES))
        assert not missing, (
            f"logical axes {missing} are used by models/ param spec trees "
            "but have no DEFAULT_RULES entry — add one (map to a mesh axis, "
            "or to None to record a deliberate replication decision)")

    def test_batch_and_seq_rules_exist(self):
        # activation-constraint axes the model bodies use
        from ray_tpu.parallel.sharding import DEFAULT_RULES

        assert "batch" in DEFAULT_RULES
        assert "seq" in DEFAULT_RULES


# ---------------------------------------------------------------------------
# Worker-side session API (single process; 8 virtual CPU devices)
# ---------------------------------------------------------------------------


@pytest.fixture
def local_session():
    """An in-process train session (the exact state TrainWorker.start_loop
    builds), torn down after the test."""
    from ray_tpu.train import session as session_mod

    created = []

    def start(mesh=None, rules=None):
        from ray_tpu.parallel.mesh import resolve_mesh_config as rmc

        s = session_mod._start_session(
            rank=0, world_size=1, group_name="local-test", config={},
            checkpoint=None, mesh_config=rmc(mesh), axis_rules=rules)
        created.append(s)
        return s

    yield start
    with session_mod._session_lock:
        session_mod._session = None


class TestSessionMeshAPI:
    def test_get_mesh_resolves_preset_over_all_devices(self, local_session):
        import jax

        local_session(mesh="fsdp_tp")
        mesh = train.get_mesh()
        n = len(jax.devices())
        assert mesh.size == n
        assert mesh.shape["tp"] == (2 if n % 2 == 0 else 1)
        assert mesh.shape["fsdp"] == n // mesh.shape["tp"]
        # cached: same object on every call
        assert train.get_context().get_mesh() is mesh

    def test_get_mesh_clamps_oversized_request(self, local_session):
        import jax

        # requested mesh needs 64 devices; must clamp, not raise
        local_session(mesh=MeshConfig(dp=1, fsdp=32, tp=2))
        mesh = train.get_mesh()
        assert mesh.size == len(jax.devices())

    def test_get_mesh_default_is_pure_dp(self, local_session):
        import jax

        local_session()
        mesh = train.get_mesh()
        assert mesh.shape["dp"] == len(jax.devices())

    def test_shard_params_places_leaves_per_rules(self, local_session):
        import jax

        from ray_tpu.models.llama import (
            LlamaConfig, llama_init, llama_param_specs,
        )

        local_session(mesh="fsdp")
        cfg = LlamaConfig.tiny()
        host = llama_init(jax.random.PRNGKey(0), cfg)
        sharded = train.shard_params(host, llama_param_specs(cfg))
        mesh = train.get_mesh()
        n_fsdp = mesh.shape["fsdp"]
        # embed ("vocab", "embed"): embed dim sharded over fsdp (vocab
        # maps to tp, size 1 on this preset)
        emb = sharded["embed"]
        assert emb.sharding.spec[1] == "fsdp", emb.sharding.spec
        assert emb.addressable_shards[0].data.shape == (
            cfg.vocab_size, cfg.hidden_size // n_fsdp)
        # values survive the placement
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(emb)), np.asarray(host["embed"]))
        # norms are explicitly replicated
        assert sharded["final_norm"].sharding.spec == \
            jax.sharding.PartitionSpec()

    def test_shard_inputs_shards_batch_axis(self, local_session):
        import jax

        local_session(mesh="fsdp")
        batch = {"tokens": np.arange(8 * 4, dtype=np.int32).reshape(8, 4)}
        out = train.shard_inputs(batch)
        mesh = train.get_mesh()
        spec = out["tokens"].sharding.spec
        assert spec and "fsdp" in (
            spec[0] if isinstance(spec[0], tuple) else (spec[0],))
        assert out["tokens"].shape == (8, 4)
        per = out["tokens"].addressable_shards[0].data.shape[0]
        assert per == 8 // mesh.shape["fsdp"]

    def test_rules_override_travels_through_session(self, local_session):
        import jax

        # override: batch replicated (e.g. for eval loops)
        local_session(mesh="fsdp", rules={"batch": None})
        out = train.shard_inputs({"x": np.ones((4, 2), np.float32)})
        assert out["x"].sharding.spec == jax.sharding.PartitionSpec()


# ---------------------------------------------------------------------------
# Mesh request threads trainer -> controller -> worker group -> session
# ---------------------------------------------------------------------------


@pytest.mark.usefixtures("ray_start")
class TestMeshThreading:
    def test_scaling_config_mesh_reaches_worker_session(self):
        def loop():
            ctx = train.get_context()
            mesh = ctx.get_mesh()
            train.report({
                "shape": {a: int(s) for a, s in mesh.shape.items()},
                "size": int(mesh.size),
            })

        result = train.DataParallelTrainer(
            loop,
            scaling_config=train.ScalingConfig(
                num_workers=1, mesh="fsdp_tp"),
        ).fit()
        assert result.error is None, result.error
        shape = result.metrics["shape"]
        assert shape["tp"] == 2
        assert shape["fsdp"] * shape["tp"] == result.metrics["size"]
        assert result.metrics["size"] > 1  # all virtual devices meshed

    def test_trainer_path_sharded_step_runs(self):
        """The bench's multichip loop shape, through a real worker: mesh
        preset -> sharded tiny-Llama step -> loss reported."""

        def loop():
            import jax

            from ray_tpu.models.llama import LlamaConfig
            from ray_tpu.models.training import (
                default_optimizer, make_llama_trainer,
            )

            ctx = train.get_context()
            mesh = ctx.get_mesh()
            cfg = LlamaConfig.tiny()
            tr = make_llama_trainer(
                cfg, mesh,
                optimizer=default_optimizer(warmup=1, decay_steps=10))
            state = tr.init_state(jax.random.PRNGKey(0))
            tokens = jax.random.randint(
                jax.random.PRNGKey(1), (8, 9), 0, cfg.vocab_size)
            b = tr.shard_batch({"tokens": tokens})
            state, m = tr.step(state, b)
            train.report({"loss": float(m["loss"]),
                          "step": int(state["step"]),
                          "mesh_size": int(mesh.size)})

        result = train.DataParallelTrainer(
            loop,
            scaling_config=train.ScalingConfig(num_workers=1, mesh="fsdp"),
        ).fit()
        assert result.error is None, result.error
        assert result.metrics["loss"] > 0
        assert result.metrics["step"] == 1
        assert result.metrics["mesh_size"] > 1


# ---------------------------------------------------------------------------
# CPU-mesh multi-process smoke (the tier-1 acceptance test)
# ---------------------------------------------------------------------------


@pytest.mark.usefixtures("ray_start")
class TestCpuMeshMultiProcessSmoke:
    """2 worker processes × 2 cpu devices each join ONE jax.distributed
    mesh through JaxTrainer; the sharded train-step update over the
    4-way mesh must match the single-process full-batch update."""

    def test_sharded_update_matches_single_process(self):
        import jax

        from ray_tpu.models.llama import LlamaConfig
        from ray_tpu.models.training import (
            default_optimizer, make_llama_trainer,
        )

        rng = np.random.default_rng(0)
        global_tokens = rng.integers(
            0, 256, (8, 9), dtype=np.int64).astype(np.int32)

        # --- reference: single-process, single-device, FULL batch
        cfg = LlamaConfig.tiny()
        ref_mesh = create_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
        ref_tr = make_llama_trainer(
            cfg, ref_mesh,
            optimizer=default_optimizer(lr=1e-2, warmup=1, decay_steps=10))
        ref_state = ref_tr.init_state(jax.random.PRNGKey(0))
        ref_state, ref_m = ref_tr.step(
            ref_state, ref_tr.shard_batch({"tokens": global_tokens}))
        ref_loss = float(ref_m["loss"])
        ref_csum = float(sum(
            np.sum(np.asarray(jax.device_get(x), dtype=np.float64))
            for x in jax.tree.leaves(ref_state["params"])))

        # --- distributed: 2 processes x 2 devices, fsdp mesh
        def loop(config):
            import jax
            import numpy as np

            from ray_tpu import train
            from ray_tpu.models.llama import LlamaConfig
            from ray_tpu.models.training import (
                default_optimizer, make_llama_trainer,
            )

            ctx = train.get_context()
            mesh = ctx.get_mesh()  # joins jax.distributed itself
            world = ctx.get_world_size()
            rank = ctx.get_world_rank()
            assert jax.process_count() == world, jax.process_count()
            nloc = len(jax.local_devices())
            assert nloc == 2, f"worker should see 2 cpu devices, got {nloc}"
            assert mesh.size == world * nloc

            cfg = LlamaConfig.tiny()
            tr = make_llama_trainer(
                cfg, mesh, optimizer=default_optimizer(
                    lr=1e-2, warmup=1, decay_steps=10))
            state = tr.init_state(jax.random.PRNGKey(0))
            full = np.asarray(config["tokens"], dtype=np.int32)
            rows = full.shape[0] // world
            local = full[rank * rows:(rank + 1) * rows]
            b = tr.shard_batch({"tokens": local})  # multiprocess-aware
            state, m = tr.step(state, b)

            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            csum_fn = jax.jit(
                lambda p: sum(jnp.sum(x.astype(jnp.float64))
                              for x in jax.tree.leaves(p)),
                out_shardings=NamedSharding(mesh, P()))
            csum = float(np.asarray(jax.device_get(
                csum_fn(state["params"]).addressable_data(0))))
            loss = float(np.asarray(jax.device_get(
                m["loss"].addressable_data(0))))
            train.report({
                "loss": loss, "csum": csum,
                "procs": jax.process_count(), "nloc": nloc,
                "mesh_shape": {a: int(s) for a, s in mesh.shape.items()
                               if int(s) > 1},
            })

        class TwoDeviceJaxTrainer(train.JaxTrainer):
            # each worker gets its OWN 2-device cpu platform (the env
            # applies before the worker's first jax backend touch)
            def _dist_env_fn(self, group):
                env = super()._dist_env_fn(group)
                for e in env or []:
                    e["JAX_PLATFORMS"] = "cpu"
                    e["XLA_FLAGS"] = \
                        "--xla_force_host_platform_device_count=2"
                return env

        result = TwoDeviceJaxTrainer(
            loop,
            train_loop_config={"tokens": global_tokens},
            scaling_config=train.ScalingConfig(
                num_workers=2, mesh="fsdp"),
        ).fit()
        assert result.error is None, result.error
        m = result.metrics
        assert m["procs"] == 2
        assert m["nloc"] == 2
        assert m["mesh_shape"] == {"fsdp": 4}
        # the 4-way-sharded update equals the single-process full-batch
        # update (both f32; tolerance covers reduction-order drift)
        assert np.isclose(m["loss"], ref_loss, rtol=1e-4, atol=1e-5), \
            (m["loss"], ref_loss)
        assert np.isclose(m["csum"], ref_csum, rtol=1e-4, atol=1e-2), \
            (m["csum"], ref_csum)
