"""Layout discipline: the sharded train step compiles with ZERO XLA
SPMD resharding warnings on every mesh the trainer path can form.

Three layers:

1. **Golden-sharding gate** (the satellite the multichip warning tails
   demanded): a subprocess with fd-captured stderr lowers + compiles
   the sharded Llama train step on the 8-device CPU mesh for every
   ``MESH_PRESETS`` entry AND the dryrun's multi-axis / hybrid meshes,
   asserting no "involuntary full rematerialization" / last-resort
   replicate line.  The same subprocess compiles the step once more on
   the hybrid mesh with a mis-pinned embedding gather of its own (no
   operand constraints) and must see warnings there — proof the capture
   isn't vacuously quiet.
2. **Warning-capture units** — marker counting and the fd-level
   capture actually seeing C-level fd-2 writes.
3. **Donation** — the train step really donates the state buffers
   (update-in-place in HBM), and ``donate_batch=True`` extends that to
   the input buffers; the default keeps reusable batches alive.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_GOLDEN_WORKER = r'''
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.models.training import default_optimizer, make_llama_trainer
from ray_tpu.parallel import (
    MESH_PRESETS,
    MeshConfig,
    create_hybrid_mesh,
    create_mesh,
    resolve_mesh_config,
)
from ray_tpu.parallel.xla_warnings import sharding_warning_capture


def compile_count(mesh):
    """Compile (AOT, no execution) init + train step; count warnings."""
    cfg = LlamaConfig.tiny(num_heads=4, num_kv_heads=4, num_layers=2)
    with sharding_warning_capture(replay=False) as w:
        tr = make_llama_trainer(
            cfg, mesh, optimizer=default_optimizer(warmup=1, decay_steps=10))
        state = tr.init_state(jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 9), 0, cfg.vocab_size)
        batch = tr.shard_batch({"tokens": tokens})
        tr.compile(state, batch)
    return w["count"], w["lines"]


meshes = {name: create_mesh(resolve_mesh_config(name).clamp_to(8))
          for name in sorted(MESH_PRESETS)}
# the two dryrun layouts whose gathers produced the historical warning
# tails: every axis at once, and the 2-slice hybrid
meshes["dp_fsdp_tp_sp"] = create_mesh(MeshConfig(dp=1, fsdp=2, tp=2, sp=2))
meshes["hybrid_2slice"] = create_hybrid_mesh(
    ici_config=MeshConfig(dp=1, fsdp=2, tp=2), num_slices=2)

out = {"presets": {}, "lines": {}}
for name, mesh in meshes.items():
    count, lines = compile_count(mesh)
    out["presets"][name] = count
    if lines:
        out["lines"][name] = lines[:2]

# control on the hybrid mesh: a gather whose operands are not pinned (the
# table's model-dim sharding flows into its output, then the batch
# constraint) reshards; the capture must SEE that, or the zeros above
# prove nothing
def unpinned_lookup(params, tokens, cfg, *, mesh, rules=None):
    x = params["embed"][tokens].astype(cfg.dtype)
    return llama._constrain(x, mesh, "batch", "seq", None, rules=rules)


llama._embed_lookup = unpinned_lookup
out["unpinned_hybrid"], _ = compile_count(meshes["hybrid_2slice"])

print("GOLDEN " + json.dumps(out))
'''


@pytest.fixture(scope="module")
def golden_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _GOLDEN_WORKER],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO, timeout=900)
    assert proc.returncode == 0, proc.stdout[-4000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("GOLDEN "))
    return json.loads(line[len("GOLDEN "):])


class TestGoldenShardingGate:
    def test_every_preset_compiles_clean(self, golden_result):
        dirty = {name: n for name, n in golden_result["presets"].items()
                 if n != 0}
        assert not dirty, (
            f"SPMD resharding warnings on meshes {dirty}; first lines: "
            f"{golden_result['lines']}")

    def test_gate_covers_every_preset_and_the_dryrun_meshes(
            self, golden_result):
        from ray_tpu.parallel import MESH_PRESETS

        covered = set(golden_result["presets"])
        assert covered >= set(MESH_PRESETS) | {"dp_fsdp_tp_sp",
                                               "hybrid_2slice"}

    def test_legacy_constraints_still_warn(self, golden_result):
        """The capture is not vacuous: a gather without the operand
        pins reshards on the hybrid mesh and the counter sees it."""
        assert golden_result["unpinned_hybrid"] >= 1


class TestWarningCaptureUnits:
    def test_marker_counting(self):
        from ray_tpu.parallel.xla_warnings import count_sharding_warnings

        text = (
            "2026-01-01: E spmd_partitioner.cc:613] [spmd] Involuntary "
            "full rematerialization. The compiler was not able ...\n"
            "some unrelated line\n"
            "... As the last resort, SPMD will replicate the tensor and "
            "then partition it to obtain the target sharding, which is "
            "inefficient ...\n")
        assert count_sharding_warnings(text) == 2
        assert count_sharding_warnings("all clean") == 0

    def test_fd_level_writes_are_captured_and_replayed(self, capfd):
        from ray_tpu.parallel.xla_warnings import capture_stderr_fd

        with capture_stderr_fd() as cap:
            os.write(2, b"raw fd2 write: Involuntary full "
                        b"rematerialization\n")
        assert "Involuntary full rematerialization" in cap["text"]
        # replayed: the bytes still reach the real stderr afterwards
        assert "raw fd2 write" in capfd.readouterr().err

    def test_capture_nests(self):
        from ray_tpu.parallel.xla_warnings import capture_stderr_fd

        with capture_stderr_fd(replay=False) as outer:
            os.write(2, b"outer-a\n")
            with capture_stderr_fd(replay=False) as inner:
                os.write(2, b"inner\n")
            os.write(2, b"outer-b\n")
        assert inner["text"] == "inner\n"
        assert "outer-a" in outer["text"] and "outer-b" in outer["text"]
        assert "inner" not in outer["text"]


class TestDonation:
    def _trainer(self, **kw):
        import jax

        from ray_tpu.models.llama import (
            LlamaConfig, llama_init, llama_loss, llama_param_specs,
        )
        from ray_tpu.models.training import ShardedTrainer, default_optimizer
        from ray_tpu.parallel import MeshConfig, create_mesh
        import functools

        mesh = create_mesh(MeshConfig(dp=1, fsdp=-1))
        cfg = LlamaConfig.tiny()
        tr = ShardedTrainer(
            functools.partial(llama_init, cfg=cfg),
            functools.partial(llama_loss, cfg=cfg, mesh=mesh),
            llama_param_specs(cfg),
            mesh=mesh,
            optimizer=default_optimizer(warmup=1, decay_steps=10),
            **kw)
        state = tr.init_state(jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 9), 0, cfg.vocab_size)
        batch = tr.shard_batch({"tokens": tokens})
        return tr, state, batch

    def test_step_donates_state_buffers(self):
        tr, state, batch = self._trainer()
        old_embed = state["params"]["embed"]
        new_state, _ = tr.step(state, batch)
        # the old tree's buffers were donated into the update — the
        # params copy can never serialize the step tail
        assert old_embed.is_deleted()
        assert not new_state["params"]["embed"].is_deleted()
        # the batch is NOT donated by default: benches and the H2D
        # stager legitimately feed the same buffers every step
        assert not batch["tokens"].is_deleted()
        tr.step(new_state, batch)  # reusable

    def test_donate_batch_opt_in(self):
        """The opt-in batch donation reaches XLA.  On the CPU test
        backend an int32 tokens buffer can alias no output, so the
        donation surfaces as jax's "not usable" warning — which is
        exactly the proof the donate_argnums plumbing carried it (the
        default trainer's step raises no such warning; see
        test_step_donates_state_buffers)."""
        import warnings

        tr, state, batch = self._trainer(donate_batch=True)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            tr.step(state, batch)
        assert any("donated buffers were not usable" in str(x.message)
                   for x in w), [str(x.message) for x in w]
