"""Compile a cell's programs at their real size for a v5e that is
described, not attached (on-chip-measurement guide, section 2.3), and print
``memory_analysis()``.  Costs no chip time; run it before a chip call:

    JAX_PLATFORMS=cpu python cells/tools/compile_for_v5e.py train-1chip-s4096
    JAX_PLATFORMS=cpu python cells/tools/compile_for_v5e.py serve-chat-steady

A compile that passes is not a chip run and gives no time.
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from cells.train_worker import _model_config  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
# the program asks jax.default_backend() whether to run its Pallas kernel
# in interpret mode; here the backend is the CPU and the target is not
jax.default_backend = lambda: "tpu"


def gb(x):
    return round(x / 1e9, 3)


def report(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: arguments {gb(m.argument_size_in_bytes)} GB, outputs "
          f"{gb(m.output_size_in_bytes)} GB, aliased "
          f"{gb(m.alias_size_in_bytes)} GB, temporaries "
          f"{gb(m.temp_size_in_bytes)} GB -> {gb(total)} GB a device; "
          f"Mosaic calls {compiled.as_text().count('tpu_custom_call')}",
          flush=True)


def train(cell, config, traffic, topo):
    from ray_tpu.models.training import default_optimizer, make_llama_trainer
    from ray_tpu.parallel.mesh import MESH_AXES, resolve_mesh_config

    cfg = _model_config(config["model"])
    n = cell["chips"]
    shape = resolve_mesh_config(config["scaling"]["mesh"]).resolve(n)
    devs = np.array(topo.devices[:n]).reshape(shape)
    mesh = Mesh(devs, MESH_AXES)
    tr = make_llama_trainer(cfg, mesh, optimizer=default_optimizer(
        **traffic["optimizer"]))
    state = jax.eval_shape(tr._state_init, jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, tr.state_shardings)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (traffic["batch"], traffic["seq"] + 1), jnp.int32,
        sharding=tr.batch_sharding)}
    with mesh:
        report(f"train step {cell['name']}",
               tr._jit_step.lower(state, batch).compile())


def serve(cell, config, traffic, topo):
    from ray_tpu.models.llama import llama_init
    from ray_tpu.models.paged_generation import (init_kv_pool,
                                                 paged_decode_sample,
                                                 prefill_suffix)

    cfg = _model_config(config["model"])
    e = config["engine"]
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)
    B, bs = e["batch_slots"], e["block_size"]
    MB = -(-e["max_len"] // bs)
    params = on(jax.eval_shape(
        functools.partial(llama_init, cfg=cfg), jax.random.PRNGKey(0)))
    pool = on(jax.eval_shape(
        lambda: init_kv_pool(cfg, e.get("num_blocks") or B * MB + 1, bs)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa
    key = on(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    report("decode step", jax.jit(
        functools.partial(paged_decode_sample, cfg=cfg),
        donate_argnums=(4,)).lower(
            params, i32(B), i32(B), i32(B, MB), pool, key,
            jax.ShapeDtypeStruct((B,), jnp.float32, sharding=one)
        ).compile())
    S = max(traffic["warmup"]["prompt_lengths"])
    hd = cfg.resolved_head_dim
    empty = jax.ShapeDtypeStruct(
        (cfg.num_layers, 0, cfg.num_kv_heads, hd), cfg.dtype, sharding=one)
    report(f"prefill of {S} tokens", jax.jit(
        functools.partial(prefill_suffix, cfg=cfg),
        donate_argnums=(9,)).lower(
            params, i32(1, S), i32(), i32(), empty, empty, i32(),
            i32(S), i32(S), pool).compile())


def main():
    name = sys.argv[1]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    load = lambda kind, n: json.load(open(os.path.join(  # noqa: E731
        ROOT, "cells", kind, n + ".json")))
    config = load("configs", cell["config"])
    traffic = load("traffic", cell["traffic"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    {"train": train, "serve": serve}[traffic["runner"]](
        cell, config, traffic, topo)


if __name__ == "__main__":
    main()
