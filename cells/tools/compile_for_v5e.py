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

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from cells import families, train_worker  # noqa: E402

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
    from ray_tpu.parallel.mesh import MESH_AXES, resolve_mesh_config

    fam = families.load(config["family"])
    cfg = fam.config(config["model"])
    n = cell["chips"]
    shape = resolve_mesh_config(config["scaling"]["mesh"]).resolve(n)
    devs = np.array(topo.devices[:n]).reshape(shape)
    mesh = Mesh(devs, MESH_AXES)
    tr = fam.make_trainer(cfg, mesh, traffic["optimizer"])
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
        # the set-up's comparison shares the chips with the state
        group = jax.ShapeDtypeStruct(
            (min(traffic["batch"], n), traffic["seq"] + 1), jnp.int32,
            sharding=tr.batch_sharding)
        logits = jax.ShapeDtypeStruct(
            (group.shape[0], traffic["seq"], cfg.vocab_size), jnp.float32,
            sharding=tr.batch_sharding)
        programs = train_worker.reference_programs(
            fam, cfg, config["model"], mesh)
        one = jax.ShapeDtypeStruct(group.shape[1:], jnp.int32,
                                   sharding=NamedSharding(mesh, P()))
        for name, args in (("compare", (logits, group)), ("loss", (group,)),
                           ("gradient", (one,))):
            report(f"reference {name}", programs[name].lower(
                state["params"], *args).compile())


def serve(cell, config, traffic, topo):
    fam = families.load(config["family"])
    one = SingleDeviceSharding(topo.devices[0])
    programs = fam.serve_programs(
        fam.config(config["model"]), config["engine"],
        max(traffic["warmup"]["prompt_lengths"]))
    for name, fn, donated, args in programs:
        args = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), args)
        report(name, jax.jit(fn, donate_argnums=donated).lower(
            *args).compile())


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
