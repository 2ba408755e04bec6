"""Bring-up contracts a CPU can hold the code to: the chip smoke's two
modes, where the compile cache lives, and one process per chip on a node
whose TPUs are only a number."""

import json
import os
import re
import subprocess
import sys

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, timeout):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_rehearsal_runs_both_phases():
    out = _smoke("--rehearse", timeout=300)
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-2000:])
    assert "FAIL" not in out.stdout
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {
        "ok": True, "rehearsal": True}
    assert out.stdout.startswith("REHEARSAL")


def test_chip_smoke_without_a_tpu_fails_and_prints_no_result():
    out = _smoke(timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "phase 2" not in out.stdout  # nothing trained, nothing served
    assert "detected 0 TPU chip(s)" in out.stderr


# -- compile cache -----------------------------------------------------------

def test_compile_cache_path_from_outside_wins(monkeypatch):
    from ray_tpu._private.node import ensure_compile_cache_env

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert ensure_compile_cache_env() == "/somewhere/else"
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/else"


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    from ray_tpu._private.node import ensure_compile_cache_env

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert ensure_compile_cache_env() == os.path.join(REPO, ".jax_cache")
    assert ensure_compile_cache_env() == os.path.join(REPO, ".jax_cache")


def test_the_cache_key_takes_the_programs_metadata_in(monkeypatch):
    """A program's name scopes (``tracing.scope``) are metadata: under
    JAX's default key a warm cache hands back an executable with whatever
    scopes its compiler had (PERF.md section 6, PR 37).  Two programs that
    differ in a scope alone get two keys once the variable is set, one
    without it."""
    import subprocess
    import sys

    from ray_tpu._private.node import ensure_compile_cache_env

    var = "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"
    monkeypatch.delenv(var, raising=False)
    ensure_compile_cache_env()
    assert os.environ[var] == "true"
    monkeypatch.setenv(var, "false")  # a choice from outside wins
    ensure_compile_cache_env()
    assert os.environ[var] == "false"
    probe = (
        "import jax, jax.numpy as jnp\n"
        "from jax._src import cache_key\n"
        "from ray_tpu._private import tracing\n"
        "def f(x):\n"
        "    return x * 2\n"
        "def g(x):\n"
        "    with tracing.scope('ffn'):\n"
        "        return x * 2\n"
        "import numpy as np\n"
        "x, dev, keys = jnp.ones(4), jax.devices(), []\n"
        "for fn in (f, g):\n"
        "    fn.__name__ = fn.__qualname__ = 'same'\n"
        "    low = jax.jit(fn).lower(x)\n"
        "    keys.append(cache_key.get(low.compiler_ir(), np.array(dev[:1]),"
        " jax._src.compiler.get_compile_options(1, 1), dev[0].client))\n"
        "print(len(set(keys)))\n")
    for value, want in (("true", "2"), ("false", "1")):
        out = subprocess.run(
            [sys.executable, "-c", probe], capture_output=True, text=True,
            env={**os.environ, var: value, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": REPO})
        assert out.stdout.strip() == want, (value, out.stdout, out.stderr)


def test_spawned_worker_compiles_into_the_drivers_cache(ray_start):
    @ray_tpu.remote
    def where():
        import jax

        return (os.environ.get("JAX_COMPILATION_CACHE_DIR"),
                jax.config.jax_compilation_cache_dir)

    # init() exported it (or kept the outside value) before the head
    # started; the zygote imported jax under it, the worker forked from
    # the zygote
    want = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert ray_tpu.get(where.remote(), timeout=60) == (want, want)


def test_one_place_decides_the_compile_cache():
    hits = []
    paths = [os.path.join(REPO, "chip_smoke.py")]
    for root, _, files in os.walk(os.path.join(REPO, "ray_tpu")):
        paths += [os.path.join(root, f) for f in files if f.endswith(".py")]
    for path in paths:
        with open(path) as f:
            if re.search("compilation_cache", f.read(), re.I):
                hits.append(os.path.relpath(path, REPO))
    assert hits == ["ray_tpu/_private/node.py"]


# -- one process per chip ----------------------------------------------------

def test_a_live_backend_cannot_be_bound(monkeypatch):
    import jax

    from ray_tpu._private import accelerators

    jax.devices()  # the cpu backend of this test process is now live
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    assert accelerators.bind_tpu_chips([0], node_chips=2) is False
    assert "TPU_VISIBLE_CHIPS" not in os.environ
    assert jax.config.jax_platforms == "cpu"


def test_visible_chips_form_their_own_topology():
    from ray_tpu._private.accelerators import TPUAcceleratorManager

    env = {}
    TPUAcceleratorManager.set_visible_chips(env, [1])
    assert env["TPU_VISIBLE_CHIPS"] == "1"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    TPUAcceleratorManager.set_visible_chips(env, [2, 3])
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"


def test_tpu_leases_bind_workers_to_disjoint_chips(no_cluster):
    """A node with two TPUs that exist only as a number: the binding is
    all there is to see, and a backend that cannot be a TPU must raise."""
    ray_tpu.init(num_cpus=4, num_tpus=2)

    @ray_tpu.remote(num_tpus=1)
    def held(seconds):
        import time

        import jax

        time.sleep(seconds)
        return (os.getpid(), os.environ["TPU_VISIBLE_CHIPS"],
                jax.config.jax_platforms)

    @ray_tpu.remote
    def cpu_backend():
        import jax

        return os.getpid(), jax.devices()[0].platform

    @ray_tpu.remote(num_tpus=1)
    def touch():
        import jax

        return jax.devices()[0].platform

    # a zero-TPU worker gets the CPU, and its process now holds a backend
    cpu_pid, platform = ray_tpu.get(cpu_backend.remote(), timeout=60)
    assert platform == "cpu"

    # two leases at once: disjoint chips, pinned to tpu, never the worker
    # whose backend is already up
    a, b = ray_tpu.get([held.remote(1.0), held.remote(1.0)], timeout=60)
    assert {a[1], b[1]} == {"0", "1"}
    assert a[2] == b[2] == "tpu"
    assert cpu_pid not in (a[0], b[0])

    # released leases free their chips — once their workers are gone,
    # so the next holder is a new process
    c = ray_tpu.get(held.remote(0), timeout=60)
    assert c[1] in ("0", "1") and c[0] not in (a[0], b[0])

    # holds a TPU lease, cannot get a TPU: an error, never the CPU
    with pytest.raises(Exception, match="Unable to initialize backend 'tpu'"):
        ray_tpu.get(touch.remote(), timeout=120)
