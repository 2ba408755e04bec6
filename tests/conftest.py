"""Test configuration.

JAX tests run on a virtual 8-device CPU mesh (the reference's trick of
emulating multi-node on one host, and the compiled-graph CPU-communicator
trick at ``python/ray/experimental/channel/cpu_communicator.py``): multi-chip
sharding logic is validated without TPU hardware.

Tiers (the intent of the reference's Bazel size/tag sharding,
``python/ray/tests/BUILD:16-72``): JAX-compile-heavy model/learning
modules carry ``pytest.mark.slow``; the core-runtime tier runs with
``-m "not slow"`` for fast iteration.  The default run executes
everything.
"""

import os
import sys

# Before jax is imported (it reads these then); worker subprocesses spawned
# by the cluster inherit them.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402,F401

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


# the shared session cluster's shape — every fixture that restores it
# after isolation must use the same parameters
SESSION_CLUSTER = {"num_cpus": 16, "num_tpus": 0}


@pytest.fixture(scope="session")
def ray_session():
    """One shared cluster for the whole test session (fast: workers reused)."""
    import ray_tpu

    ray_tpu.init(**SESSION_CLUSTER)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start(ray_session):
    """Alias onto the shared cluster; use ray_isolated for a fresh one."""
    yield


@pytest.fixture
def ray_isolated():
    """A fresh cluster, torn down after the test (for FT/failure tests).

    If the shared session cluster is up, it is stopped and restarted after,
    so isolated failure-injection cannot pollute other tests.
    """
    import ray_tpu

    was_up = ray_tpu.is_initialized()
    if was_up:
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_tpus=0)
    try:
        yield
    finally:
        ray_tpu.shutdown()
        if was_up:
            ray_tpu.init(**SESSION_CLUSTER)


@pytest.fixture
def no_cluster():
    """A clean slate for tests that drive ray_tpu.init() themselves (bare
    init while the session cluster is up raises 'called twice', and a
    shutdown inside such a test would strand every later ray_start test);
    restores the shared session cluster afterwards."""
    import ray_tpu

    was_up = ray_tpu.is_initialized()
    if was_up:
        ray_tpu.shutdown()
    yield
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    if was_up:
        ray_tpu.init(**SESSION_CLUSTER)


@pytest.fixture
def ray_start_2cpu():
    import ray_tpu

    ray_tpu.init(num_cpus=2, num_tpus=0)
    try:
        yield
    finally:
        ray_tpu.shutdown()
