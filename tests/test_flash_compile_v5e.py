"""The flash kernels, and the decode-shaped expert kernel (PR 32), compiled
for a v5e that is described, not attached.

The interpreter cannot see what the chip's compiler refuses: more VMEM
than a kernel may use (the backward keeps dK/dV whole in scratch and sets
its own ``vmem_limit_bytes``), a slice off the (8, 128) tiling.  libtpu is
installed in the sandbox and compiles for a topology by name, in about two
seconds a shape and at no chip time.  Nothing runs: no result, no time.

Only the worker that is given this file may load libtpu, and only once a
test has started: the topology is described in a fixture, never at import.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as env:
        env.setenv("TPU_LOG_DIR", "disabled")
        env.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        env.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_was)


def _compile_grad(one_chip, b, sq, sk, h, kvh, d, causal):
    def shape(s, heads):
        return jax.ShapeDtypeStruct((b, s, heads, d), jnp.bfloat16,
                                    sharding=one_chip)

    def loss(q, k, v):
        # interpret=False: the backend here is the CPU, the target is not
        out = fa._flash(q, k, v, causal, 1024, 1024, False)
        return out.astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(sq, h), shape(sk, kvh), shape(sk, kvh)).compile()


@pytest.mark.parametrize("b,sq,sk,h,kvh,d,causal", [
    pytest.param(4, 4096, 4096, 32, 8, 128, True, id="train-1chip-s4096"),
    pytest.param(1, 32768, 32768, 32, 8, 128, True, id="published-length"),
    pytest.param(1, 32768, 32768, 8, 8, 128, True, id="published-length-mha"),
    pytest.param(2, 1000, 1000, 12, 12, 128, True, id="padded"),
    pytest.param(2, 2048, 4096, 8, 2, 128, False, id="full-unequal"),
])
def test_forward_and_backward_compile_as_two_kernels(
        one_chip, b, sq, sk, h, kvh, d, causal):
    text = _compile_grad(one_chip, b, sq, sk, h, kvh, d, causal).as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_a_sequence_whose_scratch_outgrows_vmem_is_refused_at_lowering(
        one_chip):
    """dK/dV whole in float32 at S = 65 536 are 64 MiB beside as much in
    output blocks: past the v5e's 128 MiB, and the compiler says so."""
    with pytest.raises(Exception, match="(?i)vmem"):
        _compile_grad(one_chip, 1, 65536, 65536, 8, 8, 128, True)


@pytest.mark.parametrize("sq,h,kvh,window", [
    pytest.param(14352, 28, 4, 4096, id="smallthinker-prefill-window"),
    pytest.param(14352, 28, 4, None, id="smallthinker-prefill-full"),
    pytest.param(8192, 32, 8, 1000, id="window-off-the-block-grid"),
])
def test_the_windowed_forward_compiles_as_one_kernel(one_chip, sq, h, kvh,
                                                     window):
    """The forward alone, as a prefill runs it (PR 31): the index map that
    holds the first K block a window reaches, and the edge's mask."""
    def shape(heads):
        return jax.ShapeDtypeStruct((1, sq, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    text = jax.jit(lambda q, k, v: fa._flash(
        q, k, v, True, 1024, 1024, False, window)).lower(
            shape(h), shape(kvh), shape(kvh)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


# ------------------------- the expert layer at decode shape (PR 32)

_EXPERT_CELLS = {
    # T, k, E held, H, F: a decode step of the cell's engine
    "serve-smallthinker-long-context": (32, 6, 64, 2560, 768),
    "serve-longcat-long-answers": (128, 12, 16, 6144, 2048),
}


def _compile_expert_layer(one_chip, monkeypatch, T, k, E, H, F):
    from ray_tpu.ops import experts

    # the backend here is the CPU, the target is not: what the rule and
    # the kernel's interpret switch ask is answered for the target
    # (cells/tools/compile_for_v5e.py does the same)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    bf = jnp.bfloat16
    return jax.jit(lambda y, idx, weight, *ws: experts.held_experts_ffn(
        y, idx, weight, *ws, first=0, activation=experts.reglu)).lower(
            shape((T, H), bf), shape((T, k), jnp.int32),
            shape((T, k), jnp.float32), shape((E, H, F), bf),
            shape((E, H, F), bf), shape((E, F, H), bf)).compile().as_text()


@pytest.mark.parametrize("cell", sorted(_EXPERT_CELLS))
def test_the_expert_layer_of_a_decode_step_compiles_as_one_kernel(
        one_chip, monkeypatch, cell):
    """Both expert cells' decode shapes take ``ops/pallas/expert_decode.py``:
    ONE Mosaic call an expert layer for its three products and its combine,
    within the VMEM the call asks for, and no grouped product is left."""
    text = _compile_expert_layer(one_chip, monkeypatch, *_EXPERT_CELLS[cell])
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "ragged" not in text and "scatter" not in text


@pytest.mark.parametrize("T", [
    pytest.param(14352, id="smallthinker-longest-bucket"),
    pytest.param(1024, id="smallthinker-first-bucket-past-the-rule"),
])
def test_a_long_prefill_bucket_keeps_the_grouped_products(
        one_chip, monkeypatch, T):
    """Past ``DECODE_KERNEL_MAX_PAIRS`` the expert layer is the grouped
    path's: three ``ragged_dot`` a chunk and the scatter-add."""
    text = _compile_expert_layer(one_chip, monkeypatch, T, 6, 64, 2560, 768)
    assert text.count("ragged-dot") >= 3 and "scatter" in text
