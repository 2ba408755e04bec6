"""The head and the loss as one function with its own backward rule
(``ray_tpu/ops/head_loss.py``, PR 38), held to the plain formulation it
replaced: float32 logits of the whole batch through ``log_softmax``.

What only the chip's compiler shows (three products, no ``.remat`` twin, no
``[tokens, vocab]`` float32 buffer) is in ``tests/test_flash_compile_v5e.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import (LlamaConfig, _head_chunks, llama_apply,
                                  llama_init, llama_loss, llama_param_specs)
from ray_tpu.models.training import ShardedTrainer, make_llama_trainer
from ray_tpu.ops import head_loss as hl
from ray_tpu.parallel.mesh import MeshConfig, create_mesh

B, S = 4, 16  # the loss sees S - 1 = 15 positions: 2 and 4 chunks leave a tail


def plain_loss(params, batch, cfg, *, mesh=None, rules=None):
    """``llama_loss`` as it stood before PR 38."""
    tokens = batch["tokens"]
    logits = llama_apply(params, tokens[:, :-1], cfg, mesh=mesh, rules=rules)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:].astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def _batch(mask=None, seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0, 256)
    if mask == "some":
        return {"tokens": tokens, "mask": (jax.random.uniform(
            jax.random.PRNGKey(seed + 1), (B, S)) > 0.4).astype(jnp.int32)}
    if mask == "none-counted":
        return {"tokens": tokens, "mask": jnp.zeros((B, S), jnp.int32)}
    return {"tokens": tokens}


def _chunked(monkeypatch, chunks, rows=B, vocab=256):
    """Set the module's constant so that ``chunks`` pieces of the 15
    positions are what the rule gives ``rows`` batch rows a device."""
    per_chunk = {1: 15, 2: 8, 4: 4}[chunks]
    monkeypatch.setattr(hl, "CHUNK_LOGITS_BYTES", 4 * rows * vocab * per_chunk)
    monkeypatch.setattr(hl, "CHUNK_MIN_ROWS", 1)
    assert hl.head_loss_chunks(rows, S - 1, vocab) == chunks


def _worst(got, want):
    return max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree.leaves(got), jax.tree.leaves(want), strict=True))


@pytest.mark.parametrize("tie", [False, True], ids=["own-head", "tied"])
@pytest.mark.parametrize("mask", [None, "some", "none-counted"])
@pytest.mark.parametrize("chunks", [1, 2, 4])
def test_loss_and_gradients_are_the_plain_formulations(monkeypatch, chunks,
                                                       mask, tie):
    """Float32 toy model: the loss, and the gradient of every parameter (the
    layers that produce the hidden states, the final norm, the head or,
    tied, the embedding it is the transpose of) within 1e-5; 4 chunks of 15
    positions are 3 of 4 and one of 3."""
    cfg = LlamaConfig.tiny(tie_embeddings=tie)
    params = llama_init(jax.random.PRNGKey(0), cfg)
    batch = _batch(mask)
    _chunked(monkeypatch, chunks)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg)))(params)
    want, want_grads = jax.value_and_grad(
        lambda p: plain_loss(p, batch, cfg))(params)
    assert abs(float(loss) - float(want)) < 1e-5
    assert _worst(grads, want_grads) < 1e-5
    assert ("lm_head" in grads) != tie
    # without differentiation: the same number, and no cotangent formed
    assert abs(float(llama_loss(params, batch, cfg)) - float(want)) < 1e-5
    if mask == "none-counted":
        assert float(loss) == 0.0 and _worst(grads, jax.tree.map(
            jnp.zeros_like, grads)) == 0.0


def test_the_undifferentiated_loss_forms_no_cotangent(monkeypatch):
    """The primal shares the rule's chunk loop, not its saved array: no
    ``[b, s, vocab]`` value anywhere in it, a chunk's logits the largest."""
    cfg = LlamaConfig.tiny()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    batch = _batch()
    _chunked(monkeypatch, 2)
    whole = f"f32[{B},{S - 1},256]"
    primal = str(jax.make_jaxpr(lambda p: llama_loss(p, batch, cfg))(params))
    assert whole not in primal and f"f32[{B},8,256]" in primal
    ruled = str(jax.make_jaxpr(jax.grad(
        lambda p: llama_loss(p, batch, cfg)))(params))
    assert whole in ruled  # the cotangent, in the products' operand type


@pytest.mark.parametrize("chunks", [1, 2])
def test_accumulated_microbatches_take_the_rule(monkeypatch, chunks):
    """``accum_steps`` 2: the rule inside the trainer's scan, against the
    same trainer over the plain loss."""
    import functools

    cfg = LlamaConfig.tiny()
    mesh = create_mesh(MeshConfig(dp=1), devices=jax.devices("cpu")[:1])
    _chunked(monkeypatch, chunks, rows=B // 2)
    ruled = make_llama_trainer(cfg, mesh, accum_steps=2)
    plain = ShardedTrainer(
        functools.partial(llama_init, cfg=cfg),
        functools.partial(plain_loss, cfg=cfg, mesh=mesh),
        llama_param_specs(cfg), mesh=mesh, accum_steps=2)
    out = []
    for trainer in (ruled, plain):
        state = trainer.init_state(jax.random.PRNGKey(0))
        state, metrics = trainer.step(state, trainer.shard_batch(_batch()))
        out.append((state["params"], metrics))
    assert abs(float(out[0][1]["loss"]) - float(out[1][1]["loss"])) < 1e-5
    assert abs(float(out[0][1]["grad_norm"])
               - float(out[1][1]["grad_norm"])) < 1e-5
    assert _worst(out[0][0], out[1][0]) < 1e-5


@pytest.mark.parametrize("mc,rows", [
    pytest.param(MeshConfig(dp=2, fsdp=2), 1, id="batch-over-dp-fsdp"),
    pytest.param(MeshConfig(dp=2, tp=2), 2, id="tp2-over-the-vocabulary"),
    pytest.param(MeshConfig(dp=1, fsdp=2, tp=2), 2, id="fsdp2-tp2"),
])
@pytest.mark.parametrize("chunks", [1, 4])
def test_on_a_mesh_the_rule_is_the_plain_loss_on_one_device(monkeypatch, mc,
                                                            rows, chunks):
    """The tests' CPU mesh, parameters sharded by the rule table: the batch
    over dp and fsdp (each device chunks its own rows), the head's vocabulary
    over tp."""
    cfg = LlamaConfig.tiny()
    mesh = create_mesh(mc, devices=jax.devices("cpu")[:4])
    _chunked(monkeypatch, chunks, rows=rows)
    assert _head_chunks(B, S - 1, cfg, mesh, None) == chunks
    trainer = make_llama_trainer(cfg, mesh)
    params = trainer.init_state(jax.random.PRNGKey(0))["params"]
    batch = trainer.shard_batch(_batch("some"))
    with mesh:
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: llama_loss(p, batch, cfg, mesh=mesh)))(params)
    host = jax.device_get
    want, want_grads = jax.value_and_grad(
        lambda p: plain_loss(p, host(batch), cfg))(host(params))
    assert abs(float(loss) - float(want)) < 1e-5
    assert _worst(host(grads), want_grads) < 1e-5


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("chunks", [1, 4])
def test_bf16_activations_stay_in_the_cells_band(monkeypatch, chunks, seed):
    """The configurations' precision (bf16 activations, float32 state)
    against the float32 formulation, in the band of
    ``cells/tests/test_cells.py::test_the_control_reads_not_correct``: the
    gradient's direction within 1e-4 (its float8 control reads 6e-4)."""
    cfg = LlamaConfig.tiny(dtype=jnp.bfloat16, attention_impl="ref")
    exact = LlamaConfig.tiny(attention_impl="ref")
    params = llama_init(jax.random.PRNGKey(seed), cfg)
    batch = _batch(seed=seed + 10)
    _chunked(monkeypatch, chunks)
    loss, grads = jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg))(params)
    want, want_grads = jax.value_and_grad(
        lambda p: plain_loss(p, batch, exact))(params)
    assert abs(float(loss) - float(want)) < 0.012
    flat = lambda t: jnp.concatenate(  # noqa: E731
        [g.astype(jnp.float32).ravel() for g in jax.tree.leaves(t)])
    a, b = flat(grads), flat(want_grads)
    assert 1 - float(a @ b / (jnp.linalg.norm(a) * jnp.linalg.norm(b))) < 1e-4
    assert all(g.dtype == jnp.float32 for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("rows,seq,vocab,chunks", [
    pytest.param(4, 4096, 32768, 32, id="train-1chip-s4096"),
    pytest.param(16, 1024, 32768, 32, id="sixteen-rows-a-device"),
    pytest.param(1, 4096, 32768, 8, id="one-row-a-device"),
    pytest.param(4, 4096, 151936, 32, id="smallthinker-vocabulary"),
    pytest.param(4, 500, 32000, 4, id="a-tail-of-116"),
    pytest.param(1, 500, 32000, 1, id="under-the-limit"),
    pytest.param(4, 15, 256, 1, id="toy"),
])
def test_the_chunk_count_comes_from_static_shapes(rows, seq, vocab, chunks):
    """The fewest chunks whose float32 logits fit ``CHUNK_LOGITS_BYTES``
    (64 MiB a device), none under ``CHUNK_MIN_ROWS`` (512) rows: the four
    shapes the chip saw (PERF.md §6, PR 38) take the size that read
    fastest there: 128, 32, 512 and, at SmallThinker's vocabulary, 128
    positions of 311 MB where 64 MiB would hold 27."""
    assert (hl.CHUNK_LOGITS_BYTES, hl.CHUNK_MIN_ROWS) == (64 * 2**20, 512)
    n = hl.head_loss_chunks(rows, seq, vocab)
    assert n == chunks
    size = -(-seq // n)
    fits = 4 * rows * size * vocab <= hl.CHUNK_LOGITS_BYTES
    assert fits or rows * (size - 1) < hl.CHUNK_MIN_ROWS or n == 1
    if n > 1:  # and one chunk fewer would break what held this count
        longer = -(-seq // (n - 1))
        assert 4 * rows * longer * vocab > hl.CHUNK_LOGITS_BYTES


def test_a_mesh_that_shards_the_sequence_leaves_it_whole(monkeypatch):
    """With the rule table's ``seq`` on a mesh axis each device holds a
    piece of the sequence already: one chunk, and the loss is the plain
    one."""
    cfg = LlamaConfig.tiny()
    mesh = create_mesh(MeshConfig(dp=2, sp=2), devices=jax.devices("cpu")[:4])
    monkeypatch.setattr(hl, "CHUNK_LOGITS_BYTES", 4 * 256)
    monkeypatch.setattr(hl, "CHUNK_MIN_ROWS", 1)
    assert _head_chunks(B, S, cfg, None, None) == S
    assert _head_chunks(B, S, cfg, mesh, None) == 1
    no_sp = {"batch": ("dp", "sp"), "vocab": None}
    assert _head_chunks(B, S, cfg, mesh, no_sp) == S
    trainer = make_llama_trainer(cfg, mesh)
    params = trainer.init_state(jax.random.PRNGKey(0))["params"]
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (B, S + 1), 0, 256)}
    with mesh:
        loss = jax.jit(lambda p, b: llama_loss(p, b, cfg, mesh=mesh))(
            params, trainer.shard_batch(batch))
    want = plain_loss(jax.device_get(params), batch, cfg)
    np.testing.assert_allclose(float(loss), float(want), atol=1e-5)
