"""The flash kernels, the decode-shaped expert kernel (PR 32), the two
decode programs whose heads projections read their weights in place (PR 35)
the three served models' decode programs' name scopes after XLA:TPU's
fusion (PR 37), the train cell's step with its head and loss as one
function (PR 38) and Phi-4-mini-flash's decode step writing a token's
slab into its page as one update (PR 40), LongCat-Flash's uncached
prefill through the flash forward at 192-wide keys beside 128-wide values
(PR 43) and its decode step reading the absorbed pair in place (PR 45),
GigaChat3.1's flash forward at 192 beside 192 and its decode step on both
kernels (PR 47), the gated delta rule's decode update and chunked scan
(PR 52) and GigaChat3.5's decode step around that update, both state pools
aliased through it (PR 53), compiled for a v5e that is described, not
attached.

The interpreter cannot see what the chip's compiler refuses: more VMEM
than a kernel may use (the backward keeps dK/dV whole in scratch and sets
its own ``vmem_limit_bytes``), a slice off the (8, 128) tiling.  libtpu is
installed in the sandbox and compiles for a topology by name, in about two
seconds a shape and at no chip time.  Nothing runs: no result, no time.

Only the worker that is given this file may load libtpu, and only once a
test has started: the topology is described in a fixture, never at import.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
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


def _mosaic_calls(text):
    """The operands' shapes of every Mosaic call in a compiled module, as
    its ``operand_layout_constraints`` give them."""
    return [re.findall(r"(\w+\[[\d,]*\])", m) for m in re.findall(
        r'custom_call_target="tpu_custom_call", operand_layout_constraints='
        r"\{((?:\w+\[[\d,]*\]\{[\d,]*\}(?:, )?)+)\}", text)]


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
    # equal widths: the operands are the parent's (PR 43), the sequence
    # padded to whole blocks and the heads folded into the batch
    padded = -(-sq // 1024) * 1024
    assert _mosaic_calls(text) == [[f"bf16[{h},{padded},128]"]
                                   + 2 * [f"bf16[{kvh},{padded},128]"]]


# ------------------ values narrower than the keys (PR 43): latent
# attention's non-absorbed form, LongCat-Flash's uncached prefill

@pytest.mark.parametrize("s", [2048, 1024, 256])
def test_the_forward_compiles_at_192_wide_keys_beside_128_wide_values(
        one_chip, monkeypatch, s):
    """LongCat-Flash's widths (64 heads, keys ``qk_nope + qk_rope`` = 192,
    values 128, its own scale), bf16, the longest bucket, the one that is a
    single block and the shortest the rule gives the kernel: one Mosaic
    call whose key operand is 192 wide as it stands (a block's last
    dimension equal to the array's), the output 128 wide."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def shape(d):
        return jax.ShapeDtypeStruct((1, s, 64, d), jnp.bfloat16,
                                    sharding=one_chip)

    text = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, scale=192 ** -0.5)).lower(
            shape(192), shape(192), shape(128)).compile().as_text()
    assert _mosaic_calls(text) == [
        2 * [f"bf16[64,{s},192]"] + [f"bf16[64,{s},128]"]]
    assert re.search(rf"= \(bf16\[64,{s},128\][^=]*custom-call\(", text)


@pytest.mark.parametrize("s", [2048, 256])
def test_the_forward_compiles_at_192_wide_keys_beside_192_wide_values(
        one_chip, monkeypatch, s):
    """GigaChat3.1's widths (64 heads, keys ``qk_nope + qk_rope`` = 192,
    values ``v_head_dim`` = 192, the YaRN scale 0.144680 and not
    ``192^-0.5``), bf16, the longest bucket and the shortest the rule gives
    the kernel: one Mosaic call, every operand and the output 192 wide as
    they stand."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = jax.ShapeDtypeStruct((1, s, 64, 192), jnp.bfloat16,
                                 sharding=one_chip)
    text = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, scale=0.144680)).lower(
            shape, shape, shape).compile().as_text()
    assert _mosaic_calls(text) == [3 * [f"bf16[64,{s},192]"]]
    assert re.search(rf"= \(bf16\[64,{s},192\][^=]*custom-call\(", text)


def test_longcat_uncached_prefill_attends_through_the_flash_kernel(
        one_chip, monkeypatch):
    """LongCat-Flash's 1024-token prefill at the cell's widths (its four
    double layers, 16 held experts): eight flash calls, one an attention
    block, all eight one function of the lowered module (traced and lowered
    to Mosaic once), and no score matrix: on the parent the program held
    ``f32[16,1024,1024]`` scores and ``pred[..,1024,1024]`` masks, a group
    of 16 heads at a time, each a pass through HBM."""
    from ray_tpu.models import longcat

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = longcat.LongcatConfig(
        vocab_size=16384, num_layers=4, first_expert=80, held_experts=16,
        max_seq_len=3584, param_dtype=jnp.bfloat16)
    S = 1024
    assert longcat.prefill_attention_path(S, 0) == "flash"
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(
        functools.partial(longcat.longcat_init, cfg=cfg), key)
    pool = jax.eval_shape(lambda: longcat.init_latent_pool(cfg, 512, 16))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    empty = lambda w: jax.ShapeDtypeStruct(  # noqa: E731
        (2 * cfg.num_layers, 0, w), cfg.dtype)
    args = (params, i32(1, S), i32(), i32(), empty(cfg.kv_lora_rank),
            empty(cfg.qk_rope_head_dim), i32(), i32(S), i32(S), pool)
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), args)
    lowered = jax.jit(
        functools.partial(longcat.latent_prefill_suffix, cfg=cfg),
        donate_argnums=(9,)).lower(*args)
    module = lowered.as_text()
    assert module.count("func.func private @_flash_forward_only") == 1
    assert module.count("call @_flash_forward_only") == 8
    text = lowered.compile().as_text()
    flash = [c for c in _mosaic_calls(text)
             if c == 2 * ["bf16[64,1024,192]"] + ["bf16[64,1024,128]"]]
    assert len(flash) == 8
    assert "f32[16,1024,1024]" not in text
    assert not re.search(r"pred\[[\d,]*1024,1024\]", text)


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


# --------------- the heads projection read in place (PR 35): the decode
# programs of the two served models whose layers project onto their heads
# through ``ops/layers.py:heads_projection``

_MIB = 1 << 20
_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1, "pred": 1}


def _entry(text):
    """The lines of a compiled module's entry computation."""
    return re.search(r"^ENTRY .*?\{\n(.*?)^\}", text,
                     re.S | re.M).group(1).splitlines()


def _entry_results(text):
    """(name, opcode, dims, bytes) of every array-valued instruction of the
    compiled module's entry computation."""
    for line in _entry(text):
        m = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(",
            line)
        if not m:
            continue
        name, dtype, dims, op = m.groups()
        dims = tuple(int(d) for d in dims.split(",") if d)
        size = _BYTES.get(dtype, 4)
        for d in dims:
            size *= d
        yield name, op, dims, size


def _relayouts(text, at_least=_MIB):
    """What the parent's decode step did to a weight before multiplying by
    it: a ``slice*fusion`` through the vector unit and a synchronous
    ``copy`` (the transposition), results of ``at_least`` bytes; and
    (PR 45) an asynchronous ``copy-start`` whose destination is laid out
    otherwise than its source, which is the same transposition under
    another name (a ``copy-start`` that keeps the layout is XLA's prefetch
    into fast memory and moves the bytes the product would read anyway)."""
    found = [(name, op, dims) for name, op, dims, size in _entry_results(text)
             if size >= at_least
             and (op == "copy" or (op == "fusion" and "slice" in name))]
    for line in _entry(text):  # (destination, source, context) = copy-start(
        m = re.match(r"\s*%?([\w.\-]+) = \((\w+)\[([\d,]*)\]\{([\d,]*)[:}]\S* "
                     r"\w+\[[\d,]*\]\{([\d,]*)[:}].* copy-start\(", line)
        if m and m.group(4) != m.group(5):
            dims = tuple(int(d) for d in m.group(3).split(","))
            if _BYTES.get(m.group(2), 4) * np.prod(dims) >= at_least:
                found.append((m.group(1), "copy-start", dims))
    return found


# what hands an array on as it is, or moves it without an instruction's work
_PASSES_ON = ("bitcast", "get-tuple-element", "copy-start", "copy-done",
              "slice-start", "slice-done")


def _origin(text, name):
    """(opcode, ``op_name``) of what an entry instruction's first operand
    is, looked up through ``_PASSES_ON``: ``("parameter",
    "args[0]['layers'][0]...")`` for a weight, ``("fusion",
    ".../attn.proj/.../dot_general")`` for a product's result."""
    lines = {m.group(1): m.group(2) for m in (re.match(
        r"\s*(?:ROOT )?%?([\w.\-]+) = (.*)", line) for line in _entry(text))
        if m}

    def instruction(name):
        """(opcode, first operand's name, the line's right-hand side)."""
        op, operand = re.search(r" ([\w\-]+)\(%?([\w.\-]*)",
                                lines[name]).groups()
        return op, operand, lines[name]

    op, name, _ = instruction(name)
    while True:
        op, operand, rhs = instruction(name)
        if op not in _PASSES_ON:
            label = re.search(r'op_name="([^"]*)"', rhs)
            return op, label.group(1) if label else ""
        name = operand


def _unscoped(text):
    """(instructions counted, those without a part of the vocabulary) among
    what a profiler's trace times: the ``fusion``, ``convolution`` and
    Mosaic-call instructions of the entry computation and of the loop
    bodies.  A fusion carries its root's ``op_name``; XLA's own
    instructions (a ``copy``, a ``ConcatBitcast`` of prefetched slices)
    carry none and are not a model's part."""
    from ray_tpu._private import tracing

    # {computation: its lines}; the entry computation under "ENTRY"
    comps = {m.group(1) or m.group(2): m.group(3) for m in re.finditer(
        r"^(?:(ENTRY) )?%([\w.\-]+) [^\n]*\{\n(.*?)^\}", text,
        re.S | re.M)}
    bodies = re.findall(r"body=%([\w.\-]+)", text)
    lines = "\n".join(comps[c] for c in ["ENTRY", *bodies])
    counted, bare = 0, []
    for line in lines.splitlines():
        m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = .*? "
                     r"(fusion|convolution|custom-call)\(", line)
        if not m or (m.group(2) == "custom-call"
                     and "tpu_custom_call" not in line):
            continue
        counted += 1
        name = re.search(r'op_name="([^"]*)"', line)
        words = re.split(r"[/()]", name.group(1)) if name else []
        if "engine.decode" not in words or not any(
                w in tracing.PART_SCOPES for w in words):
            bare.append((m.group(1), name.group(1) if name else None))
    return counted, bare


def _compiled_decode(step, params, pool, B, MB, one_chip):
    """``step(params, token, cur_len, block_tables, pool, key,
    temperature)`` under the engine's program scope, compiled for ``B``
    slots of ``MB`` blocks (``block_tables`` by type where ``pool`` is),
    the pool donated as the engine donates it."""
    from ray_tpu._private import tracing

    step = functools.partial(tracing.scoped, "engine.decode", step)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    tables = ({t: i32(B, 1 if {"ssm", "s"} & set(pool[t]) else MB)
               for t in pool}
              if "k" not in pool and "kv" not in pool else i32(B, MB))
    args = (params, i32(B), i32(B), tables, pool, key,
            jax.ShapeDtypeStruct((B,), jnp.float32))
    args = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), args)
    return jax.jit(step, donate_argnums=(4,)).lower(*args).compile()


def _compile_decode(*args):
    """The text of ``_compiled_decode``'s program."""
    return _compiled_decode(*args).as_text()


def _page_buffer_bytes(page_rows, width, n_pools, max_blocks):
    """VMEM the paged kernel's page buffers take in a program whose bf16
    pools have pages ``[page_rows, width]``: ``depth`` buffers a pool of
    ``pages`` pages each (``paged_attention.pipeline_plan``, which the
    kernel's one ``pallas_call`` sizes its scratch by)."""
    from ray_tpu.ops.pallas.paged_attention import pipeline_plan

    pages, depth = pipeline_plan(page_rows, width, 2, n_pools, max_blocks)
    return depth * n_pools * pages * page_rows * width * 2


def test_the_dense_decode_step_reads_wq_wk_wv_in_place(one_chip, monkeypatch):
    """``paged_decode_sample`` at Mistral-7B-v0.3's widths (2 of the cell's
    22 layers, its 32 slots, block size and ``max_len``, a tenth of its
    pool, bf16): no instruction of the entry computation slices a weight
    through the vector unit or copies it into another layout.  On the
    parent this finds 3 copies a layer (``copy = bf16[4096,4096]{1,0}``,
    two ``bf16[1024,4096]{1,0}``: wq, wk and wv TRANSPOSED for a product
    that XLA folded with the reshape behind it), 100.7 MB at two layers
    and 1 107 MB a step at the cell's 22 beside 797 MB of
    ``slice_bitcast_fusion*``.

    Not held to zero: ``slice-done``.  At two layers the compiler's memory
    assignment fetches all six weights ahead into memory space 1 by
    ``slice-start``/``slice-done``, a DMA in the parameter's own layout that
    the product then reads there (no vector work, the same bytes once); at
    22 layers it fetches none.  The parent's ``slice-done`` fed the copies,
    and with no copy left there is nothing for one to feed."""
    from ray_tpu.models.llama import LlamaConfig, llama_init
    from ray_tpu.models.paged_generation import (init_kv_pool,
                                                 paged_decode_sample)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = LlamaConfig(
        vocab_size=32768, hidden_size=4096, num_layers=2, num_heads=32,
        num_kv_heads=8, head_dim=128, mlp_dim=14336, max_seq_len=2560,
        rope_theta=1e6, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    B, bs, MB = 32, 16, 160
    params = jax.eval_shape(functools.partial(llama_init, cfg=cfg),
                            jax.random.PRNGKey(0))
    pool = jax.eval_shape(lambda: init_kv_pool(cfg, 256, bs))
    text = _compile_decode(functools.partial(paged_decode_sample, cfg=cfg),
                           params, pool, B, MB, one_chip)
    assert text.count('custom_call_target="tpu_custom_call"') == 2  # a layer
    assert _relayouts(text) == []
    # PR 37: what the trace will time names its part (docs/observability.md)
    counted, bare = _unscoped(text)
    assert counted >= 50 and len(bare) <= 0.05 * counted, bare


@pytest.fixture(scope="module")
def longcat_decode(one_chip):
    """LongCat-Flash's decode step at its published widths, one double
    layer, 16 held experts, the cell's 128 slots: (its compiled text, the
    configuration)."""
    from ray_tpu.models import longcat

    cfg = longcat.LongcatConfig(
        vocab_size=16384, num_layers=1, first_expert=80, held_experts=16,
        max_seq_len=3584, param_dtype=jnp.bfloat16)
    B, bs, MB = 128, 16, 224
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(
        functools.partial(longcat.longcat_init, cfg=cfg), key)
    pool = jax.eval_shape(lambda: longcat.init_latent_pool(cfg, 512, bs))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        mp.setattr(jax, "device_count", lambda: 1)
        text = _compile_decode(
            functools.partial(longcat.latent_decode_sample, cfg=cfg,
                              attn="latent_kernel"),
            params, pool, B, MB, one_chip)
    return text, cfg


def test_longcat_decode_step_reads_w_qb_in_place(longcat_decode):
    """No copy of ``w_qb`` (``[q_lora_rank, heads x (nope + rope)]`` =
    ``[1536, 12288]``, 37.7 MB) in either layout.  The parent of PR 35 had
    one an attention block, ``copy = bf16[1536,12288]{0,1}`` without a
    memory space: read, written transposed to HBM and read again.  What
    stays an attention block, and is not this test's: the projection's own
    result ``[128, 1, 12288]`` (3.1 MB), which is where the barrier moves
    the relayout to.  (Until PR 45 also ``w_kvb`` whole: the test below.)"""
    text, cfg = longcat_decode
    w_qb = (cfg.q_lora_rank,
            cfg.num_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
    assert w_qb == (1536, 12288)
    assert [m for m in _relayouts(text)
            if m[2] in (w_qb, w_qb[::-1])] == []
    counted, bare = _unscoped(text)  # PR 37, as for the dense step
    assert counted >= 80 and len(bare) <= 0.05 * counted, bare


def test_longcat_decode_step_reads_the_absorbed_pair_in_place(longcat_decode):
    """The same program: no relayout of 1 MiB or more of ANY parameter, and
    none with the dimensions of ``w_kvb`` ``(512, 16384)``, of one of its
    halves or of a transposed half but the one of a product's own result.
    Until PR 45 every attention block had ``copy = bf16[512,16384]{0,1...
    S(1)}`` of the parameter ``w_kvb``: the whole matrix (16.8 MB) fetched
    transposed into fast memory for the two absorbed products, which read
    strided halves of it.  They read ``w_uk`` / ``w_uv`` now, each where it
    lies.

    What stays, by its origin: the way in's RESULT ``q_lat`` ``[heads,
    slots, kr]`` permuted to the kernel's ``[slots, heads, kr]`` (8.4 MB:
    with 128 slots it has a half's dimensions, which is how PR 35 came to
    read the parent's as "one of the halves"; the parent transposed it out
    of ``[heads, kr, slots]``), and the swapped ``q_nope`` (2.1 MB)."""
    text, cfg = longcat_decode
    kr, nh = cfg.kv_lora_rank, cfg.num_heads
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    found = [(m, _origin(text, m[0])) for m in _relayouts(text)]
    assert [f for f in found if f[1][0] == "parameter"] == []
    alike = [f for f in found if sorted(f[0][2]) in (
        sorted((kr, nh * (dn + dv))), sorted((kr, nh, dn)),
        sorted((kr, nh, dv)))]
    assert len(alike) == 2, alike  # one an attention block
    assert all(op == "fusion" and "attn.proj/hbd,hdk->hbk" in label
               for _, (op, label) in alike), alike
    # the products read the parameters themselves (or XLA's own fetch of
    # one in the parameter's layout): nothing of w_kvb is an operand
    assert "w_uk" in text and "w_uv" in text and "w_kvb" not in text


def test_gigachat_decode_step_runs_both_kernels_and_names_its_parts(
        one_chip, monkeypatch):
    """GigaChat3.1-702B-A36B's decode step at the cell's widths (one
    leading dense layer and one expert layer of its six, 16 held experts,
    16 032 rows, 128 slots of 320 blocks): the latent arm of the paged
    kernel a layer over a pool of ``L`` rows (values 192 wide leave the
    cached row what it is: the kernel's result is ``[slots, heads, 512]``),
    the expert decode kernel at ``H`` 7168, and after XLA:TPU's fusion at
    least 95% of the instructions a trace will time carry ``engine.decode``
    and a part of the vocabulary; the shared expert's products carry
    ``experts.shared`` inside ``experts``."""
    from ray_tpu.models import deepseek_v3 as ds

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = ds.DeepseekV3Config(
        vocab_size=16032, num_layers=2, dense_layers=1, first_expert=80,
        held_experts=16, max_seq_len=5120, param_dtype=jnp.bfloat16)
    B, bs, MB = 128, 16, 320
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(
        functools.partial(ds.deepseek_v3_init, cfg=cfg), key)
    pool = jax.eval_shape(lambda: ds.init_latent_pool(cfg, 512, bs))
    assert pool["kv"].shape == (2, 512, bs, 640)
    assert served_expert_path(cfg, B) == "decode_kernel"
    text = _compile_decode(
        functools.partial(ds.decode_sample, cfg=cfg, attn="latent_kernel"),
        params, pool, B, MB, one_chip)
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    latent = re.findall(r"= bf16\[128,64,512\][^=]*custom-call\(", text)
    assert len(latent) == 2  # a layer
    # the copy pipeline Mosaic just took at this shape: 3 buffers of 64
    # pages [16, 640] of the one pool (PR 49; 2 buffers until then)
    assert _page_buffer_bytes(bs, 640, 1, MB) == 3 * 1_310_720
    assert any("bf16[16,7168,2048]" in " ".join(c)
               for c in _mosaic_calls(text))  # the held experts, in place
    counted, bare = _unscoped(text)
    assert counted >= 60 and len(bare) <= 0.05 * counted, bare
    assert re.search(r'op_name="[^"]*/experts/experts\.shared/[^"]*dot_general',
                     text)
    assert "w_kvb" not in text  # the decode step reads the derived pair


def served_expert_path(cfg, tokens):
    from ray_tpu.models.served import served_model

    return served_model(cfg).expert_path(cfg, tokens)


def test_smallthinker_decode_step_names_its_parts(one_chip, monkeypatch):
    """SmallThinker's decode step as its cell runs it (one period of four
    layers, every expert, the whole vocabulary, 32 slots of 897 blocks):
    after XLA:TPU's fusion at least 95% of the instructions a trace will
    time carry ``engine.decode`` and a part of the vocabulary, and the
    paged kernel and the expert kernel are there, a call a layer each."""
    from ray_tpu.models import smallthinker as st

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = st.SmallThinkerConfig(num_layers=4, max_seq_len=14352,
                                param_dtype=jnp.bfloat16)
    B, bs, MB = 32, 16, 897
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(
        functools.partial(st.smallthinker_init, cfg=cfg), key)
    pool = jax.eval_shape(
        lambda: st.init_pools(cfg, {"full": 512, "window": 512}, bs))
    text = _compile_decode(
        functools.partial(st.decode_sample, cfg=cfg, attn="paged_kernel"),
        params, pool, B, MB, one_chip)
    assert text.count('custom_call_target="tpu_custom_call"') == 8
    counted, bare = _unscoped(text)
    assert counted >= 120 and len(bare) <= 0.05 * counted, bare


def _scatters(text):
    """(the result's dims, the number of updates) of every ``scatter`` in
    the compiled module: an update is one index vector, so the updates'
    dims that are no window's."""
    out = []
    for m in re.finditer(
            r"= \w+\[([\d,]*)\]\S* scatter\(%[\w.\-]+, %[\w.\-]+, "
            r"%([\w.\-]+)\), update_window_dims=\{([\d,]*)\}", text):
        dims, updates, window = m.groups()
        shape = re.search(
            r"%" + re.escape(updates) + r" = \w+\[([\d,]*)\]", text).group(1)
        window = {int(d) for d in window.split(",") if d}
        out.append((tuple(int(d) for d in dims.split(",") if d), int(np.prod(
            [int(d) for i, d in enumerate(shape.split(",")) if d
             and i not in window]))))
    return out


def test_phi4flash_decode_step_writes_slabs(one_chip, monkeypatch):
    """Phi-4-mini-flash's decode step as its cell runs it (all 32 layers,
    64 slots of 257 blocks, the cell's pools): each of the nine storing
    layers' keys and values reaches its page in ONE scatter of 64 updates,
    a token's ``[10, 128]`` slab as the window ``[5, 2, 128]`` of the page
    viewed ``[16, 5, 2, 128]``: the same bytes in the same order, so a
    ``bitcast`` and no copy (temporaries 0.040 GB, XLA's own prefetches of
    weights; a layer of the window pool is 0.089).  The parent's step
    wrote 640 single rows a scatter, ~70 ns each on the chip (1.03 of a
    step's 19 ms), and a pool held ``[.., 16, 10, 128]`` was copied whole
    every layer (PR 39: 4.19 GB)."""
    from ray_tpu.models import phi4flash as pf

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = pf.Phi4FlashConfig(max_seq_len=4112, param_dtype=jnp.bfloat16)
    B, bs, MB = 64, 16, 257
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(
        functools.partial(pf.phi4flash_init, cfg=cfg), key)
    pool = jax.eval_shape(lambda: pf.init_pools(
        cfg, {"full": 8320, "window": 2180, "state": 65}, bs))
    compiled = _compiled_decode(
        functools.partial(pf.decode_sample, cfg=cfg, attn="paged_kernel"),
        params, pool, B, MB, one_chip)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 16  # reads
    # the copy pipeline Mosaic just took at this shape: 4 buffers of 6
    # pages [160, 128] in each of two pools (PR 49; 2 buffers until then)
    assert _page_buffer_bytes(bs * 10, 128, 2, MB) == 4 * 2 * 245_760
    scatters = _scatters(text)
    assert all(n <= B for _, n in scatters), scatters
    # keys and values of the full layer and the eight window layers (XLA
    # folds the leading dimensions as it likes; the window is the slab)
    assert [n for dims, n in scatters if dims[-3:] == (5, 2, 128)] == 18 * [B]
    assert compiled.memory_analysis().temp_size_in_bytes < 0.05e9


# ------------------ the gated delta rule's two programs (PR 52: ops/delta.py)

@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_the_delta_update_rewrites_the_records_where_they_lie(one_chip,
                                                               dtype):
    """GigaChat3.5's decode update at its cell's widths (128 slots, 4
    layers of 129 records of 64 heads x [128, 128] float32, 2.2 GB, and
    their tails of 3 x 16 384 channels, bfloat16 in the cell): ONE Mosaic
    call from the slot's row of ``qkv`` to both pools, the records' and the
    tails' operands aliased to its results, so the compiled program holds
    each pool once (no temporary of their size).  A slot's whole record of a
    layer (4 MiB) and its tail (96 KiB) are blocks: two in and two out in
    flight fit the VMEM the call asks for, the convolution, the heads'
    vectors and the one ``[128, 128]`` transpose beside them."""
    from ray_tpu.ops import delta

    b, Hv, d, L, R, C, K = 128, 64, 128, 4, 129, 16384, 4
    arr = lambda dt, *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, dt, sharding=one_chip)
    f32 = functools.partial(arr, jnp.float32)
    state = {"s": f32(L, R, Hv, d, d),
             "conv": arr(dtype, L, R, (K - 1) * C // d, d)}

    def update(qkv, alpha, beta, conv_w, state, rec):
        return delta.delta_update_records(
            qkv, alpha, beta, conv_w, state, 2, rec, path="kernel",
            interpret=False)

    compiled = jax.jit(update, donate_argnums=(4,)).lower(
        arr(dtype, b, C), f32(b, Hv), f32(b, Hv), arr(dtype, K, C), state,
        arr(jnp.int32, b)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    m = compiled.memory_analysis()
    records = L * R * Hv * d * d * 4
    tails = L * R * (K - 1) * C * jnp.dtype(dtype).itemsize
    assert m.alias_size_in_bytes >= records + tails
    assert m.temp_size_in_bytes < 0.05 * records


def test_gigachat35_decode_step_moves_no_pool_around_its_update(
        one_chip, monkeypatch):
    """GigaChat3.5-432B-A28B's decode step as its cell runs it (published
    layers 0, 4-7: four Gated DeltaNet layers and one latent block, 16 held
    experts, 16 032 rows, 128 slots, 129 records): four
    ``gated_delta_update`` calls, each handed both state pools as they came
    out of the one before (aliased: the program holds them once, its
    temporaries a few MB), beside the latent arm of the paged kernel and
    four expert kernels.  What the parent's step did around its kernel is
    gone (PERF.md section 5, PR 52's traced run): XLA's copies of the
    tails' pool (``bf16[4,129,49152]``, twice rematerialised, 0.40 ms a
    step) and the kernel's column vectors laid out (``copy
    f32[16,8,32,128]``, two a layer, 0.30 ms).  The relayout that is left
    falls on the 128 rows of ``qkv`` (4 MB a layer), behind the barrier
    that keeps ``W_qkv`` (235 MB) from being transposed with it."""
    from ray_tpu.models import gigachat3_5 as gc

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    cfg = gc.GigaChat35Config(
        vocab_size=16032, num_layers=5, dense_layers=1,
        full_attention_layers=(4,), first_expert=80, held_experts=16,
        max_seq_len=4112, param_dtype=jnp.bfloat16)
    B, bs, MB, R = 128, 16, 257, 129
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    params = jax.eval_shape(
        functools.partial(gc.gigachat3_5_init, cfg=cfg), key)
    pool = jax.eval_shape(
        lambda: gc.init_pools(cfg, {"latent": 512, "state": R}, bs))
    tails = pool["state"]["conv"]
    assert tails.shape == (4, R, 384, 128) and tails.dtype == jnp.bfloat16
    assert gc.delta.delta_update_path(pool["state"]) == "kernel"
    compiled = _compiled_decode(
        functools.partial(gc.decode_sample, cfg=cfg, attn="latent_kernel"),
        params, pool, B, MB, one_chip)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    assert len(re.findall(r"%gated_delta_update[.\d]* = ", text)) == 4
    copies = re.findall(r"= (\w+\[[\d,]*\])\S* copy\(", text)
    assert copies and not {
        "bf16[4,129,49152]", "bf16[129,49152]", "bf16[4,129,384,128]",
        "bf16[129,384,128]", "f32[16,8,32,128]"} & set(copies), copies
    assert "bf16[16384,7168]" not in copies  # W_qkv, transposed
    m = compiled.memory_analysis()
    pools = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in jax.tree.leaves(pool))
    assert m.alias_size_in_bytes >= pools
    assert m.temp_size_in_bytes < 0.05 * pool["state"]["s"].size * 4
    counted, bare = _unscoped(text)
    assert counted >= 100 and len(bare) <= 0.05 * counted, bare


def test_the_chunked_delta_scan_compiles_for_a_prompt_bucket(one_chip):
    """The prefill's scan at the cell's largest bucket (1024 positions, 64
    value heads of 128): plain XLA, the triangular solve included, and
    temporaries of some tens of MB beside 9.5 GB of weights."""
    from ray_tpu.ops import delta

    S, Hv, d = 1024, 64, 128
    f32 = lambda *s: jax.ShapeDtypeStruct(  # noqa: E731
        s, jnp.float32, sharding=one_chip)

    def scan(q, k, v, g, beta):
        return delta.chunked_delta_scan(
            q, k, v, g, beta, jnp.zeros((1, Hv, d, d), jnp.float32), 700)

    compiled = jax.jit(scan).lower(
        f32(1, S, Hv, d), f32(1, S, Hv, d), f32(1, S, Hv, d), f32(1, S, Hv),
        f32(1, S, Hv)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e9


# ------------------- the train cell's head and loss as one function (PR 38)

@pytest.fixture(scope="module")
def train_step(one_chip):
    """The text of ``train-1chip-s4096``'s step (the cell's configuration
    and traffic files: Mistral-7B-v0.3's widths, 2 layers, 4 x 4096 tokens,
    AdamW), compiled as ``cells/tools/compile_for_v5e.py`` compiles it."""
    import json
    import os

    from jax.sharding import Mesh

    from cells import families
    from ray_tpu.parallel.mesh import MESH_AXES

    cells = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "cells")
    with open(os.path.join(
            cells, "configs", "mistral-7b-v0.3-L2-train.json")) as f:
        config = json.load(f)
    with open(os.path.join(cells, "traffic", "train-b4-s4096.json")) as f:
        traffic = json.load(f)
    fam = families.load(config["family"])
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(
        (1,) * len(MESH_AXES)), MESH_AXES)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        tr = fam.make_trainer(fam.config(config["model"]), mesh,
                              traffic["optimizer"])
        state = jax.eval_shape(tr._state_init, jax.random.PRNGKey(0))
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            state, tr.state_shardings)
        batch = {"tokens": jax.ShapeDtypeStruct(
            (traffic["batch"], traffic["seq"] + 1), jnp.int32,
            sharding=tr.batch_sharding)}
        with mesh:
            return tr._jit_step.lower(state, batch).compile().as_text()


def _part(line):
    """The part of an instruction's ``op_name``, as the trace's reader
    (``cells/parts.py``) finds it."""
    from cells import parts

    name = re.search(r'op_name="([^"]*)"', line)
    return parts.scopes_of(name.group(1))[1] if name else None


def test_the_train_step_holds_the_parents_flash_calls(train_step):
    """Equal widths: the forward and the backward kernel of the scanned
    layer, with the operands the parent's step handed them (PR 43 made the
    values' width a parameter of the forward; at ``dv == d`` nothing of
    the call may move)."""
    q, kv, lse = "bf16[128,4096,128]", "bf16[32,4096,128]", \
        "f32[128,1,4096]"
    assert sorted(_mosaic_calls(train_step), key=len) == [
        [q, kv, kv], [q, kv, kv, q, lse, lse]]


def test_the_train_step_holds_three_products_of_the_heads_shape(train_step):
    """Logits (a sequence chunk at a time, in the rule's loop), dX and dW:
    three, each bf16 x bf16 into float32 as the parent's compiled step ran
    them (its cotangent a float32 operand at the default precision: one
    bf16 pass, PERF.md section 6, PR 38).  The parent held four: XLA ran
    the forward product again in the backward (``fusion.284.remat``)
    rather than keep 2.1 GB of logits."""
    products = [line for line in train_step.splitlines()
                if " convolution(" in line and _part(line) == "head"]
    assert len(products) == 3, products
    results = sorted(re.search(r"= (\w+\[[\d,]*\])", p).group(1)
                     for p in products)
    assert results == ["f32[4,128,32768]", "f32[4,4096,4096]",
                       "f32[4096,32768,1]"], results
    twins = [line.split(" = ")[0].strip() for line in train_step.splitlines()
             if re.match(r"\s*(?:ROOT )?%[\w.\-]*\.remat[\w.\-]* = ", line)
             and _part(line) in ("head", "loss")]
    assert twins == []


def test_the_train_step_forms_no_float32_logits_of_the_whole_batch(
        train_step):
    """No ``[tokens, vocab]`` float32 array in any computation: a chunk's
    ``f32[4,128,32768]`` (64 MiB) is the largest, and what the backward
    reads is the cotangent in the products' operand type."""
    for shape in ("f32[4,4096,32768]", "f32[16384,32768]",
                  "f32[4,4096,1,32768]"):
        assert shape not in train_step, shape
    assert "f32[4,128,32768]" in train_step
    assert "bf16[4,4096,32768]" in train_step


def test_every_instruction_of_the_rule_is_head_or_loss(train_step):
    """What a trace will time inside the rule's loop (fusions, the product)
    carries ``head`` or ``loss``, so ``step_head_loss_ms.train`` covers it
    and ``(unscoped)`` does not grow (the two backward products are held
    to ``head`` by the test of the three products)."""
    bodies = dict(re.findall(
        r"^%([\w.\-]+) [^\n]*\{\n(.*?)^\}", train_step, re.S | re.M))
    timed = [[line for line in bodies[b].splitlines()
              if re.search(r" (fusion|convolution)\(", line)]
             for b in re.findall(r"body=%([\w.\-]+)", train_step)]
    (timed,) = [t for t in timed if any(_part(line) == "head" for line in t)]
    assert len(timed) >= 3
    assert [line[:160] for line in timed
            if _part(line) not in ("head", "loss")] == []


@pytest.mark.parametrize("tp", [1, 2], ids=["one-device", "tp2"])
@pytest.mark.parametrize("s", [1, 64])
def test_the_layers_q_k_v_are_the_plain_products_bit_for_bit(s, tp):
    """On the CPU (no libtpu): what ``_layer_with_cache`` hands its
    attention equals ``apply_rope((y @ w).reshape(b, s, heads, hd))`` bit
    for bit, at decode and prefill row counts, on one device and with the
    weights sharded over heads on a 2-way ``tp`` mesh of host devices;
    float32 parameters cast to the bf16 of the products."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models.paged_generation import _layer_with_cache
    from ray_tpu.models.llama import LlamaConfig, _layer_init
    from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies

    cfg = LlamaConfig.tiny(dtype=jnp.bfloat16, head_dim=16)
    hd, b = cfg.resolved_head_dim, 3
    lp = _layer_init(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (b, s, cfg.hidden_size),
                          cfg.dtype)
    cos, sin = rope_frequencies(hd, 128, cfg.rope_theta)
    positions = 5 + jnp.arange(b)[:, None] + jnp.arange(s)[None]
    if tp > 1:
        mesh = Mesh(np.array(jax.devices("cpu")[:tp]), ("tp",))
        over_heads = NamedSharding(mesh, P(None, "tp"))
        lp = {k: jax.device_put(w, over_heads) if k in ("wq", "wk", "wv")
              else w for k, w in lp.items()}

    @jax.jit
    def layer(x, lp):
        seen = []

        def attend(q, k, v):
            seen.extend((q, k, v))
            return jnp.zeros_like(q)

        _layer_with_cache(x, lp, None, cfg=cfg, cos=cos, sin=sin, mask=None,
                          positions=positions, attend=attend)
        return seen

    @jax.jit
    def plain(x, lp):
        y = rms_norm(x, lp["attn_norm"])
        q, k, v = ((y @ lp[w].astype(cfg.dtype)).reshape(b, s, heads, hd)
                   for w, heads in (("wq", cfg.num_heads),
                                    ("wk", cfg.num_kv_heads),
                                    ("wv", cfg.num_kv_heads)))
        return [apply_rope(q, cos, sin, positions),
                apply_rope(k, cos, sin, positions), v]

    for got, want in zip(layer(x, lp), plain(x, lp)):
        assert got.dtype == want.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)))
