"""The name scopes inside the device programs (``tracing.scope``).

A scope is metadata of the traced instructions: ``op_name`` reads
``jit(<unknown>)/engine.decode/attn.proj/dot_general``.  These tests hold
every served model's programs and the train step to the two-level
vocabulary of ``docs/observability.md`` (the program, then the part), on
the CPU, from the programs as the engine and the trainer really build them;
``tests/test_flash_compile_v5e.py`` holds the programs compiled for the v5e
to it, after XLA's fusion.  What the engine says about a prefill's size
(``engine.admit``'s ``prefilled_tokens``, two counters) is here too: the
readers in ``cells/parts.py`` divide a prefill's device time by it.
"""

import os
import re
import subprocess

import jax
import jax.numpy as jnp
import pytest

from ray_tpu._private import tracing
from ray_tpu.llm import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = ("tiny", "longcat_flash_tiny", "smallthinker_tiny")
# what carries a model's arithmetic: every product and every call of a kernel
# (none on the CPU) must name its program and its part
HEAVY = re.compile(r"stablehlo\.dot_general|chlo\.ragged_dot|"
                   r"stablehlo\.convolution|tpu_custom_call|"
                   r" dot\(| convolution\(")
LOC = re.compile(r'^(#loc\d+) = loc\("([^"]*)"', re.M)


def _hlo(jitted, args, compiled=False):
    """(the module's name, [(operation, op_name)]) of ``jitted`` lowered
    for ``args``: the program as JAX hands it to XLA, every operation with
    the name stack it was traced under.  ``compiled``: after XLA:CPU's
    passes instead, where a called function's operations (a layer under
    ``checkpoint`` and ``scan``) carry their whole path; some of XLA:CPU's
    passes drop a product's metadata, so the served programs are read
    before them (XLA:TPU's are held to the vocabulary in
    ``tests/test_flash_compile_v5e.py``)."""
    lowered = jitted.lower(*args)
    if compiled:
        text = lowered.compile().as_text()
        ops = [(line.strip()[:120], m.group(1))
               for line in text.splitlines()
               if (m := re.search(r'op_name="([^"]*)"', line))]
        return re.match(r"HloModule (\w+)", text).group(1), ops
    text = lowered.as_text(debug_info=True)
    names = dict(LOC.findall(text))
    ops = [(line.strip().split(" : ")[0][:120], names[m.group(1)])
           for line in text.splitlines()
           if (m := re.search(r" loc\((#loc\d+)\)$", line))
           and m.group(1) in names]
    return re.search(r"module @(\w+)", text).group(1), ops


def _scopes_of(op_name):
    """(program, part) of an ``op_name``: the first word that is a program
    scope, the innermost that is a part."""
    words = re.split(r"[/()]", op_name)  # ``transpose(jvp(head))/...``
    parts = [w for w in words if w in tracing.PART_SCOPES]
    return (next((w for w in words if w in tracing.PROGRAM_SCOPES), None),
            parts[-1] if parts else None)


def _heavy(ops):
    """[(operation, program, part)] of the products and kernel calls."""
    return [(op, *_scopes_of(name)) for op, name in ops if HEAVY.search(op)]


@pytest.fixture(scope="module")
def programs():
    """{(model, program): ``_hlo``'s pair}, each model's engine built once: the
    engine's own jitted programs, lowered for the arguments the engine
    handed them while it generated."""
    from ray_tpu.llm import LLMEngine
    from ray_tpu.models import served

    out = {}

    def record(eng, attr, key):
        jitted = getattr(eng, attr)

        def recording(*args):
            if key not in out:
                out[key] = _hlo(jitted, jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                                   jnp.result_type(a)),
                    args))
            return jitted(*args)
        setattr(eng, attr, recording)

    sp = SamplingParams(temperature=0.0, max_tokens=6)
    for name in MODELS:
        eng = LLMEngine(served.preset(name), batch_slots=2, max_len=64,
                        decode_window=4)
        record(eng, "_decode1", (name, "engine.decode"))
        record(eng, "_prefill", (name, "engine.prefill"))
        eng.generate([[3, 4, 5, 6, 7], [8, 9]], sp)
    return out


@pytest.mark.parametrize("model,program", [
    (m, p) for m in MODELS for p in ("engine.decode", "engine.prefill")])
def test_every_product_of_a_served_program_names_its_program_and_part(
        programs, model, program):
    module, ops = programs[(model, program)]
    assert module == "jit__unknown"  # the name the trace readers, the
    # traffic files and the compile cache know the module by
    heavy = _heavy(ops)
    assert len(heavy) >= 8, heavy
    wrong = [h for h in heavy if h[1] != program or h[2] is None]
    assert not wrong, wrong[:5]
    parts = {h[2] for h in heavy}
    assert {"attn.proj", "attn.core", "attn.out", "head"} <= parts, parts
    if model == "tiny":
        assert "ffn" in parts and "experts" not in parts
    else:
        assert {"router", "experts"} <= parts, parts
    assert ("ffn" in parts) == (model != "smallthinker_tiny")


def test_every_product_of_the_train_step_names_its_program_and_part():
    """Forward, replay and backward alike: JAX adds ``jvp(...)``,
    ``transpose(jvp(...))`` and ``checkpoint/rematted_computation`` itself,
    around and between our two levels."""
    from jax.sharding import Mesh

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.models.training import make_llama_trainer

    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32, remat=True,
                           remat_policy="save_attn")
    mesh = Mesh(jax.devices("cpu")[:1], ("dp",))
    trainer = make_llama_trainer(cfg, mesh)
    state = jax.eval_shape(trainer._state_init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 17), jnp.int32)}
    with mesh:
        module, ops = _hlo(trainer._jit_step, (state, batch), compiled=True)
    assert module == "jit__train_step"
    heavy = _heavy(ops)
    wrong = [h for h in heavy if h[1] != "train.step" or h[2] is None]
    assert len(heavy) >= 20 and not wrong, wrong[:5]
    assert {"attn.proj", "attn.core", "attn.out", "ffn", "head"} \
        <= {h[2] for h in heavy}
    names = [name for _, name in ops]
    for part in ("embed", "loss", "optimizer"):  # no product of their own
        assert any(_scopes_of(n) == ("train.step", part) for n in names)
    replayed = [n for n in names if "rematted_computation" in n]
    assert replayed and all(_scopes_of(n)[1] for n in replayed
                            if n.endswith("dot_general"))


@pytest.mark.parametrize("word", [
    "moe_route", "moe_experts", "mla", "dense_ffn", "attention",
    "named_scope"])
def test_one_vocabulary_and_one_way_to_open_a_scope(word):
    """The five scope names of PR 27 and PR 31 were renamed into the
    vocabulary, and ``jax.named_scope`` is reached through
    ``tracing.scope`` alone."""
    pattern = (r"named_scope" if word == "named_scope"
               else rf"scope\(\s*[\"']{word}[\"']")
    hits = subprocess.run(
        ["grep", "-rnE", "--include=*.py", pattern,
         os.path.join(ROOT, "ray_tpu")],
        capture_output=True, text=True).stdout.splitlines()
    allowed = os.path.join("ray_tpu", "_private", "tracing.py")
    assert [h for h in hits if word != "named_scope"
            or allowed not in h] == []


def test_the_readers_vocabulary_is_the_programs():
    """``cells/parts.py`` keeps its own copy (the benchmark's files also
    run against a parent commit that has no scopes)."""
    import sys

    sys.path.insert(0, ROOT)
    try:
        from cells import parts
    finally:
        sys.path.remove(ROOT)
    assert tuple(parts.PROGRAMS) == tracing.PROGRAM_SCOPES
    assert set(parts.PARTS) == set(tracing.PART_SCOPES)


# ------------------------------------------------- what an admission says

@pytest.mark.parametrize("case", ["full", "prefix_hit", "chunked"])
def test_admit_says_how_many_tokens_it_prefilled(case):
    """``engine.admit``'s ``prefilled_tokens`` is the true length of the
    suffix the prefill program ran (``bucket`` what it was padded to), and
    ``prefill_tokens`` / ``prefill_padded_tokens`` of
    ``stats()["counters"]`` are the sums of the two."""
    from ray_tpu.llm import LLMEngine
    from ray_tpu.llm.engine import _bucket
    from ray_tpu.models import served

    eng = LLMEngine(served.preset("tiny"), batch_slots=2, max_len=128,
                    decode_window=4, block_size=16,
                    prefill_chunk=32 if case == "chunked" else 0)
    seen = []
    admit_stats = eng._admit_stats

    def recording(i, res):
        stats = admit_stats(i, res)
        seen.append(stats)
        return stats
    eng._admit_stats = recording
    sp = SamplingParams(temperature=0.0, max_tokens=3)
    prompt = list(range(3, 3 + 45))
    if case == "prefix_hit":
        eng.generate([prompt], sp)  # its two full blocks are now cached
        before = dict(eng.stats()["counters"])
        seen.clear()
        eng.generate([prompt[:40] + [99, 98, 97]], sp)
        want = [43 - 32]
    else:
        before = dict(eng.stats()["counters"])
        eng.generate([prompt], sp)
        # chunks of 32 block-aligned tokens, never the whole rest
        want = [32, 13] if case == "chunked" else [45]
    ran = [s for s in seen if s.get("prefilled_tokens")]
    assert [s["prefilled_tokens"] for s in ran] == want, seen
    assert [s["kind"] for s in ran] == ["partial"] * (len(want) - 1) \
        + ["full"]
    assert all(s["bucket"] == _bucket(s["prefilled_tokens"], eng.max_len)
               and s["prompt_tokens"] >= s["prefilled_tokens"]
               for s in ran)
    if case == "prefix_hit":
        assert ran[-1]["cached_tokens"] == 32
    after = eng.stats()["counters"]
    assert after["prefill_tokens"] - before["prefill_tokens"] == sum(want)
    assert after["prefill_padded_tokens"] \
        - before["prefill_padded_tokens"] == sum(s["bucket"] for s in ran)
