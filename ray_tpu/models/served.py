"""What ``LLMEngine`` needs of a model, and which models supply it.

The engine is handed a configuration and finds its programs from the
configuration's type: ``served_model(cfg)`` returns the model's
:class:`ServedModel`.  A model is served when it supplies

* ``init(key, cfg) -> params``;
* ``init_pool(cfg, num_blocks, block_size, kv_dtype) -> pool``: a dict of
  arrays whose axis 1 is the block (block 0 the scratch block); it raises
  for a ``kv_dtype`` it does not store;
* ``prefill_suffix(params, tokens[1, S], length, start_pos, *prefix,
  prefix_len, dst_blocks[S], dst_offsets[S], pool, cfg=) -> (logits[1, V],
  pool, *counters)``: a prompt's suffix against its cached prefix
  (``paged_generation.prefill_suffix`` is the contract's text);
* ``gather_prefix(pool, blocks[P], cfg) -> prefix``: the pair of arrays
  ``prefill_suffix`` takes for a list of cached blocks;
* ``decode_sample(params, token[B], cur_len[B], block_tables[B, MB], pool,
  key, temps[B], cfg=, attn=) -> (token, cur_len, key, pool, *counters)``:
  one step with on-device sampling, everything the next step needs a
  device array;
* ``decode_attention_path(pool, mesh=) -> str``: which attention the
  decode step runs, from what it sees;

and optionally ``param_specs`` (an engine with a mesh), ``handoff``
(``export_kv`` / ``adopt_prefilled`` know its pool), ``layer_types``
(below), ``expert_path(cfg, tokens) -> str`` (a model with an expert
layer: which path ``ops/experts.py:expert_path`` gives a program of
``tokens`` rows, the engine's ``experts`` label),
``prefill_attention_path(cfg, bucket, prefix) -> str`` (a model whose
prefill has more than one attention path: which one the program of a
``bucket``-token suffix after ``prefix`` padded cached positions runs, the
engine's ``attention`` label) and ``counters``: the names of the small
integer sums its prefill and decode programs return beside the rest (one
int32 vector, fetched with the window's tokens, summed into
``LLMEngine.stats()["counters"]``).
What a model leaves out the engine refuses by name at construction or at
the call, never by a wrong answer.

A model whose layers do not all keep the same positions supplies
``layer_types(cfg) -> {type: {"layers": n, "window": None | int}}``, the
type without a window first.  The engine then builds a pool, a block
manager and a block table for each type (a type's pool holds blocks of
positions, as key/value pages or as latent rows: whichever arm of the paged
decode kernel the model's ``decode_attention_path`` names reads it; or
records, below): ``init_pool`` takes
``num_blocks`` by type and returns ``{type: pool}``, ``decode_sample``
takes ``block_tables`` by type, ``prefill_suffix`` its ``dst_blocks`` by
type, and a window type's blocks that lie wholly behind the window go back
to its pool while a request decodes (``docs/llm_serving.md``).  Such a
model takes no prefix hits and no ``prefill_chunk`` yet.  Two further keys
of a type, both optional:

* ``"readers": m``: the layers that READ the type's pool in a decode step,
  where they are not the ``n`` that store it (a cache that later layers
  attend over with queries of their own);
* ``"state": True``: a **state type**.  Axis 1 of its pool is a RECORD of
  fixed size, one a request (record 0 the scratch one), not a block of
  positions: it is allocated at admission, given back at retire and at
  preemption, never grown and never published to the prefix cache.  Its
  table is ``[B, 1]`` (a slot's record; 0 for an idle slot),
  ``prefill_suffix``'s ``dst_blocks[type]`` is ``[1]`` (the request's
  record, which the prefill writes from a zero state), and ``num_blocks``
  of the type is records + 1.

``presets`` are the configurations the model offers by name
(``build_llm_deployment({"model": "<name>"})`` resolves one through
``preset`` without the driver importing ``jax``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ServedModel:
    name: str
    init: Callable
    init_pool: Callable
    prefill_suffix: Callable
    gather_prefix: Callable
    decode_sample: Callable
    decode_attention_path: Callable
    presets: Dict[str, Callable[[], Any]]
    # the presets at toy widths, written in the float32 the CPU tests run
    test_presets: Tuple[str, ...] = ()
    param_specs: Optional[Callable] = None
    handoff: bool = False
    layer_types: Optional[Callable] = None
    expert_path: Optional[Callable] = None
    prefill_attention_path: Optional[Callable] = None
    counters: Tuple[str, ...] = ()

    def require(self, what: str, have: bool) -> None:
        if not have:
            raise NotImplementedError(
                f"{self.name} does not supply {what} yet (see "
                f"docs/llm_serving.md, 'Which options each model supports')")


def _expert_path(cfg, tokens: int) -> str:
    """``ServedModel.expert_path`` of both expert models: their expert
    layers hand ``held_experts_ffn`` ``tokens`` rows of ``hidden_size`` in
    ``cfg.dtype`` and experts ``expert_ffn_dim`` wide."""
    from ray_tpu.ops import experts

    return experts.expert_path(
        tokens, cfg.experts_per_token, cfg.hidden_size, cfg.expert_ffn_dim,
        cfg.dtype)


def _llama() -> ServedModel:
    from ray_tpu.models import paged_generation as pg
    from ray_tpu.models.llama import (LlamaConfig, llama_init,
                                      llama_param_specs)

    return ServedModel(
        name="llama", init=llama_init, init_pool=pg.init_kv_pool,
        prefill_suffix=pg.prefill_suffix,
        gather_prefix=lambda pool, blocks, cfg: pg.gather_prefix(pool,
                                                                 blocks),
        decode_sample=pg.paged_decode_sample,
        # looked up at the call, as the engine did before it read a model
        decode_attention_path=lambda pool, **seen: pg.decode_attention_path(
            pool, **seen),
        presets={n: getattr(LlamaConfig, n) for n in (
            "tiny", "llama2_7b", "llama2_13b", "llama3_8b")},
        test_presets=("tiny",),
        param_specs=llama_param_specs, handoff=True)


def _longcat() -> ServedModel:
    from ray_tpu.models import longcat as lc
    from ray_tpu.models.paged_generation import decode_attention_path

    return ServedModel(
        name="longcat_flash", init=lc.longcat_init,
        init_pool=lc.init_latent_pool,
        prefill_suffix=lc.latent_prefill_suffix,
        gather_prefix=lc.gather_latent_prefix,
        decode_sample=lc.latent_decode_sample,
        decode_attention_path=decode_attention_path,
        presets={"longcat_flash_tiny": lc.LongcatConfig.tiny,
                 "longcat_flash": lc.LongcatConfig},
        test_presets=("longcat_flash_tiny",),
        expert_path=_expert_path,
        prefill_attention_path=lambda cfg, bucket, prefix:
            lc.prefill_attention_path(bucket, prefix),
        counters=("moe_pairs_held", "moe_experts_hit", "moe_zero_picks"))


def _deepseek_v3() -> ServedModel:
    from ray_tpu.models import deepseek_v3 as ds
    from ray_tpu.models.paged_generation import decode_attention_path

    return ServedModel(
        name="deepseek_v3", init=ds.deepseek_v3_init,
        init_pool=ds.init_latent_pool, prefill_suffix=ds.prefill_suffix,
        gather_prefix=ds.gather_latent_prefix,
        decode_sample=ds.decode_sample,
        decode_attention_path=decode_attention_path,
        presets={"deepseek_v3_tiny": ds.DeepseekV3Config.tiny,
                 "gigachat3_1_702b": ds.DeepseekV3Config},
        test_presets=("deepseek_v3_tiny",),
        expert_path=_expert_path,
        prefill_attention_path=lambda cfg, bucket, prefix:
            ds.prefill_attention_path(bucket, prefix),
        # LongCat's three (0 picks of a zero-compute expert: it has none)
        # and the tokens whose kept groups reach this chip's experts
        counters=ds.COUNTERS)


def _smallthinker() -> ServedModel:
    from ray_tpu.models import smallthinker as st

    return ServedModel(
        name="smallthinker", init=st.smallthinker_init,
        init_pool=st.init_pools, prefill_suffix=st.prefill_suffix,
        gather_prefix=st.gather_prefix, decode_sample=st.decode_sample,
        decode_attention_path=st.decode_attention_path,
        presets={"smallthinker_tiny": st.SmallThinkerConfig.tiny,
                 "smallthinker_21b": st.SmallThinkerConfig},
        test_presets=("smallthinker_tiny",),
        layer_types=st.layer_types, expert_path=_expert_path,
        # LongCat's names, so that one reader reads both; this model has no
        # zero-compute expert and reports 0 picks of one
        counters=("moe_pairs_held", "moe_experts_hit", "moe_zero_picks"))


def _phi4flash() -> ServedModel:
    from ray_tpu.models import phi4flash as pf

    return ServedModel(
        name="phi4flash", init=pf.phi4flash_init, init_pool=pf.init_pools,
        prefill_suffix=pf.prefill_suffix, gather_prefix=pf.gather_prefix,
        decode_sample=pf.decode_sample,
        decode_attention_path=pf.decode_attention_path,
        presets={"phi4flash_tiny": pf.Phi4FlashConfig.tiny,
                 "phi4_mini_flash": pf.Phi4FlashConfig},
        test_presets=("phi4flash_tiny",), layer_types=pf.layer_types,
        # positions the self-decoder and the cross-decoder computed: a
        # prefill's read "prefill_positions" / "prefill_cross_positions"
        counters=("positions", "cross_positions"))


def _gigachat3_5() -> ServedModel:
    from ray_tpu.models import gigachat3_5 as gc

    return ServedModel(
        name="gigachat3_5", init=gc.gigachat3_5_init,
        init_pool=gc.init_pools, prefill_suffix=gc.prefill_suffix,
        gather_prefix=gc.gather_prefix, decode_sample=gc.decode_sample,
        decode_attention_path=gc.decode_attention_path,
        presets={"gigachat3_5_tiny": gc.GigaChat35Config.tiny,
                 "gigachat3_5_432b": gc.GigaChat35Config},
        test_presets=("gigachat3_5_tiny",), layer_types=gc.layer_types,
        expert_path=_expert_path,
        prefill_attention_path=lambda cfg, bucket, prefix:
            gc.prefill_attention_path(bucket, prefix),
        counters=gc.COUNTERS)  # LongCat's three


# configuration class -> the function that builds its ServedModel: a model's
# modules are imported when it is first asked for, so that a process which
# serves one model loads one model
_MODELS = {"LlamaConfig": _llama, "LongcatConfig": _longcat,
           "DeepseekV3Config": _deepseek_v3,
           "SmallThinkerConfig": _smallthinker,
           "Phi4FlashConfig": _phi4flash,
           "GigaChat35Config": _gigachat3_5}


@functools.lru_cache(maxsize=None)
def _load(cls_name: str) -> ServedModel:
    return _MODELS[cls_name]()


def served_model(cfg) -> ServedModel:
    """The programs of the model whose configuration ``cfg`` is."""
    name = type(cfg).__name__
    if name not in _MODELS:
        raise TypeError(
            f"LLMEngine cannot serve a {name}: no served model is "
            f"registered for it (ray_tpu/models/served.py; served: "
            f"{sorted(_MODELS)})")
    return _load(name)


def preset(name: str, *, serve_max_len: Optional[int] = None):
    """A configuration by its preset's name, from whichever served model
    offers it.  With ``serve_max_len`` it comes as a deployment runs it:
    weights in bf16 and a rotary table as long as the engine's ``max_len``
    (0: the preset's own), except the model's ``test_presets``, which stay
    as written."""
    offered = []
    for owner in _MODELS:
        model = _load(owner)
        if name in model.presets:
            cfg = model.presets[name]()
            if serve_max_len is not None and name not in model.test_presets:
                import jax.numpy as jnp

                cfg = dataclasses.replace(
                    cfg, param_dtype=jnp.bfloat16,
                    max_seq_len=serve_max_len or cfg.max_seq_len)
            return cfg
        offered += sorted(model.presets)
    raise ValueError(f"unknown model preset {name!r}; offered: {offered}")
