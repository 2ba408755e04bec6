"""LLMEngine: continuous batching over a paged block-table KV cache.

Reference capability: ``ray.llm`` delegates the engine to vLLM
(``_internal/serve/deployments/llm/vllm/vllm_engine.py`` — continuous
batching, paged attention, automatic prefix caching,
``vllm_models.py:123-127``).  TPU-native redesign:

* **Paged KV**: one global block pool ``[L, num_blocks, bs, KVH, hd]``
  (``models/paged_generation.py``); each request holds a block table.
  Capacity is measured in blocks, not worst-case slots×max_len, so many
  short requests fit where the dense layout held few.
* **Prefix caching**: full prompt blocks are registered under a rolling
  hash chain ``key = (parent_key, block_tokens)``; a new request walks its
  prompt's chain and reuses every hit — the shared-system-prompt pattern
  prefills only the suffix.  Refcounted blocks; refcount-0 blocks retire
  into an LRU that retains contents for future hits and is evicted last.
* **Static shapes**: decode is ONE compiled program (B slots × MB blocks,
  gather + mask); prefill compiles per power-of-2 (suffix, prefix) bucket.
  Host-side scheduling (admit/preempt/retire) is plain numpy — no jit
  boundary crossings beyond the two program calls.
* **Layer types**: a model whose layers do not all keep the same positions
  (window and full attention mixed: ``ServedModel.layer_types``) gets a
  pool, a block manager and a block table PER TYPE.  A window type's table
  keeps logical indexing; an entry wholly behind the window is the scratch
  block and its block is back in that pool's free list, while the request
  decodes (``_release_behind_window``) and from admission on for a prompt
  longer than the window.  A model without the field is one type, and
  everything below is what it was.
* **State types**: a layer type may keep, for a request, ONE record of
  fixed size instead of blocks of positions (recurrent state:
  ``ServedModel.layer_types``' ``"state"``).  Its pool's axis 1 is the
  record (record 0 the scratch one, which idle slots update), its table
  one entry a slot; a record is allocated at admission, given back at
  retire and at preemption, never grown, never published to the prefix
  cache.  A type also says how many layers READ its pool (``"readers"``)
  where they are not the layers that store it.
* **Runs**: a slot is handed its blocks in aligned runs of adjacent
  blocks, as many as the decode kernel moves with one copy descriptor
  (``ops/pallas/paged_attention.py:page_run``, a rule of the pool's page):
  at admission and whenever its table crosses a multiple of the run, where
  the pool has a whole run, else one block at a time, as ever.  Up to a
  run less one block a slot and pool are so held ahead of the sequence,
  listed and released like any other block (``_BlockManager``).
* **Preemption**: out of blocks mid-decode → the youngest request is
  rolled back to the queue (its tokens re-prefill later), matching vLLM's
  recompute-preemption policy.

The default tokenizer is the in-repo byte-level BPE (``llm/bpe.py``);
``ByteTokenizer`` remains as the dependency-free fallback.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
import functools
import itertools
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu._private import accelerators, tracing
from ray_tpu.models.paged_generation import SamplingParams


class ByteTokenizer:
    """Dependency-free fallback tokenizer: UTF-8 bytes shifted by the
    special ids (0=pad, 1=bos, 2=eos, byte b -> 3+b)."""

    pad_id, bos_id, eos_id = 0, 1, 2
    vocab_size = 259

    def encode(self, text: str) -> List[int]:
        return [self.bos_id] + [3 + b for b in text.encode("utf-8")]

    def decode(self, ids) -> str:
        data = bytes(i - 3 for i in ids if i >= 3)
        return data.decode("utf-8", "replace")


def default_tokenizer(model_vocab_size: Optional[int] = None):
    """The in-repo BPE vocab when it fits the model's embedding table,
    byte fallback otherwise (ids past ``cfg.vocab_size`` would be clamped
    silently by the gather — garbage generation, no error)."""
    try:
        from ray_tpu.llm.bpe import BPETokenizer

        tok = BPETokenizer()
        if (model_vocab_size is None
                or tok.vocab_size <= model_vocab_size):
            return tok
    except Exception:  # noqa: BLE001 - vocab artifact missing
        pass
    return ByteTokenizer()


@dataclasses.dataclass
class Request:
    request_id: int
    prompt_tokens: List[int]
    sampling: SamplingParams
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    blocks: List[int] = dataclasses.field(default_factory=list)
    # a model with several layer types: the blocks held in each pool after
    # the first (``LLMEngine._more``'s order), by logical index as
    # ``blocks`` is, 0 where a window type's block was never allocated or
    # has been given back
    more_blocks: List[List[int]] = dataclasses.field(default_factory=list)
    # chunked prefill: blocks already written for this prompt, refs HELD
    # (pinned against ORDINARY pool pressure; forfeited by
    # _yield_chunk_pins when a starved queue head needs the pool);
    # transferred into ``blocks`` at final admission
    chunk_blocks: List[int] = dataclasses.field(default_factory=list)
    # cached prompt hash-chain keys (prompt_tokens are immutable while
    # queued; preemption rewrites them and must clear this)
    chain_keys: Optional[List[Any]] = None
    cached_prefix_len: int = 0  # tokens served from the prefix cache
    # preemption folds generated tokens into prompt_tokens for re-prefill;
    # n_prompt remembers the ORIGINAL prompt length so outputs and the
    # max_tokens budget survive any number of preemptions
    n_prompt: int = -1
    error: Optional[str] = None
    # disaggregated serving: a prefill-only request retires right after
    # its first sampled token, holding its blocks for export (the KV
    # handoff to a decode replica) instead of releasing them
    prefill_only: bool = False
    # tracing (wall clock, like every host span): when the request (last)
    # entered the queue — at submit, and again at a preemption — when it
    # left the queue for a slot, and when its decode began; ``trace_ctx``
    # is the submitter's span context (the ``serve.request`` root under
    # serve), so the per-request spans ``engine.queue_wait`` /
    # ``engine.prefill`` / ``engine.decode`` join the request's trace in
    # ``util.state.timeline()``
    t_queued: float = 0.0
    t_admit: float = 0.0
    t_decode: float = 0.0
    trace_ctx: Optional[tracing.SpanContext] = None
    # recorded, not yet handed to ``on_token``: a request's first token
    # waits here for the tokens of its first window (``LLMEngine._tell``)
    held: List[int] = dataclasses.field(default_factory=list)

    def __post_init__(self):
        if self.n_prompt < 0:
            self.n_prompt = len(self.prompt_tokens)

    @property
    def num_generated(self) -> int:
        return (len(self.prompt_tokens) - self.n_prompt
                + len(self.out_tokens))

    @property
    def all_out_tokens(self) -> List[int]:
        return self.prompt_tokens[self.n_prompt:] + self.out_tokens


@dataclasses.dataclass
class GenerationOutput:
    request_id: int
    prompt_tokens: List[int]
    token_ids: List[int]
    text: Optional[str] = None
    error: Optional[str] = None  # per-request failure (e.g. pool too small)


class _BlockManager:
    """Host-side pool bookkeeping: free blocks, refcounts, prefix hash chain
    with LRU retention of refcount-0 blocks (vLLM's automatic prefix
    caching, evict-last).

    **Runs.**  The pool is cut into aligned runs of ``run`` adjacent blocks
    (``ops/pallas/paged_attention.py:page_run``: as many pages as one copy
    descriptor of the decode kernel should move), and a block is
    *available* when it is free or LRU-retained.  A run whose blocks are
    all available is *whole* and waits in ``whole_free`` (no block of it
    cached) or ``whole_cached``, in the order they became whole;
    ``alloc_run`` hands one out entire, free ones first, so that ``run``
    consecutive entries of a slot's table name adjacent blocks and the
    kernel copies them with one descriptor.  ``alloc`` (one block) takes
    from broken runs first (``free``, FIFO), then breaks a whole free run,
    and evicts the oldest cached block last, as ever.  A run is whole again
    the moment its last block comes back, in whatever order; the run that
    holds the scratch block 0, and a tail shorter than ``run``, never are.
    With ``run`` 1 every available block is a whole run and the order of
    allocation is what it was: free blocks first in, first out, then the
    cache's oldest."""

    def __init__(self, num_blocks: int, run: int = 1):
        # block 0 is the jit-side scratch block (padding / masked writes)
        self.num_blocks = num_blocks
        self.run = run
        self.refs: Dict[int, int] = {}
        self.key_of: Dict[int, Any] = {}
        self.by_key: Dict[Any, int] = {}
        self.lru: "collections.OrderedDict[Any, int]" = \
            collections.OrderedDict()
        # dicts as ordered sets: the free blocks of broken runs, and the
        # first blocks of whole runs
        self.free: Dict[int, None] = {}
        self.whole_free: Dict[int, None] = {}
        self.whole_cached: Dict[int, None] = {}
        # available blocks, a run and in all
        self._avail = [0] * -(-num_blocks // run)
        self._n_avail = 0
        self.stats = {"prefix_hits": 0, "prefix_blocks_reused": 0,
                      "evictions": 0, "preemptions": 0,
                      "adopted_blocks": 0}
        for bid in range(1, num_blocks):
            self._gain(bid)

    def available(self) -> int:
        return self._n_avail

    def free_count(self) -> int:
        return self._n_avail - len(self.lru)

    def _gain(self, bid: int) -> None:
        """``bid`` became available (in ``key_of``: LRU-retained, else
        free): into ``free``, or its run is whole again."""
        first = bid - bid % self.run
        self._n_avail += 1
        self._avail[bid // self.run] += 1
        if self._avail[bid // self.run] < self.run:
            if bid not in self.key_of:
                self.free[bid] = None
            return
        cached = False
        for b in range(first, first + self.run):
            if b in self.key_of:
                cached = True
            else:
                self.free.pop(b, None)
        (self.whole_cached if cached else self.whole_free)[first] = None

    def _lose(self, bid: int) -> None:
        """``bid``, available, is being taken alone: out of ``free``, or
        its whole run breaks and the run's other free blocks go there."""
        first = bid - bid % self.run
        if self._avail[bid // self.run] == self.run:
            self.whole_free.pop(first, None)
            self.whole_cached.pop(first, None)
            for b in range(first, first + self.run):
                if b != bid and b not in self.key_of:
                    self.free[b] = None
        else:
            self.free.pop(bid, None)
        self._avail[bid // self.run] -= 1
        self._n_avail -= 1

    def _evict(self, bid: int) -> None:
        """Forget the cached contents of ``bid`` (no-op for a free one)."""
        key = self.key_of.pop(bid, None)
        if key is not None:
            self.by_key.pop(key, None)
            self.lru.pop(key, None)
            self.stats["evictions"] += 1

    def alloc(self) -> Optional[int]:
        if self.free:
            bid = next(iter(self.free))
        elif self.whole_free:
            bid = next(iter(self.whole_free))  # the run breaks
        elif self.lru:
            bid = next(iter(self.lru.values()))  # evict oldest cached
        else:
            return None
        self._lose(bid)
        self._evict(bid)
        self.refs[bid] = 1
        return bid

    def alloc_run(self) -> Optional[int]:
        """The first block of a whole run, all ``run`` of them now held
        once; None when no run is whole."""
        for whole in (self.whole_free, self.whole_cached):
            if whole:
                first = next(iter(whole))
                del whole[first]
                break
        else:
            return None
        self._avail[first // self.run] = 0
        self._n_avail -= self.run
        for bid in range(first, first + self.run):
            self._evict(bid)
            self.refs[bid] = 1
        return first

    def take(self, at: int, room: int) -> Optional[List[int]]:
        """The blocks for a table's entries from ``at`` on: a whole run
        where ``at`` starts a group of ``run`` entries, ``room`` entries
        may be filled and a run is whole, else one block.  None: the pool
        is exhausted."""
        if self.run > 1 and at % self.run == 0 and room >= self.run:
            first = self.alloc_run()
            if first is not None:
                return list(range(first, first + self.run))
        bid = self.alloc()
        return None if bid is None else [bid]

    def acquire_cached(self, key) -> Optional[int]:
        """Prefix hit: bump the block's refcount (reviving it from the
        LRU if it was retired)."""
        bid = self.by_key.get(key)
        if bid is None:
            return None
        if key in self.lru:
            self._lose(bid)
            del self.lru[key]
            self.refs[bid] = 0
        self.refs[bid] = self.refs.get(bid, 0) + 1
        self.stats["prefix_blocks_reused"] += 1
        return bid

    def register(self, bid: int, key) -> None:
        """Publish a freshly-filled full block under its chain key."""
        if key in self.by_key:
            return  # a concurrent identical prefill won the race; keep ours unpublished
        self.key_of[bid] = key
        self.by_key[key] = bid

    def release(self, bid: int) -> None:
        n = self.refs.get(bid, 0) - 1
        if n > 0:
            self.refs[bid] = n
            return
        self.refs.pop(bid, None)
        key = self.key_of.get(bid)
        if key is not None:
            self.lru[key] = bid  # retain contents for future prefix hits
        self._gain(bid)

    def adopt(self, keys: List[Any]) -> Optional[List[int]]:
        """Allocate one block per entry of ``keys`` for KV grafted from a
        remote pool (disaggregated prefill handoff) and register the
        non-None chain keys so the shipped prefix serves future local
        prefix hits too.  All-or-nothing: on pool pressure every block
        allocated so far is UNPUBLISHED and freed (a plain ``release``
        would LRU-retain the registered keys pointing at never-written
        blocks — a prefix-cache poisoning: the fallback re-prefill would
        then "hit" garbage KV) and None is returned."""
        bids: List[int] = []
        for key in keys:
            bid = self.alloc()
            if bid is None:
                self.unpublish_free(bids)
                return None
            if key is not None:
                self.register(bid, key)
            bids.append(bid)
        self.stats["adopted_blocks"] += len(bids)
        return bids

    def unpublish_free(self, bids: List[int]) -> None:
        """Roll back adopted blocks whose KV was never (fully) written:
        unpublish any registered chain keys and return the blocks to the
        free list.  A plain ``release`` would LRU-retain the keys
        pointing at garbage blocks — prefix-cache poisoning."""
        for b in bids:
            k = self.key_of.pop(b, None)
            if k is not None and self.by_key.get(k) == b:
                del self.by_key[k]
            self.refs.pop(b, None)
            self._gain(b)

    def assert_integrity(self) -> None:
        """Audit invariant (tests): every non-scratch block is in exactly
        one of {free, LRU-retained, refcounted}, and every refcount is
        positive — the abort/preemption paths must never leak or
        double-free a block.  A free block is in ``free`` or in a whole
        run; a run is in ``whole_free`` / ``whole_cached`` exactly while
        all its blocks are available."""
        lru = set(self.lru.values())
        refed = set(self.refs)
        assert all(n > 0 for n in self.refs.values()), \
            f"non-positive refcounts: {self.refs}"
        assert not (lru & refed), f"blocks both cached and held: {lru & refed}"
        assert lru == set(self.key_of) - refed, \
            f"cached blocks out of the LRU: {lru ^ (set(self.key_of) - refed)}"
        free = set(self.free)
        assert not (free & lru), f"blocks both free and cached: {free & lru}"
        assert not (free & refed), f"blocks both free and held: {free & refed}"
        for first in range(0, self.num_blocks, self.run):
            run = set(range(max(first, 1),
                            min(first + self.run, self.num_blocks)))
            avail = run - refed
            assert self._avail[first // self.run] == len(avail), \
                f"run {first}: {self._avail[first // self.run]} != {avail}"
            whole = (first in self.whole_free) + (first in self.whole_cached)
            if len(avail) < self.run:
                assert not whole, f"broken run {first} listed as whole"
                assert avail - lru <= free, \
                    f"block accounting leak: missing {avail - lru - free}"
            else:
                assert whole == 1 and (first in self.whole_cached) == bool(
                    avail & lru), f"whole run {first} listed {whole} times"
                assert not (avail & free), \
                    f"blocks of whole run {first} in free: {avail & free}"
        assert free <= set(range(1, self.num_blocks)), \
            f"phantom {free - set(range(1, self.num_blocks))}"
        assert self._n_avail == sum(self._avail)


@dataclasses.dataclass
class _LayerPool:
    """One layer type's share of the cache on the host: its block manager,
    its ``[B, MB]`` table, and the window behind which its blocks are dead
    (None: never).  ``readers``: the layers that read the pool in a decode
    step (``layers`` store it).  A ``state`` type's manager hands out
    records, one a request, and its table is ``[B, 1]``."""
    name: str
    layers: int
    window: Optional[int]
    blocks: _BlockManager
    tables: np.ndarray
    readers: int
    state: bool = False
    pages: int = 1  # of a compute block of the decode kernel

    def held(self) -> int:
        return self.blocks.num_blocks - 1 - self.blocks.available()


@dataclasses.dataclass
class _Window:
    """A dispatched decode window whose tokens the host has not fetched."""
    out_d: Any  # [k, B (+ the model's counters)] int32, on its way over
    k: int
    active: List[int]  # the slots it decodes for


class LLMEngine:
    def __init__(self, cfg, params=None, *,
                 tokenizer: Optional[Any] = None, batch_slots: int = 8,
                 max_len: Optional[int] = None, block_size: int = 16,
                 num_blocks: Optional[int] = None, decode_window: int = 16,
                 seed: int = 0, mesh=None,
                 kv_cache_dtype: Optional[str] = None,
                 prefill_chunk: int = 0):
        import jax
        import jax.numpy as jnp

        # set-up's parts in seconds (stats()["startup"]), each an
        # ``engine.startup.<part>`` span under the ``engine.startup`` of
        # whoever builds the engine (serving.py:_build_engine)
        self.startup: Dict[str, float] = {}
        entered = time.time()
        from ray_tpu.models.served import served_model

        # the programs come from the model whose configuration this is
        # (models/served.py): init, pool, suffix prefill, decode-and-sample,
        # prefix gather, and optionally parameter specs
        self.model = model = served_model(cfg)
        model.require("parameter specs for an engine with a mesh",
                      mesh is None or model.param_specs is not None)
        # {type: {"layers", "window"}} of a model whose layers do not all
        # keep the same positions; None: one type, one pool, one table
        types = model.layer_types(cfg) if model.layer_types else None
        model.require("chunked prefill (prefill_chunk) for a model with "
                      "several layer types: it resumes through prefix "
                      "hits, which such a model does not take",
                      not prefill_chunk or types is None)
        self.cfg = cfg
        self.mesh = mesh
        self.tokenizer = tokenizer or default_tokenizer(cfg.vocab_size)
        self.B = batch_slots
        self.max_len = max_len or cfg.max_seq_len
        self.bs = block_size
        self.MB = -(-self.max_len // block_size)  # blocks per sequence
        # multi-step window: K on-device steps chained without any host
        # sync (token/position/key stay device-resident), sampled tokens
        # fetched ONCE per window — the host↔device round trip
        # amortizes over window*slots tokens
        self.K = max(1, decode_window)
        # default pool = dense-equivalent capacity (callers can shrink it:
        # prefix sharing + short requests usually need far less)
        if types is None:
            self.num_blocks = num_blocks or (self.B * self.MB + 1)
        else:  # an int is every type's of positions; a window type never
            # holds more than its window, a state type a record a slot
            asked = num_blocks if isinstance(num_blocks, dict) else {
                t: num_blocks for t, spec in types.items()
                if not spec.get("state")}
            most = {t: 1 if spec.get("state") else min(
                self.MB, self._window_blocks(spec["window"]))
                for t, spec in types.items()}
            self.num_blocks = {t: asked.get(t) or self.B * most[t] + 1
                               for t in types}
        with self._startup_phase("backend"):
            jax.devices()  # the process's first touch of its backend
            accelerators.record_chip_acquire()
        # until the weights are dispatched, not until they are there: the
        # wait for them overlaps the pool and the first program's lowering
        with self._startup_phase("weights"):
            if params is None:
                params = model.init(jax.random.PRNGKey(seed), cfg)
        self.params = params
        self._key = jax.random.PRNGKey(seed + 1)

        # kv_cache_dtype="int8": ~half the pool HBM -> ~2x the slots fit
        # next to the weights (vLLM kv_cache_dtype, TPU-native)
        self.kv_cache_dtype = kv_cache_dtype
        with self._startup_phase("pool"):
            self.pool = model.init_pool(cfg, self.num_blocks, self.bs,
                                        kv_dtype=kv_cache_dtype)
            if mesh is not None:
                self._shard_over_mesh(mesh)
            # the host's side of each pool; ``blocks`` / ``_tables`` are the
            # first's (the only one's, for a model of one layer type), whose
            # blocks a request holds in ``Request.blocks``; ``_more`` the rest
            one = {"kv": {"layers": cfg.num_layers, "window": None}}
            sizes = self.num_blocks if types else {"kv": self.num_blocks}
            # (pages a compute block of the decode kernel, pages a copy of
            # it): the blocks a slot is handed at once; a state type's records
            # come one by one
            plan = {t: (1, 1) if spec.get("state") else self._page_plan(
                self.pool[t] if types else self.pool)
                for t, spec in (types or one).items()}
            pools = [_LayerPool(t, spec["layers"], spec["window"],
                                _BlockManager(sizes[t], plan[t][1]),
                                np.zeros((self.B, 1 if spec.get("state")
                                          else self.MB), np.int32),
                                spec.get("readers", spec["layers"]),
                                bool(spec.get("state")), plan[t][0])
                     for t, spec in (types or one).items()]
            if pools[0].window is not None or pools[0].state:
                raise ValueError(f"{model.name}: the first layer type keeps "
                                 f"every position (a slot is live where its "
                                 f"first table holds a block)")
            self._pools, self._more = pools, pools[1:]
            # the pools of positions: what "blocks" counts in stats()
            self._kv_pools = [p for p in pools if not p.state]
            self._by_type = types is not None  # programs take tables by type
            self.blocks = pools[0].blocks
        # the decode step's attention, read off what is in front of us:
        # "paged_kernel" / "latent_kernel" (live blocks read in place) for
        # a dense / latent pool on one TPU device, "gather" for the rest
        self.attn = model.decode_attention_path(self.pool, mesh=mesh)
        # the decode step's expert layer, read off the same way
        # (ops/experts.py:expert_path at this engine's B rows a step):
        # "decode_kernel" / "grouped"; None for a model without experts
        self.experts = self._expert_path(self.B)
        # each of the three model programs under its name scope (the
        # outer level of docs/observability.md's vocabulary; the parts are
        # the models'): still a jitted partial, so the module keeps the
        # name the trace readers and the compile cache know it by
        self._decode1 = jax.jit(
            functools.partial(tracing.scoped, "engine.decode",
                              model.decode_sample, cfg=cfg, attn=self.attn),
            donate_argnums=(4,))
        self._stack = jax.jit(lambda *ts: jnp.stack(ts))
        from ray_tpu.models.paged_generation import sample_token_batch

        self._prefill = jax.jit(
            functools.partial(tracing.scoped, "engine.prefill",
                              model.prefill_suffix, cfg=cfg),
            donate_argnums=(9,))  # the pool (avoid a full second copy)
        self._sample = jax.jit(sample_token_batch)
        # the small integer sums a model's programs return beside the rest
        # (ServedModel.counters): a window's ride to the host in the
        # tokens' array, a prefill's with the first tokens
        # (decode windows under the model's names beside "decode_steps",
        # prefills under "prefill_<name>" beside "prefill_calls")
        self.counters = dict.fromkeys(
            (*model.counters, "decode_steps",
             *(f"prefill_{n}" for n in model.counters), "prefill_calls")
            if model.counters else (), 0)
        if any(p.window for p in pools):
            self.counters["window_blocks_released"] = 0
        # every model: windows dispatched, and how many of them were
        # launched before the emit of the window before (step()); prompt
        # tokens prefilled (suffixes' true lengths) and the buckets they
        # were padded to (the bucket rule's waste without a trace)
        self.counters.update(decode_windows=0, windows_carried=0,
                             prefill_tokens=0, prefill_padded_tokens=0)
        if self.experts:  # of decode_windows: those the kernel ran
            self.counters["expert_kernel_windows"] = 0
        # a model whose prefill has more than one attention path
        # (ServedModel.prefill_attention_path): prefill programs run, by
        # path, and the last one's (engine.admit's "attention")
        self.prefill_attention = collections.Counter() \
            if model.prefill_attention_path else None
        self._prefill_path = None
        self._stack_counted = jax.jit(lambda toks, counts: jnp.concatenate(
            [jnp.stack(toks), jnp.stack(counts)], axis=1))
        # chunked prefill (vLLM's feature TPU-natively): cap the prompt
        # tokens prefilled per step so a long prompt can't stall the
        # decode batch.  Chunks are block-aligned; their full blocks
        # register in the prefix cache and the NEXT admission resumes
        # from them via ordinary prefix hits — no separate partial state.
        self.prefill_chunk = max(0, int(prefill_chunk))
        if self.prefill_chunk and self.prefill_chunk < self.bs:
            raise ValueError(
                f"prefill_chunk ({prefill_chunk}) must be >= block_size "
                f"({self.bs})")
        self.prefill_stats = {"chunks": 0}
        self._ids = itertools.count()
        self._queue: "collections.deque[Request]" = collections.deque()
        self._failed: List[Request] = []  # per-request admission failures
        # disaggregated serving state: finished prefill-only requests
        # holding their blocks for export, adopted (already-prefilled)
        # requests waiting for a free decode slot, and the jitted
        # gather/scatter programs that move block-aligned pool slices
        self._exports: Dict[int, Request] = {}
        self._adopt_queue: "collections.deque[Request]" = collections.deque()
        self._gather_blocks = None
        self._scatter_blocks = None
        self.handoff_stats = {"exported": 0, "adopted": 0,
                              "adopt_failures": 0}
        self._slots: List[Optional[Request]] = [None] * self.B
        self._cur_len = np.zeros(self.B, np.int32)
        self._next_token = np.zeros(self.B, np.int32)
        self._tables = pools[0].tables
        # device mirrors of the decode inputs, kept resident across
        # windows: re-uploading unchanged tables/temps/token/cur costs a
        # dispatch each through a high-latency link.  A changed table row
        # (admit/retire/preempt/block growth) sets the flag; a request
        # entering a slot also drops (tok_d, cur_d)
        # (_refresh_device_mirrors).
        self._dev: Optional[Tuple[Any, Any]] = None  # (tok_d, cur_d)
        self._tables_d = None
        self._temps_d = None
        self._dev_dirty = True
        # the window dispatched by the last step() and not yet fetched
        self._inflight: Optional[_Window] = None
        # per-token hook for streaming consumers: on_token(request_id, tok)
        self.on_token: Optional[Any] = None
        # a model's prefill counters, summed on the device (one shape,
        # however many prefills) until the first tokens' fetch takes them
        self._prefill_sum: Optional[Any] = None
        self._prefill_calls = 0
        # this constructor's wall: the parts above and the little between
        self.startup["total_s"] = round(time.time() - entered, 3)

    @contextlib.contextmanager
    def _startup_phase(self, part: str):
        """One part of ``__init__``: an ``engine.startup.<part>`` span, and
        its seconds in ``stats()["startup"]``."""
        with tracing.span(f"engine.startup.{part}", kind="startup"):
            t0 = time.time()
            yield
            self.startup[f"{part}_s"] = round(time.time() - t0, 3)

    def _page_plan(self, pool: Dict[str, Any]) -> Tuple[int, int]:
        """``(pages, run)`` of one type's pool of positions as the decode
        kernel cuts it: the pages of a compute block and those of one copy
        descriptor, read off the same page as the kernel reads them (the
        pool's first leaf's, ``k`` or ``kv``: ``[L, NB, *page]``) and this
        engine's table width.  ``run`` is what a slot is handed at once, so
        that the kernel finds its runs."""
        from ray_tpu.ops.pallas.paged_attention import (page_run,
                                                        pipeline_plan)

        leaf = next(iter(pool.values()))
        rows, width = int(np.prod(leaf.shape[2:-1])), leaf.shape[-1]
        size = leaf.dtype.itemsize
        pages, _ = pipeline_plan(rows, width, size, 1, self.MB)
        return pages, page_run(rows, width, size, pages)

    def _shard_over_mesh(self, mesh) -> None:
        """Tensor-parallel inference: place params by the logical-axis rule
        table (heads/kv_heads/mlp/vocab over the mesh's ``tp`` axis) and
        the KV pool over its kv-head dim; every existing jitted program
        (prefill, decode window, sampling) then compiles SPMD with XLA
        inserting the collectives.  Reference capability:
        ``ray.llm`` tensor_parallel_size → vLLM worker bundles
        (``vllm_models.py:123-127``); here TP is a sharding spec, not a
        process group.
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu.parallel.sharding import (TP_INFERENCE_RULES,
                                               shard_tree)

        tp = int(mesh.shape.get("tp", 1))
        if tp > 1:
            if self.cfg.num_kv_heads % tp:
                raise ValueError(
                    f"num_kv_heads={self.cfg.num_kv_heads} not divisible "
                    f"by tp={tp}")
            if self.cfg.num_heads % tp:
                raise ValueError(
                    f"num_heads={self.cfg.num_heads} not divisible by "
                    f"tp={tp}")
        self.params = shard_tree(self.params,
                                 self.model.param_specs(self.cfg),
                                 mesh, TP_INFERENCE_RULES)
        # pool tensors: [L, blocks, bs, KVH, hd] (values) and
        # [L, blocks, bs, KVH] (int8 scales) — KVH is axis 3 in both.
        # With a pp axis the layer dim shards alongside the stacked
        # per-layer weights (each stage holds its own layers' KV).
        pp = ("pp" if "pp" in mesh.axis_names
              and int(mesh.shape.get("pp", 1)) > 1 else None)
        if pp and self.cfg.num_layers % int(mesh.shape["pp"]):
            raise ValueError(
                f"num_layers={self.cfg.num_layers} not divisible by "
                f"pp={int(mesh.shape['pp'])}")
        kv_s = NamedSharding(mesh, P(pp, None, None, "tp"))
        self.pool = {k: jax.device_put(v, kv_s)
                     for k, v in self.pool.items()}

    # -- request API --------------------------------------------------------

    def submit(self, prompt, sampling: Optional[SamplingParams] = None, *,
               prefill_only: bool = False) -> int:
        self.model.require("the prefill/decode handoff (prefill_only)",
                           not prefill_only or self.model.handoff)
        if isinstance(prompt, str):
            prompt = self.tokenizer.encode(prompt)
        sampling = sampling or SamplingParams(
            stop_token_id=getattr(self.tokenizer, "eos_id", None))
        req = Request(next(self._ids), list(prompt), sampling,
                      prefill_only=prefill_only, t_queued=time.time(),
                      trace_ctx=tracing.current())
        if len(req.prompt_tokens) >= self.max_len:
            raise ValueError(
                f"prompt of {len(req.prompt_tokens)} tokens >= engine "
                f"max_len {self.max_len}")
        self._queue.append(req)
        return req.request_id

    def abort(self, request_id: int) -> bool:
        """Drop a request whose client stopped waiting (budget expired or
        the stream consumer disconnected).  A still-queued request is
        removed outright — releasing any chunk-prefill block pins it
        accumulated — and an active one is marked ``done`` so the next
        ``step()`` retires it through the ordinary path (slot cleared,
        blocks released, device mirrors refreshed); what a window in
        flight decodes for it meanwhile is dropped at that step's commit,
        and its blocks stay held until then.  Returns ``True``
        when the request was found; the retire still emits its (partial)
        ``GenerationOutput``, which an abandoning caller simply drops.

        NOT thread-safe against a concurrent ``step()`` — callers hold
        the same lock that serializes the engine loop."""
        for qi, req in enumerate(self._queue):
            if req.request_id == request_id:
                del self._queue[qi]
                for bid in req.chunk_blocks:
                    self.blocks.release(bid)
                req.chunk_blocks = []
                return True
        for qi, req in enumerate(self._adopt_queue):
            if req.request_id == request_id:
                del self._adopt_queue[qi]
                for bid in req.blocks:
                    self.blocks.release(bid)
                req.blocks = []
                return True
        if request_id in self._exports:
            self.release_export(request_id)
            return True
        for i in range(self.B):
            req = self._slots[i]
            if req is not None and req.request_id == request_id:
                req.done = True
                req.prefill_only = False  # abandoned: nothing to export
                return True
        return False

    def has_unfinished(self) -> bool:
        return (bool(self._queue) or bool(self._failed)
                or bool(self._adopt_queue)
                or any(s is not None for s in self._slots))

    def free_slot_count(self) -> int:
        return sum(1 for s in self._slots if s is None)

    def queued_count(self) -> int:
        return len(self._queue)

    # -- continuous-batching step ------------------------------------------

    def step(self) -> List[GenerationOutput]:
        """Admit queued requests into free slots (prefix-cached prefill),
        take ONE decode window's tokens for all active slots, retire
        finished.

        A one-deep pipeline of decode windows: the window whose tokens
        this step hands out was as a rule launched by the step before,
        and this step launches the next one from the device-resident
        ``(tok_d, cur_d, key_d, pool)`` as soon as it has fetched and
        committed this one, before any per-token Python.  The order:

        1. ``engine.admit`` / ``engine.first_tokens``: prefills are
           dispatched BEHIND a window in flight (``self.pool`` is chained,
           the device orders them), the first tokens sync as ever;
        2. the window: the one in flight, or with none in flight one
           launched now (``engine.prepare_window`` +
           ``engine.dispatch_window[carried=0]``);
        3. ``engine.fetch_window`` (the one host sync), then
           ``engine.emit[part=commit]``: each slot's column cut at its
           stop token / ``max_tokens`` / ``max_len`` and the host state
           (``out_tokens``, ``_cur_len``, ``_next_token``, ``done``)
           advanced in bulk, a slot at a time and not a token at a time;
        4. the carried window: ``engine.prepare_window`` +
           ``engine.dispatch_window[carried=1]`` for the slots that go on
           (this step's admissions among them); the step returns with it
           running;
        5. ``engine.emit[part=notify]`` (``on_token``) and
           ``engine.retire`` (``tokenizer.decode``, request spans), under
           the carried window, as is all the caller does between steps.
           A request's first token goes to ``on_token`` with the tokens
           of its first window, one step after its admission (``_tell``).

        A window is not carried when no slot goes on (so an engine whose
        ``has_unfinished()`` is false has nothing in flight).  The
        un-carried order (``_carries``) is the same code with the launch
        left to the next step.
        ``stats()["counters"]["windows_carried"]`` of ``["decode_windows"]``
        says how often it engages.

        A slot that is done or empty is not in a launch: its row of the
        device's block tables is zero (``_refresh_device_mirrors``), so
        the decode program takes it for an empty slot: it reads nothing
        for it and its write goes to the scratch block, never into blocks
        that ``retire`` releases after the launch or that the prefix
        cache holds.

        The step is tiled by ``tracing.annotate`` phases (``engine.admit``,
        ``engine.first_tokens``, ``engine.prepare_window``,
        ``engine.dispatch_window``,
        ``engine.fetch_window``, ``engine.emit``, ``engine.retire``) under
        one ``engine.step``: in a profiler trace they say what the host
        was doing in every idle gap of the device.  One annotation per
        phase, never one per token."""
        used = self.B - self.free_slot_count()
        with tracing.annotate("engine.step", queued=len(self._queue),
                              slots_used=used):
            return self._step_phases()

    def _step_phases(self) -> List[GenerationOutput]:
        import jax
        import jax.numpy as jnp

        # 0. place adopted (already-prefilled, KV grafted) requests into
        # free slots: no prefill dispatch at all — the shipped blocks ARE
        # the cache, the first token came with the handoff
        if self._adopt_queue:
            with tracing.annotate("engine.admit", kind="adopt") as ann:
                ann.set_metadata(n=self._place_adopted())

        # 1. admit — prefills dispatch back-to-back (behind the window in
        # flight, if any); the first tokens of ALL admissions are sampled
        # and fetched in ONE host sync
        admitted: List[Tuple[int, Any]] = []
        budget = self.prefill_chunk or None  # tokens of prefill this step
        for i in range(self.B):
            if self._slots[i] is None and self._queue:
                with tracing.annotate("engine.admit") as ann:
                    res = self._admit(i, budget)
                    ann.set_metadata(**self._admit_stats(i, res))
                if res is None:
                    break  # out of blocks: stop admitting this step
                kind, payload, used = res
                if budget is not None:
                    budget -= used
                if kind == "partial":
                    break  # head request still prefilling; slot stays free
                admitted.append((i, payload))
                if budget is not None and budget <= 0:
                    break  # spent: further walks would only defer
        if admitted:
            with tracing.annotate("engine.first_tokens",
                                  n=len(admitted)) as ann:
                self._key, k = jax.random.split(self._key)
                # padded to a power of two (the last row again, greedy):
                # a number of admissions not seen before would lower three
                # small programs here, on this thread, with the device idle
                rows = [d for _, d in admitted]
                rows += rows[-1:] * (_bucket(len(rows), self.B) - len(rows))
                lg = self._stack(*rows)[:, 0]
                temps = np.zeros(len(rows), np.float32)
                temps[:len(admitted)] = [
                    self._slots[i].sampling.temperature for i, _ in admitted]
                first = self._sample(lg, k, jnp.asarray(temps))
                if self._prefill_sum is not None:
                    first, counts = jax.device_get(
                        (first, self._prefill_sum))
                    ann.set_metadata(**{
                        "prefill_" + name: v for name, v in self._count(
                            counts, self._prefill_calls,
                            "prefill_").items()})
                    self._prefill_sum, self._prefill_calls = None, 0
                first = np.asarray(first)[:len(admitted)]
                now = time.time()
                for (i, _), tok in zip(admitted, first):
                    req = self._slots[i]
                    self._request_span(
                        "engine.prefill", req, req.t_admit, now,
                        prompt_tokens=len(req.prompt_tokens),
                        cached_tokens=req.cached_prefix_len)
                    req.t_decode = now
                    self._record_token(i, req, int(tok))
                # the step's device temporaries die inside the phase that
                # made them, not at the frame's exit under no phase:
                # freeing a device buffer gives up the interpreter lock,
                # and what the thread then waits belongs to the phase
                del admitted, rows, lg, k, first

        # 2. the window this step hands out: the one the step before left
        # in flight, or one launched now
        window, self._inflight = self._inflight, None
        if window is None:
            window = self._launch_window(carried=False)
        if window is not None:
            # 3. its tokens, and the host state they leave
            taken = self._fetch_and_commit(window)
            # 4. the next window, before any per-token work on this one
            if self._carries():
                self._inflight = self._launch_window(carried=True)
            # 5. what the launch did not wait for
            self._notify(taken)

        with tracing.annotate("engine.retire") as ann:
            out = self._retire()
            ann.set_metadata(n=len(out))
        return out

    def _carries(self) -> bool:
        """Whether a window is launched ahead of the emit of the one
        before.  Always: this is the seam a test patches
        (``eng._carries = lambda: False``) to get the un-carried order,
        the reference order the carried one's tokens are held to."""
        return True

    def _launch_window(self, carried: bool) -> Optional[_Window]:
        """Dispatch one decode window (``window_k`` chained steps and the
        small program that stacks their tokens) for the slots that are not
        done, and return it un-fetched; ``None`` when no slot goes on.
        ``carried``: the window before this one has been committed but not
        emitted."""
        active = [i for i in range(self.B) if self._slots[i] is not None
                  and not self._slots[i].done]
        if not active:
            return None
        with tracing.annotate("engine.prepare_window"):
            # ensure every active slot has blocks for the whole window;
            # preempt the youngest request if the pool is exhausted
            active = self._ensure_decode_blocks(active)
            if not active:
                return None
            # adaptive window: never decode past what the
            # longest-running active request can still accept
            window_k = self._window_arity(active)
            live = self._live_tokens(active)  # host mirror
            self._refresh_device_mirrors()
            tok_d, cur_d = self._dev
            key_d = self._key
        with tracing.annotate(
                "engine.dispatch_window", k=window_k, active=len(active),
                attn=self.attn, carried=int(carried), **live,
                **({"experts": self.experts} if self.experts else {})):
            toks, counts = [], []
            for _ in range(window_k):  # device-chained: no host sync
                tok_d, cur_d, key_d, self.pool, *extra = self._decode1(
                    self.params, tok_d, cur_d, self._tables_d,
                    self.pool, key_d, self._temps_d)
                toks.append(tok_d)
                counts += extra
            self._key = key_d
            self._dev = (tok_d, cur_d)
            # stacked behind the last step at once (a model's counters
            # ride in the same array) and sent on its way to the host, so
            # that the fetch has nothing left to dispatch
            out_d = self._stack_counted(toks, counts) if counts \
                else self._stack(*toks)
            out_d.copy_to_host_async()
            self.counters["decode_windows"] += 1
            self.counters["windows_carried"] += int(carried)
            if self.experts == "decode_kernel":
                self.counters["expert_kernel_windows"] += 1
            del toks, counts, extra  # freed inside the phase, as above
        return _Window(out_d, window_k, active)

    def _expert_path(self, tokens: int) -> Optional[str]:
        """The expert layer's path in a program of ``tokens`` rows, as the
        model's ``expert_path`` reads it off the shapes; None for a model
        without an expert layer."""
        path = self.model.expert_path
        return path(self.cfg, tokens) if path else None

    def _live_tokens(self, active: List[int]) -> Dict[str, int]:
        """``engine.dispatch_window``'s ``live_tokens``: the cached
        positions a decode step attends over, all slots together.  With
        several layer types it is the mean over the layers that READ a
        pool of positions, a window type counting at most its window (so
        that positions x the bytes a position takes in those layers is
        what a step reads), beside each type's own count and the blocks
        each pool holds; for a state type the records a step reads and
        writes (one an active slot) and the records held."""
        lens = self._cur_len[active]
        pages = self._live_pages(active)
        if not self._by_type:
            return {"live_tokens": int(lens.sum()), **pages}
        by = {p.name: int((np.minimum(lens, p.window) if p.window
                           else lens).sum()) for p in self._kv_pools}
        layers = sum(p.readers for p in self._kv_pools)
        out = {"live_tokens": round(sum(
            by[p.name] * p.readers for p in self._kv_pools) / layers)}
        for p in self._kv_pools:
            out[f"live_tokens_{p.name}"] = by[p.name]
            out[f"blocks_held_{p.name}"] = p.held()
        for p in self._pools:
            if p.state:  # a record a slot is read and written, whole
                out[f"live_tokens_{p.name}"] = len(active)
                out[f"{p.name}_records_held"] = p.held()
        return {**out, **pages}

    def _live_pages(self, active: List[int]) -> Dict[str, int]:
        """``engine.dispatch_window``'s ``pages_live`` and
        ``pages_in_runs``: the pages the decode kernel copies a layer for
        the active slots (those a slot's length reaches and its type's
        window has not left), summed over the pools of positions, and
        those of them it copies ``run`` a descriptor (``run_flags``: the
        groups live whole in a compute block whose every such group names
        adjacent blocks).  What the allocator's runs are worth to the
        kernel."""
        from ray_tpu.ops.pallas.paged_attention import run_flags

        lens = self._cur_len[active]
        live = runs = 0
        for p in self._kv_pools:
            in_runs, whole, pages = run_flags(
                p.tables[active], lens, run=p.blocks.run, pages=p.pages,
                block_size=self.bs, window=p.window, xp=np)
            live += int(pages.sum())
            runs += p.blocks.run * int((whole & in_runs[..., None]).sum())
        return {"pages_live": live, "pages_in_runs": runs}

    def _window_blocks(self, window: Optional[int]) -> int:
        """The most blocks a slot holds in a pool whose blocks die behind
        ``window``: the window, a decode window ahead of it, and the block
        that either end cuts."""
        if window is None:
            return self.MB
        return (window + self.K - 2) // self.bs + 2

    def _most_held(self, p: _LayerPool, worst: int) -> int:
        """The most blocks (records) of pool ``p`` one sequence of up to
        ``worst`` blocks ever holds."""
        return 1 if p.state else min(worst, self._window_blocks(p.window))

    def _release_behind_window(self, i: int, req: Request) -> None:
        """Give back slot i's blocks that lie wholly behind a layer type's
        window.  The next step writes position ``cur_len`` and sees no
        position before ``cur_len + 1 - window``, and no later step sees
        further back; the window that was in flight has been fetched, and
        the next launch uploads the tables.  The entry becomes the scratch
        block, which the kernel never reads for a position before the
        window."""
        for p, held in zip(self._more, req.more_blocks):
            if p.window is None:
                continue
            dead = min(max(0, int(self._cur_len[i]) + 1 - p.window)
                       // self.bs, len(held))
            first = dead  # the held run ends where the last release did
            while first and held[first - 1]:
                first -= 1
            if first == dead:
                continue
            for b in range(first, dead):
                p.blocks.release(held[b])
                held[b] = 0
            p.tables[i, first:dead] = 0
            self._dev_dirty = True
            self.counters["window_blocks_released"] += dead - first

    def _release_blocks(self, req: Request) -> None:
        """Every block the request holds, in every pool."""
        for bid in req.blocks:
            self.blocks.release(bid)
        req.blocks = []
        for p, held in zip(self._more, req.more_blocks):
            for bid in held:
                if bid:
                    p.blocks.release(bid)
        req.more_blocks = []

    def _clear_tables(self, i: int) -> None:
        for p in self._pools:
            p.tables[i] = 0
        self._dev_dirty = True

    def _fetch_and_commit(self, w: _Window
                          ) -> List[Tuple[Request, List[int]]]:
        """The window's one host sync, then the commit: the host state
        that ``_record_token``, token by token, would leave, advanced a
        slot at a time.  Returns ``[(request, tokens kept)]`` for
        ``_notify``."""
        with tracing.annotate("engine.fetch_window") as ann:
            window = np.asarray(w.out_d)
            if self.model.counters:  # they ride behind the B tokens
                ann.set_metadata(k=w.k, active=len(w.active),
                                 **self._count(
                                     window[:, self.B:].sum(axis=0),
                                     steps=w.k))
            w.out_d = None  # freed inside the phase, as above
        with tracing.annotate("engine.emit", part="commit") as ann:
            taken = []
            for i in w.active:
                req = self._slots[i]
                if req is None or req.done:
                    continue  # aborted while the window ran: drop it all
                sp = req.sampling
                room = min(sp.max_tokens - req.num_generated,
                           self.max_len - 1 - len(req.prompt_tokens)
                           - len(req.out_tokens))
                toks = window[:max(0, min(w.k, room)), i].tolist()
                used = len(toks)  # positions the slot's cache now holds
                if sp.stop_token_id is not None \
                        and sp.stop_token_id in toks:
                    # stopped mid-window: drop the stop token and the tail
                    toks = toks[:toks.index(sp.stop_token_id)]
                    used = len(toks) + 1
                    req.done = True
                elif used >= room:
                    req.done = True
                self._cur_len[i] += used
                if toks:
                    req.out_tokens.extend(toks)
                    self._next_token[i] = toks[-1]
                if self._more and not req.done:
                    self._release_behind_window(i, req)
                taken.append((req, toks))
            ann.set_metadata(tokens=sum(len(t) for _, t in taken))
        return taken

    def _notify(self, taken) -> None:
        """What of a window's emit need not precede the next launch: the
        per-token hook."""
        with tracing.annotate("engine.emit", part="notify"):
            for req, toks in taken:
                self._tell(req, toks)

    def _place_adopted(self) -> int:
        """Move adopted requests from the adopt queue into free slots;
        returns how many were placed."""
        placed = 0
        now = time.time()
        for i in range(self.B):
            if not self._adopt_queue:
                break
            if self._slots[i] is not None:
                continue
            req = self._adopt_queue.popleft()
            self._slots[i] = req
            self._cur_len[i] = len(req.prompt_tokens)
            self._next_token[i] = req.out_tokens[-1] if req.out_tokens \
                else 0
            self._tables[i] = 0
            self._tables[i, :len(req.blocks)] = req.blocks
            self._dev_dirty = True
            self._dev = None  # a request entered: its token, position, temp
            req.t_admit = req.t_decode = now
            self._request_span("engine.queue_wait", req, req.t_queued, now)
            placed += 1
        return placed

    def _retire(self) -> List[GenerationOutput]:
        out = []
        while self._failed:
            req = self._failed.pop()
            out.append(GenerationOutput(
                req.request_id, req.prompt_tokens[:req.n_prompt], [],
                text="", error=req.error))
        now = time.time()
        for i in range(self.B):
            req = self._slots[i]
            if req is not None and req.done:
                toks = req.all_out_tokens
                out.append(GenerationOutput(
                    req.request_id, req.prompt_tokens[:req.n_prompt], toks,
                    text=self.tokenizer.decode(toks)))
                if req.prefill_only and req.blocks:
                    # blocks stay held for export_kv (the KV handoff);
                    # release_export is the abandonment path
                    self._exports[req.request_id] = req
                else:
                    self._release_blocks(req)
                    self._request_span("engine.decode", req, req.t_decode,
                                       now, tokens=len(toks))
                self._slots[i] = None
                self._clear_tables(i)
        return out

    def _admit_stats(self, i: int, res) -> Dict[str, Any]:
        """The ``engine.admit`` annotation's stats, read off what
        ``_admit(i, ...)`` just did: ``kind`` full (the request now in
        slot i), partial (a chunk of the queue head's prompt) or none
        (pool pressure)."""
        if res is None:
            return {"kind": "none"}
        kind, _, prefilled = res
        req = self._slots[i] if kind == "full" else self._queue[0]
        stats = {"kind": kind, "rid": req.request_id,
                 "prompt_tokens": len(req.prompt_tokens),
                 # the suffix's true length (a prefix hit or a chunk
                 # prefills less than the prompt) and what it was padded to
                 "prefilled_tokens": prefilled,
                 "bucket": _bucket(prefilled, self.max_len)
                 if prefilled else 0}
        if self.experts and prefilled:  # the bucket's prefill program's
            stats["experts"] = self._expert_path(stats["bucket"])
        if self._prefill_path and prefilled:
            stats["attention"] = self._prefill_path
        if self._more:  # did a window keep the prefill from K blocks
            stats["window_skips"] = int(any(
                p.window and prefilled > p.window for p in self._more))
        if kind == "full":
            stats["cached_tokens"] = req.cached_prefix_len
            stats["queue_wait_ms"] = round(
                (req.t_admit - req.t_queued) * 1e3, 3)
        return stats

    def _request_span(self, name: str, req: Request, start: float,
                      end: float, **attrs) -> None:
        """One per-request span in the HOST buffer (explicit stamps cannot
        be a profiler annotation), under the submitter's trace."""
        if not tracing.is_enabled():
            return
        ctx = (req.trace_ctx or tracing.current_or_root()).child()
        attrs["request_id"] = req.request_id
        tracing.record_span(name, start, end, ctx, kind="engine",
                            attrs=attrs)

    def generate(self, prompts, sampling: Optional[SamplingParams] = None
                 ) -> List[GenerationOutput]:
        ids = [self.submit(p, sampling) for p in prompts]
        results: Dict[int, GenerationOutput] = {}
        while self.has_unfinished():
            for out in self.step():
                results[out.request_id] = out
        return [results[i] for i in ids]

    # -- disaggregated prefill/decode handoff --------------------------------
    #
    # A prefill replica runs ``submit(..., prefill_only=True)`` requests:
    # the engine prefills the prompt, samples the FIRST token, and parks
    # the finished request in ``_exports`` with its block refs held.
    # ``export_kv`` gathers those block-aligned pool slices into fresh
    # device arrays (never views of the live pool — the alias-gotcha
    # class) and releases the refs; the payload ships to a decode
    # replica whose ``adopt_prefilled`` grafts the blocks + their
    # prefix-cache chain keys into its own pool and resumes the decode
    # loop at full batch occupancy, no re-prefill.

    def export_kv(self, request_id: int) -> Dict[str, Any]:
        """Pop a finished prefill-only request and gather its KV blocks.

        Returns the self-contained handoff payload: prompt/out tokens,
        sampling params, and ``kv`` — a dict of ``[L, n_blocks, bs, ...]``
        device arrays (one per pool tensor, so int8 pools ship their
        scales alongside).  The gather materializes NEW buffers
        (``block_until_ready`` before the refs release), so the shipped
        arrays can never alias pool blocks that a later step overwrites.
        """
        import jax
        import jax.numpy as jnp

        self.model.require("the prefill/decode handoff (export_kv)",
                           self.model.handoff)
        req = self._exports.pop(request_id)
        if self._gather_blocks is None:
            self._gather_blocks = jax.jit(
                lambda pool, ids: {k: v[:, ids] for k, v in pool.items()})
        # pad the id list to its power-of-2 bucket with the scratch
        # block: the gather/scatter programs then compile per BUCKET
        # (O(log MB) compiles), not per distinct block count — an
        # unbucketed gather recompiles a pool-sized program for every
        # new prompt length, inside the engine lock
        # the blocks its prompt and first token fill: none held ahead
        n = min(len(req.blocks), -(-(len(req.prompt_tokens) + 1) // self.bs))
        P = _bucket(n, self.MB + 1)
        ids = np.zeros(P, np.int32)
        ids[:n] = req.blocks[:n]
        kv = self._gather_blocks(self.pool, jnp.asarray(ids))
        jax.block_until_ready(kv)
        for bid in req.blocks:
            self.blocks.release(bid)
        req.blocks = []
        self.handoff_stats["exported"] += 1
        return {
            "request_id": req.request_id,
            "prompt_tokens": list(req.prompt_tokens),
            "n_prompt": req.n_prompt,
            "out_tokens": list(req.out_tokens),
            "sampling": req.sampling,
            "kv_cache_dtype": self.kv_cache_dtype,
            "block_size": self.bs,
            "n_blocks": n,
            "kv": kv,
        }

    def release_export(self, request_id: int) -> bool:
        """Abandonment path: drop a held export (client gone before the
        handoff shipped) and release its block refs."""
        req = self._exports.pop(request_id, None)
        if req is None:
            return False
        for bid in req.blocks:
            self.blocks.release(bid)
        req.blocks = []
        return True

    def adopt_prefilled(self, handoff: Dict[str, Any],
                        sampling: Optional[SamplingParams] = None
                        ) -> Optional[int]:
        """Graft a shipped prefill into this engine: allocate local
        blocks, scatter the shipped KV into the pool, register the full
        prompt blocks' prefix-chain keys (future local prompts hit the
        shipped prefix too), and queue a ready-to-decode request seeded
        with the prefill's first token.  Returns the local request id,
        or None under pool pressure (caller re-prefills the prompt
        through the ordinary path)."""
        import jax.numpy as jnp

        self.model.require("the prefill/decode handoff (adopt_prefilled)",
                           self.model.handoff)
        kv = handoff["kv"]
        if handoff.get("kv_cache_dtype") != self.kv_cache_dtype:
            raise ValueError(
                f"handoff kv_cache_dtype {handoff.get('kv_cache_dtype')!r} "
                f"!= engine {self.kv_cache_dtype!r}")
        if int(handoff.get("block_size", self.bs)) != self.bs:
            raise ValueError(
                f"handoff block_size {handoff.get('block_size')} != "
                f"engine block_size {self.bs}")
        ref = self.pool["k"]
        if set(kv) != set(self.pool) or kv["k"].shape[0] != ref.shape[0] \
                or kv["k"].shape[2:] != ref.shape[2:]:
            raise ValueError(
                f"handoff pool layout {jnp.shape(kv['k'])} incompatible "
                f"with engine pool {ref.shape}")
        prompt = list(handoff["prompt_tokens"])
        n = len(prompt)
        P = int(kv["k"].shape[1])  # bucketed width (scratch-padded)
        n_ship = int(handoff.get("n_blocks", P))
        # a handoff from a LARGER-max_len prefill engine must fail the
        # one request here (caller re-prefills or errors), never crash
        # the engine loop scattering past the [B, MB] table width
        if n_ship > self.MB or n >= self.max_len:
            raise ValueError(
                f"handoff of {n_ship} blocks / {n} prompt tokens exceeds "
                f"this engine's table ({self.MB} blocks, max_len "
                f"{self.max_len}) — prefill and decode pools must share "
                f"max_len/block_size")
        keys = self._prompt_chain_keys(prompt)
        key_list = [keys[b] if b < len(keys) and (b + 1) * self.bs <= n
                    else None for b in range(n_ship)]
        bids = self.blocks.adopt(key_list)
        if bids is None:
            self.handoff_stats["adopt_failures"] += 1
            return None
        if self._scatter_blocks is None:
            import jax

            self._scatter_blocks = jax.jit(
                lambda pool, ids, new: {
                    k: pool[k].at[:, ids].set(new[k]) for k in pool},
                donate_argnums=(0,))
        # pad lanes scatter into the scratch block (its designated role:
        # absorbing masked writes) so one compiled program per bucket
        # serves every handoff width
        dst = np.zeros(P, np.int32)
        dst[:n_ship] = bids
        try:
            self.pool = self._scatter_blocks(self.pool, jnp.asarray(dst),
                                             kv)
        except BaseException:
            # scatter failed AFTER the blocks were allocated+registered
            # (compile OOM, kv tensor rejected inside the program): the
            # never-written blocks must be unpublished, not leaked with
            # chain keys pointing at garbage
            self.blocks.unpublish_free(bids)
            raise
        sp = sampling or handoff.get("sampling") or SamplingParams(
            stop_token_id=getattr(self.tokenizer, "eos_id", None))
        req = Request(next(self._ids), prompt, sp,
                      out_tokens=list(handoff.get("out_tokens", [])),
                      blocks=bids, n_prompt=int(handoff.get("n_prompt", n)),
                      t_queued=time.time(), trace_ctx=tracing.current())
        req.cached_prefix_len = n
        # re-evaluate finish conditions locally: the prefill side's first
        # token may already exhaust the budget (max_tokens=1) or the
        # prompt may sit at the engine's length ceiling
        if not req.out_tokens:
            req.done = True  # stop token hit at the prefill's first sample
        elif (req.num_generated >= sp.max_tokens
              or len(req.prompt_tokens) + len(req.out_tokens)
              >= self.max_len - 1):
            req.done = True
        self._adopt_queue.append(req)
        self.handoff_stats["adopted"] += 1
        return req.request_id

    def stats(self) -> Dict[str, Any]:
        """Engine signals for the serve autoscaler + dashboard ``/api/llm``
        panel: queue depth, slot occupancy, block-pool pressure, prefix /
        handoff counters, and the devices this engine's
        process holds (platform, kind, HBM in use and peak).  Host-side
        bookkeeping and allocator counters only — no device sync."""
        from ray_tpu.util.health import device_memory_stats

        used = sum(1 for s in self._slots if s is not None)
        # excl. the scratch blocks; summed over the layer types' pools of
        # positions, whose blocks differ in bytes (a block x the type's
        # layers); a state type's records are under "pools" alone
        kv = self._kv_pools
        capacity = max(1, sum(p.blocks.num_blocks - 1 for p in kv))
        available = sum(p.blocks.available() for p in kv)
        by_type = {"pools": {p.name: {
            "total": p.blocks.num_blocks - 1,
            "available": p.blocks.available(), "held": p.held()}
            for p in self._pools}} if self._by_type else {}
        return {
            **by_type,
            "queued": len(self._queue),
            "adopt_queued": len(self._adopt_queue),
            "exports_held": len(self._exports),
            "slots_used": used,
            "slots_total": self.B,
            "slot_occupancy": round(used / self.B, 4),
            "blocks_total": capacity,
            "blocks_free": sum(p.blocks.free_count() for p in kv),
            "blocks_cached": sum(len(p.blocks.lru) for p in kv),
            "blocks_available": available,
            "block_pressure": round(1.0 - available / capacity, 4),
            "block_size": self.bs,
            "kv_cache_dtype": self.kv_cache_dtype or "native",
            "attn": self.attn,
            "experts": self.experts,
            **({"prefill_attention": dict(self.prefill_attention)}
               if self.prefill_attention is not None else {}),
            "prefix_cache": dict(self.blocks.stats),
            "prefill_chunks": self.prefill_stats["chunks"],
            "handoff": dict(self.handoff_stats),
            "model": self.model.name,
            "counters": dict(self.counters),
            "devices": device_memory_stats(),
            # where set-up went, and the programs this process has built
            # or loaded since: a sum that rises in steady state is a shape
            # the warm-up did not meet (docs/observability.md)
            "startup": dict(self.startup),
            "builds": tracing.build_counters(),
        }

    # -- admission / prefill ------------------------------------------------

    def _prompt_chain_keys(self, tokens: List[int]) -> List[Any]:
        """The prompt's full blocks' keys in the prefix cache; none for a
        model with several layer types, which takes no prefix hits (a hit
        would need the window type's blocks before it, which are not
        kept), so that nothing of it is ever registered or looked up."""
        keys = []
        if self._by_type:
            return keys
        parent = None
        for b in range(len(tokens) // self.bs):
            parent = (parent, tuple(tokens[b * self.bs:(b + 1) * self.bs]))
            keys.append(parent)
        return keys

    def _admit(self, i: int, budget: Optional[int] = None):
        """Prefill the next queued request into slot i.

        Returns ``("full", logits_device_array, tokens_prefilled)`` when
        the request is admitted (the caller batch-samples all admissions
        with one sync), ``("partial", None, tokens_prefilled)`` when only
        a block-aligned CHUNK of a long prompt was prefilled this step
        (the request stays queued holding refs on its chunk blocks), or
        None when the pool can't hold the suffix (queue left untouched).
        """
        req = self._queue[0]
        toks = req.prompt_tokens
        n = len(toks)
        # prefix walk: resume from this prompt's own pinned chunk blocks,
        # then reuse every further cached block (but always leave >=1
        # token to prefill — its logits seed sampling)
        pinned = list(req.chunk_blocks)
        if req.chain_keys is None:
            req.chain_keys = self._prompt_chain_keys(toks)
        keys = req.chain_keys
        hit_blocks: List[int] = pinned[:]
        for key in keys[len(pinned):]:
            if len(hit_blocks) * self.bs >= n - 1:
                break
            bid = self.blocks.acquire_cached(key)
            if bid is None:
                break
            hit_blocks.append(bid)
        cached_len = len(hit_blocks) * self.bs
        if cached_len > n - 1:  # whole prompt cached: recompute last block
            # only ever an ACQUIRED block: chunk takes are capped at
            # (n-1)//bs blocks, so the pinned prefix can't cross n-1
            for bid in hit_blocks[-1:]:
                self.blocks.release(bid)
            hit_blocks = hit_blocks[:-1]
            cached_len = len(hit_blocks) * self.bs
        suffix = toks[cached_len:]
        need = -(-(n + 1) // self.bs) - len(hit_blocks)  # +1: first decode
        # worst-case footprint from the ORIGINAL prompt + full budget: after
        # a preemption, prompt_tokens already contains generated tokens and
        # the remaining budget shrinks accordingly — double-counting here
        # would spuriously reject a request that admitted fine before
        worst = -(-min(req.n_prompt + req.sampling.max_tokens + 1,
                       self.max_len) // self.bs)
        # a further layer type's blocks: up to the first decode's, from the
        # first one a later step can still see; a state type's one record
        more = [(max(0, n + 1 - p.window) // self.bs if p.window else 0,
                 1 if p.state else need) for p in self._more]
        if any(self._most_held(p, worst) >= p.blocks.num_blocks
               for p in self._pools):
            # even an empty pool could never hold this one sequence: fail
            # THIS request (an admit/preempt livelock otherwise) — never
            # the whole batch; one oversized HTTP request must not kill
            # every other in-flight generation
            self._queue.popleft()
            for bid in hit_blocks:  # includes any pinned chunk blocks
                self.blocks.release(bid)
            req.chunk_blocks = []
            req.done = True
            req.error = (
                f"KV pool ({self.num_blocks} blocks of {self.bs}) cannot "
                f"hold one sequence of up to {worst} blocks; raise "
                f"num_blocks or lower max_tokens")
            self._failed.append(req)
            return self._admit(i, budget) if self._queue else None
        if budget is not None and len(suffix) > budget:
            # long prompt: prefill one block-aligned chunk instead of
            # stalling the decode batch on the whole suffix (checked
            # AFTER the oversized fail-fast so impossible requests never
            # chunk-prefill)
            return self._admit_chunk(i, req, hit_blocks, len(pinned),
                                      cached_len, budget, keys)
        if self.blocks.available() < need or any(
                p.blocks.available() < hi - lo
                for p, (lo, hi) in zip(self._more, more)):
            for bid in hit_blocks[len(pinned):]:
                self.blocks.release(bid)  # pinned chunk progress stays
            if self._yield_chunk_pins():
                # freed capacity is usable NOW — retry instead of
                # wasting a whole engine step (decode path does the same)
                return self._admit(i, budget)
            return None
        if len(hit_blocks) > len(pinned):
            self.blocks.stats["prefix_hits"] += 1

        req.blocks = self._extend(self._pools[0], list(hit_blocks),
                                  len(hit_blocks) + need)
        req.more_blocks = [self._extend(p, [0] * lo, hi)
                           for p, (lo, hi) in zip(self._more, more)]
        req.chunk_blocks = []  # refs transferred into req.blocks
        req.cached_prefix_len = cached_len
        self._queue.popleft()
        self._slots[i] = req
        req.t_admit = time.time()
        self._request_span("engine.queue_wait", req, req.t_queued,
                           req.t_admit)

        logits = self._run_prefill(suffix, cached_len, req.blocks,
                                   hit_blocks, req.more_blocks)
        # register freshly-computed full blocks for future prefix hits
        # (``keys``: one for each full block of the prompt, or none)
        for b in range(len(hit_blocks), len(keys)):
            self.blocks.register(req.blocks[b], keys[b])
        self._cur_len[i] = n
        self._clear_tables(i)
        for p, held in zip(self._pools, [req.blocks] + req.more_blocks):
            p.tables[i, :len(held)] = held
        self._dev = None  # a request entered: its token, position, temp
        # device array; caller batch-samples all admissions in one sync
        return ("full", logits, len(suffix))

    def _extend(self, p: _LayerPool, held: List[int], upto: int,
                ahead: bool = True) -> List[int]:
        """Grow a request's list of blocks in pool ``p`` to ``upto``
        entries, from a pool known to hold as many: in whole runs from each
        multiple of the pool's run on (``_BlockManager.take``), the last of
        which may reach past ``upto`` where ``ahead`` allows it: blocks
        held ahead, in the table and the list like any other, which no
        program reads before the sequence grows into them."""
        while len(held) < upto:
            held += p.blocks.take(len(held), (p.tables.shape[1] if ahead
                                              else upto) - len(held))
        return held

    def _yield_chunk_pins(self, include_head: bool = False):
        """Break the pinned-chunk livelock: when an allocation stalls on
        pool pressure while a queued prompt pins chunk progress, one
        victim forfeits its pins — the registered blocks retire into
        the LRU (contents may still re-hit; under real pressure they
        evict and that chunk recomputes), so the pool can drain again.
        Admission calls exclude the queue head (the head is the one
        asking); the DECODE-pressure path passes include_head=True, a
        chunk recompute being far cheaper than recompute-preempting a
        live request.  Returns True when a victim forfeited pins."""
        start = 0 if include_head else 1
        for other in list(self._queue)[start:]:
            if other.chunk_blocks:
                for bid in other.chunk_blocks:
                    self.blocks.release(bid)
                other.chunk_blocks = []
                return True
        return False

    def _run_prefill(self, suffix: List[int], cached_len: int,
                     blocks: List[int], hit_blocks: List[int],
                     more_blocks: List[List[int]] = ()):
        """ONE bucketed b=1 ``prefill_suffix`` dispatch shared by full
        admissions and chunk prefills: pads the suffix to its jit bucket,
        builds the scatter coordinates from ``blocks`` (position p ->
        ``blocks[p // bs]``), gathers the cached prefix, and returns the
        last-position logits as a device array.  A model with several
        layer types takes the block coordinates by type (``more_blocks``:
        ``Request.more_blocks``; a position whose block a window type does
        not hold goes to that pool's scratch block; a state type's entry
        is ``[1]``, the request's record)."""
        import jax.numpy as jnp

        S = _bucket(len(suffix), self.max_len)
        self.counters["prefill_tokens"] += len(suffix)
        self.counters["prefill_padded_tokens"] += S
        pad_tok = list(suffix) + [0] * (S - len(suffix))
        # pool coordinates for each padded suffix lane (pads -> scratch 0)
        pos = cached_len + np.arange(len(suffix))
        dst_o = np.zeros(S, np.int32)
        dst_o[:len(suffix)] = pos % self.bs

        def coordinates(held):
            dst = np.zeros(S, np.int32)
            dst[:len(suffix)] = np.asarray(held, np.int32)[pos // self.bs]
            return jnp.asarray(dst)

        dst_b = coordinates(blocks)
        if self._by_type:  # a state type's: the request's record
            dst_b = {p.name: dst_b if p is self._pools[0]
                     else jnp.asarray(held, jnp.int32) if p.state
                     else coordinates(held)
                     for p, held in zip(self._pools, [blocks, *more_blocks])}
        P = _bucket(len(hit_blocks), self.MB) if hit_blocks else 0
        if self.prefill_attention is not None:
            self._prefill_path = self.model.prefill_attention_path(
                self.cfg, S, P * self.bs)
            self.prefill_attention[self._prefill_path] += 1
        prefix_ids = np.zeros(P, np.int32)
        prefix_ids[:len(hit_blocks)] = hit_blocks
        pk, pv = self.model.gather_prefix(self.pool, jnp.asarray(prefix_ids),
                                          self.cfg)
        logits, self.pool, *extra = self._prefill(
            self.params, jnp.asarray([pad_tok], jnp.int32),
            jnp.int32(len(suffix)), jnp.int32(cached_len),
            pk, pv, jnp.int32(cached_len),
            dst_b, jnp.asarray(dst_o), self.pool)
        for counts in extra:  # fetched with the first tokens
            self._prefill_sum = counts if self._prefill_sum is None \
                else self._prefill_sum + counts
            self._prefill_calls += 1
        return logits

    def _admit_chunk(self, i: int, req: Request, hit_blocks: List[int],
                     n_pinned: int, cached_len: int, budget: int,
                     keys: List[Any]):
        """Prefill one block-aligned chunk of a long prompt WITHOUT
        occupying a slot: write the chunk's KV, register its (full)
        blocks under the prefix hash chain, and PIN them on the request
        (refs held in ``req.chunk_blocks``) so ordinary pool pressure
        can't evict the prompt's own progress — the next admission
        resumes from the pinned prefix directly.  Pins are forfeited
        only by ``_yield_chunk_pins`` (starved queue head).  The request
        stays at the queue head."""
        toks = req.prompt_tokens
        # chunk end: block-aligned, within budget, and NEVER the whole
        # remaining suffix (the final partial admission must sample)
        take = ((cached_len + budget) // self.bs) * self.bs - cached_len
        take = min(take, ((len(toks) - 1 - cached_len) // self.bs)
                   * self.bs)
        if take < self.bs:
            # budget tail can't cover one full block this step: defer
            # (short prompts can still full-admit from the same tail)
            for bid in hit_blocks[n_pinned:]:
                self.blocks.release(bid)
            return ("partial", None, 0)
        n_need = take // self.bs
        if self.blocks.available() < n_need:
            for bid in hit_blocks[n_pinned:]:
                self.blocks.release(bid)
            if self._yield_chunk_pins():
                return self._admit(i, budget)  # retry with freed blocks
            return None  # pool pressure: try again later
        chunk = toks[cached_len:cached_len + take]
        new_blocks = self._extend(
            self._pools[0], list(hit_blocks), len(hit_blocks) + n_need,
            ahead=False)[len(hit_blocks):]  # all to be filled and pinned
        # each chunk re-gathers the whole pinned prefix (O(n^2/chunk)
        # copy traffic over the prompt) — a constant factor of chunked
        # attention's inherent O(n^2) KV reads and far below decode's
        # per-token full-table gather, so a block-table-reading prefill
        # kernel is a future optimization, not a scaling fix
        self._run_prefill(chunk, cached_len, hit_blocks + new_blocks,
                          hit_blocks)  # logits discarded: nothing samples
        for j, bid in enumerate(new_blocks):
            self.blocks.register(bid, keys[cached_len // self.bs + j])
        # every block (prior pinned + newly acquired hits + new) is now
        # pinned on the request; refs transfer to req.blocks at admission
        req.chunk_blocks = hit_blocks + new_blocks
        self.prefill_stats["chunks"] += 1
        return ("partial", None, take)

    def _ensure_decode_blocks(self, active: List[int]) -> List[int]:
        """Allocate blocks covering a whole window's write positions (the
        next ``K``) for each active slot, a run at a time where the table
        crosses a multiple of the pool's run (``_BlockManager.take``),
        preempting the youngest request when the pool is exhausted (vLLM
        recompute preemption)."""
        for i in list(active):
            req = self._slots[i]
            if req is None or req.done:
                continue
            # cap at the request's remaining budget: tail tokens past
            # max_tokens are discarded (and clamp to scratch), so reserving
            # blocks for them could only cause needless preemption
            remaining = max(1, req.sampling.max_tokens - req.num_generated)
            last_pos = min(int(self._cur_len[i]) + min(self.K, remaining)
                           - 1, self.max_len - 1)
            blk_idx = last_pos // self.bs
            for p, held in zip(self._pools, [req.blocks] + req.more_blocks):
                if p.state:
                    continue  # a record does not grow with the position
                while blk_idx >= len(held) and self._slots[i] is req:
                    got = p.blocks.take(len(held),
                                        p.tables.shape[1] - len(held))
                    if got is None:
                        # cheapest relief first: a queued prompt's forfeited
                        # chunk pins cost at most one chunk recompute, vs a
                        # whole-request re-prefill for a preemption
                        if self._yield_chunk_pins(include_head=True):
                            continue
                        if self._preempt_youngest() is None:
                            break
                        continue  # self-preempted: slot is back in the queue
                    p.tables[i, len(held):len(held) + len(got)] = got
                    held += got
                    self._dev_dirty = True
        return [i for i in active if self._slots[i] is not None
                and not self._slots[i].done]

    def _preempt_youngest(self) -> Optional[int]:
        cand = [i for i in range(self.B) if self._slots[i] is not None
                and not self._slots[i].done]
        if not cand:
            return None
        i = max(cand, key=lambda j: self._slots[j].request_id)
        req = self._slots[i]
        self._release_blocks(req)
        # roll generated tokens into the prompt: re-prefill resumes exactly
        # (n_prompt keeps outputs and the max_tokens budget intact)
        req.prompt_tokens = req.prompt_tokens + req.out_tokens
        req.out_tokens = []
        req.cached_prefix_len = 0
        req.chain_keys = None  # prompt changed: recompute on re-admit
        # its decode so far ends here and a second queue wait begins
        now = time.time()
        self._request_span("engine.decode", req, req.t_decode, now,
                           tokens=len(req.prompt_tokens) - req.n_prompt,
                           preempted=True)
        req.t_queued = now
        self._queue.appendleft(req)
        self._slots[i] = None
        self._clear_tables(i)
        self.blocks.stats["preemptions"] += 1
        return i

    def _window_arity(self, active: List[int]) -> int:
        """The decode-window length step() would run for these slots:
        min(K, longest remaining budget)."""
        rem = 1
        for i in active:
            req = self._slots[i]
            r = min(req.sampling.max_tokens - req.num_generated,
                    self.max_len - 1 - len(req.prompt_tokens)
                    - len(req.out_tokens))
            rem = max(rem, r)
        return max(1, min(self.K, rem))

    # -- internals ----------------------------------------------------------

    def _record_token(self, i: int, req: Request, tok: int):
        """A request's first token (the one its prefill samples; a window's
        go through ``_fetch_and_commit``) into its state, kept back from
        ``on_token`` while the request goes on."""
        sp = req.sampling
        if sp.stop_token_id is not None and tok == sp.stop_token_id:
            req.done = True
            return
        req.out_tokens.append(tok)
        self._next_token[i] = tok
        if req.prefill_only:
            # first sampled token is the handoff payload's seed; the
            # decode replica generates everything after it
            req.done = True
            return
        if (req.num_generated >= sp.max_tokens
                or len(req.prompt_tokens) + len(req.out_tokens)
                >= self.max_len - 1):
            req.done = True
        if not req.done:
            req.held.append(tok)
        else:
            self._tell(req, [tok])

    def _tell(self, req: Request, toks: List[int]):
        """Hand tokens to the per-token hook, behind what the request held
        back.  A request's first token is handed out with the tokens of
        its first window, as it was when admission and that window shared
        a step.  On its own it could leave a window sooner (the step that
        samples it fetches a window the request is not in), but no token
        after it would: the stream's time from first to last token would
        grow by a window, and so would every reading of the gap between
        tokens taken from it.  The window it waits is the one its prefill
        queued behind (ROADMAP A3 gives it back to the first token)."""
        held, req.held = req.held, []
        if self.on_token is None:
            return
        for tok in held + toks:
            try:
                self.on_token(req.request_id, tok)
            except Exception:  # noqa: BLE001 - consumer hook must not kill decode
                pass

    def _count(self, sums, steps: int = 0, kind: str = "") -> Dict[str, int]:
        """Add fetched counter sums (``ServedModel.counters``' order) to
        ``self.counters``: a decode window's under the model's names and
        ``decode_steps``, prefills' (``kind="prefill_"``) under names and
        a count of calls of their own, so that each sum has its
        denominator.  Returns what was added, by the model's names."""
        added = {n: int(v) for n, v in zip(self.model.counters, sums)}
        for name, v in added.items():
            self.counters[kind + name] += v
        self.counters["prefill_calls" if kind else "decode_steps"] += steps
        return added

    def _refresh_device_mirrors(self):
        """Bring the device mirrors of the decode inputs up to the host's,
        each only where the host changed it:

        * the block tables, when a row changed (admit / retire / preempt /
          table growth set ``_dev_dirty``).  First the row of every slot
          whose request is done is zeroed, as an empty slot's is: whatever
          is launched next — a carried window runs before ``retire`` —
          reads nothing for that slot and writes its position to the
          scratch block, never into blocks about to be released;
        * ``(tok_d, cur_d)`` and the temperatures, when a request entered
          a slot (``_dev`` is None).
          A table that grew does NOT invalidate them: after a window's
          commit the host's ``_next_token`` / ``_cur_len`` of a slot that
          goes on are what the window's last step left on the device, so
          the chained pair is kept, and an upload of the host's vectors
          ([B]-shaped, whatever the number of admissions) patches the
          entering slots in without touching the others' values.

        Uploads are ``jnp.array`` (always a copy, never a view of the
        numpy buffer): the host mirrors are written by the next step's
        admissions while a window that reads the device arrays is in
        flight."""
        import jax.numpy as jnp

        for i, req in enumerate(self._slots):
            if req is not None and req.done and self._tables[i, 0]:
                self._clear_tables(i)
        if self._dev_dirty or self._tables_d is None:
            self._tables_d = ({p.name: jnp.array(p.tables)
                               for p in self._pools} if self._by_type
                              else jnp.array(self._tables))
            self._dev_dirty = False
        if self._dev is None:
            self._temps_d = jnp.array(self._temp_vec())
            self._dev = (jnp.array(self._next_token),
                         jnp.array(self._cur_len))

    def _temp_vec(self, sl: slice = slice(None)) -> np.ndarray:
        temps = np.ones(self.B, np.float32)
        for i in range(self.B):
            if self._slots[i] is not None:
                temps[i] = self._slots[i].sampling.temperature
        return temps[sl]

def _bucket(n: int, cap: int) -> int:
    """Smallest power of two >= n (>=1), capped."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)
