"""Disaggregated prefill/decode serving: KV-block handoff over the
tiered channel plane, block adoption, two-stage dispatch, re-prefill
fallback (``ray_tpu/llm/kv_transfer.py``, ``llm/serving.py``,
``serve/router.TwoStageHandle``).

Fast tier: block-manager accounting, shipper/landing round trips with
synthetic pools (write-copy counter gate, tier negotiation, dead-peer
retirement), and the two-stage router mechanics over jax-free fake
deployments.  The jax-compile-heavy engine/serve e2e paths carry
``pytest.mark.slow`` like the rest of the LLM tier.
"""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.llm.engine import _BlockManager
from ray_tpu.llm.kv_transfer import (
    KVBlockShipper,
    KVLandingStrip,
    KVShipError,
)
from ray_tpu.experimental.channel.shared_memory_channel import (
    COPY_STATS,
    reset_copy_stats,
)
from ray_tpu.experimental.channel.transport import (
    TIER_DEVICE,
    TIER_HOST,
    attach_edge_transport,
)


@pytest.fixture
def serve_shutdown(ray_start):
    yield
    serve.shutdown()


# ---------------------------------------------------------------------------
# block-manager accounting (satellite: refcount audit)
# ---------------------------------------------------------------------------


class TestBlockManagerAdopt:
    def test_adopt_registers_keys_and_integrity(self):
        bm = _BlockManager(8)
        bids = bm.adopt(["k0", "k1", None])
        assert bids is not None and len(bids) == 3
        bm.assert_integrity()
        # registered keys serve future prefix hits
        assert bm.acquire_cached("k0") == bids[0]
        bm.release(bids[0])  # the extra acquire
        for b in bids:
            bm.release(b)
        bm.assert_integrity()
        # registered blocks retired into the LRU, unkeyed one freed
        assert set(bm.lru.values()) == {bids[0], bids[1]}

    def test_adopt_all_or_nothing_under_pressure(self):
        bm = _BlockManager(4)  # 3 usable blocks
        held = [bm.alloc(), bm.alloc()]
        assert bm.adopt(["a", "b"]) is None  # needs 2, only 1 left
        bm.assert_integrity()
        assert bm.available() == 1  # the failed adopt leaked nothing
        # the rollback UNPUBLISHED its keys: a later lookup must miss —
        # an LRU-retained never-written block would serve garbage KV to
        # the very re-prefill the failure falls back to
        assert bm.acquire_cached("a") is None
        assert bm.acquire_cached("b") is None
        for b in held:
            bm.release(b)
        assert bm.adopt(["a", "b"]) is not None
        bm.assert_integrity()

    def test_adopt_duplicate_key_keeps_local_registration(self):
        bm = _BlockManager(8)
        local = bm.alloc()
        bm.register(local, "shared")
        bids = bm.adopt(["shared"])
        assert bids is not None
        # the local publication wins; the adopted copy stays unpublished
        assert bm.by_key["shared"] == local
        bm.release(local)
        for b in bids:
            bm.release(b)
        bm.assert_integrity()


# ---------------------------------------------------------------------------
# shipper / landing strip over a real channel (synthetic pools, no model)
# ---------------------------------------------------------------------------


def _fake_handoff(hid, seed=0, blocks=3, dtype=None):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    shape = (2, blocks, 4, 2, 8)  # [L, n, bs, KVH, hd]
    kv = {"k": jnp.asarray(rng.standard_normal(shape, np.float32)),
          "v": jnp.asarray(rng.standard_normal(shape, np.float32))}
    return {"handoff_id": hid, "prompt_tokens": list(range(3, 14)),
            "n_prompt": 11, "out_tokens": [7], "sampling": None,
            "kv_cache_dtype": dtype, "block_size": 4, "kv": kv}


def _pair(monkeypatch, emulate=True, channel_bytes=1 << 20):
    """A shipper + landing strip wired through one real shm channel,
    with the peer probed as a different pid so negotiation runs the
    cross-process matrix."""
    import dataclasses

    from ray_tpu.experimental.channel.transport import local_endpoint_info

    if emulate:
        monkeypatch.setenv("RAY_TPU_ICI_EMULATE", "1")
    else:
        monkeypatch.delenv("RAY_TPU_ICI_EMULATE", raising=False)
    landed = []
    lock = threading.Lock()

    def adopt(h):
        with lock:
            landed.append(h)
        return True

    strip = KVLandingStrip(adopt, poll_s=0.05)
    ship = KVBlockShipper("p0", channel_bytes=channel_bytes,
                          ship_timeout_s=10.0)
    peer = dataclasses.replace(local_endpoint_info(), pid=999999)
    ship.connect(
        "d0", peer,
        lambda tr: strip.attach(attach_edge_transport(tr, 0), "p0"))
    return ship, strip, landed, lock


class TestShipperRoundTrip:
    def test_tier_b_round_trip_zero_host_pickle_copies(self, monkeypatch):
        ship, strip, landed, lock = _pair(monkeypatch, emulate=True)
        try:
            assert ship.tier_of("d0") == TIER_DEVICE
            reset_copy_stats()
            src = _fake_handoff("h1", seed=1)
            res = ship.ship("d0", src, timeout=10)
            assert res["tier"] == TIER_DEVICE
            deadline = time.time() + 10
            while time.time() < deadline:
                with lock:
                    if landed:
                        break
                time.sleep(0.01)
            with lock:
                assert len(landed) == 1, strip.stats()
                got = landed[0]
            # the acceptance gate: ZERO host-pickle staging copies on
            # the tier-B path — payload bytes move into the segment
            # exactly once (the no-double-copy counter)
            ratio = COPY_STATS["bytes_copied"] / max(
                1, COPY_STATS["payload_bytes"])
            assert ratio < 1.05, COPY_STATS
            assert got["handoff_id"] == "h1"
            assert got["prompt_tokens"] == src["prompt_tokens"]
            np.testing.assert_array_equal(np.asarray(got["kv"]["k"]),
                                          np.asarray(src["kv"]["k"]))
            # alias safety (the PR 5/10 gotcha class): the landed arrays
            # must own their data — a SECOND ship reusing the segment
            # must not corrupt the first landing
            before = np.asarray(got["kv"]["k"]).copy()
            ship.ship("d0", _fake_handoff("h2", seed=2), timeout=10)
            deadline = time.time() + 10
            while time.time() < deadline:
                with lock:
                    if len(landed) == 2:
                        break
                time.sleep(0.01)
            np.testing.assert_array_equal(np.asarray(got["kv"]["k"]),
                                          before)
        finally:
            strip.stop()
            ship.close()

    def test_tier_c_without_emulation_still_delivers(self, monkeypatch):
        ship, strip, landed, lock = _pair(monkeypatch, emulate=False)
        try:
            assert ship.tier_of("d0") == TIER_HOST
            ship.ship("d0", _fake_handoff("h1"), timeout=10)
            deadline = time.time() + 10
            while time.time() < deadline:
                with lock:
                    if landed:
                        break
                time.sleep(0.01)
            with lock:
                assert landed and landed[0]["handoff_id"] == "h1"
        finally:
            strip.stop()
            ship.close()

    def test_dead_peer_raises_and_retires_channel(self, monkeypatch):
        ship, strip, landed, lock = _pair(monkeypatch, emulate=True,
                                          channel_bytes=1 << 16)
        strip.stop()  # reader gone: the first write fills the segment,
        ship.ship("d0", _fake_handoff("h1", blocks=1), timeout=5)
        try:  # the second can never be acked within the deadline
            with pytest.raises(KVShipError):
                ship.ship("d0", _fake_handoff("h2", blocks=1), timeout=0.3)
            assert ship.tier_of("d0") is None  # peer retired
            with pytest.raises(KVShipError):
                ship.ship("d0", _fake_handoff("h3", blocks=1), timeout=0.3)
        finally:
            ship.close()

    def test_kv_ship_fault_site_fires(self, monkeypatch):
        from ray_tpu.util import fault_injection as fi

        ship, strip, landed, lock = _pair(monkeypatch, emulate=True)
        try:
            with fi.armed("llm.kv_ship", nth=1,
                          exc=ConnectionError("chaos")):
                with pytest.raises(ConnectionError):
                    ship.ship("d0", _fake_handoff("h1"), timeout=5)
        finally:
            strip.stop()
            ship.close()

    def test_oversized_handoff_fails_without_desync(self, monkeypatch):
        ship, strip, landed, lock = _pair(monkeypatch, emulate=True,
                                          channel_bytes=1 << 12)
        try:
            with pytest.raises(ValueError):
                ship.ship("d0", _fake_handoff("big", blocks=8), timeout=5)
            # the channel survives an oversize rejection: a fitting
            # handoff still lands
            ship.ship("d0", _fake_handoff("h1", blocks=1), timeout=10)
            deadline = time.time() + 10
            while time.time() < deadline:
                with lock:
                    if landed:
                        break
                time.sleep(0.01)
            with lock:
                assert landed and landed[0]["handoff_id"] == "h1"
        finally:
            strip.stop()
            ship.close()


# ---------------------------------------------------------------------------
# two-stage dispatch mechanics (jax-free fake pools)
# ---------------------------------------------------------------------------


def _fake_pools(decode_replicas=1, chunk_sleep_s=0.0, chunks=4):
    """Prefill/decode deployments speaking the two-stage protocol
    without any engine: prefill returns a token naming the decode
    replica it was given; decode proves it served on that replica."""

    @serve.deployment(name="FakePrefill")
    class FakePrefill:
        def prefill(self, body, decode_replica):
            return {"handoff_id": f"h-{body['prompt']}",
                    "decode_actor": decode_replica._actor_id.hex()}

    @serve.deployment(name="FakeDecode", num_replicas=decode_replicas)
    class FakeDecode:
        def decode(self, token, body):
            import os

            from ray_tpu._private.worker import get_global_worker

            me = get_global_worker().actor_id.hex()
            return {"generated_text": f"dec:{body['prompt']}",
                    "num_generated_tokens": 3,
                    "served_by": me, "pid": os.getpid(),
                    "token": token}

        def decode_stream(self, token, body):
            import os

            pid = os.getpid()
            for i in range(chunks):
                if chunk_sleep_s:
                    time.sleep(chunk_sleep_s)
                yield {"index": i, "text": f"t{i}", "pid": pid}
            yield {"done": True, "generated_text":
                   "".join(f"t{i}" for i in range(chunks)),
                   "num_generated_tokens": chunks}

    serve.run(FakePrefill.bind(), name="fp", route_prefix="/fp")
    serve.run(FakeDecode.bind(), name="fd", route_prefix="/fd")


def _two_stage(max_reprefills=1):
    from ray_tpu.serve.router import DeploymentHandle, TwoStageHandle

    return TwoStageHandle(DeploymentHandle("FakePrefill"),
                          DeploymentHandle("FakeDecode"),
                          max_reprefills=max_reprefills)


def test_two_stage_unary_targets_reserved_replica(serve_shutdown):
    _fake_pools()
    two = _two_stage()
    out = two.call({"prompt": "x"}, timeout=60)
    assert out["generated_text"] == "dec:x"
    # stage 2 executed on the SAME replica stage 1 shipped to
    assert out["served_by"] == out["token"]["decode_actor"]
    assert two.stats["requests"] == 1
    assert two.stats["reprefills"] == 0


def test_two_stage_stream_chunks_in_order(serve_shutdown):
    _fake_pools()
    two = _two_stage()
    chunks = list(two.stream({"prompt": "s"}))
    assert [c["index"] for c in chunks[:-1]] == [0, 1, 2, 3]
    assert chunks[-1]["done"] and chunks[-1]["num_generated_tokens"] == 4


def test_two_stage_overload_not_retried(serve_shutdown):
    """A shed/expired verdict surfaces unchanged — never re-prefilled."""
    from ray_tpu.exceptions import DeadlineExceededError

    _fake_pools()
    two = _two_stage()
    with serve.request_scope(timeout_s=0.0):  # born expired
        with pytest.raises(DeadlineExceededError):
            two.call({"prompt": "x"})
    assert two.stats["reprefills"] == 0


def test_two_stage_decode_death_reprefills_on_healthy_pair(serve_shutdown):
    """Satellite chaos path: kill the decode replica mid-stream — the
    request re-prefills on a healthy pair (counted) and the stream
    completes with deduplicated indices, inside its deadline."""
    _fake_pools(decode_replicas=2, chunk_sleep_s=0.25, chunks=6)
    two = _two_stage(max_reprefills=3)
    got = []
    killed = {}
    t0 = time.monotonic()
    # temperature=0: greedy streams are the resumable class (sampled
    # ones surface the death instead of splicing two generations)
    with serve.request_scope(timeout_s=60.0):
        for chunk in two.stream({"prompt": "z", "temperature": 0.0}):
            got.append(chunk)
            if not killed and not chunk.get("done"):
                # first chunk names the serving pid: kill that replica
                from ray_tpu.serve.controller import get_controller

                info = ray_tpu.get(
                    get_controller().get_deployment_info.remote(
                        "FakeDecode"), timeout=10)
                for rep in info["replicas"]:
                    st = ray_tpu.get(rep.stats.remote(), timeout=10)
                    if st["pid"] == chunk["pid"]:
                        ray_tpu.kill(rep)
                        killed["pid"] = chunk["pid"]
                        break
                assert killed, "serving replica not found"
    elapsed = time.monotonic() - t0
    assert elapsed < 60.0  # deadline honored
    assert two.stats["reprefills"] >= 1  # counted
    done = got[-1]
    assert done["done"] and done["num_generated_tokens"] == 6
    idx = [c["index"] for c in got if not c.get("done")]
    assert idx == sorted(set(idx)) == list(range(6))  # deduped, complete
    # the stream spans the killed replica AND a healthy one
    finishing = {c["pid"] for c in got if not c.get("done")}
    assert len(finishing) >= 2 and killed["pid"] in finishing


def test_two_stage_sampled_stream_surfaces_death(serve_shutdown):
    """A SAMPLED (non-greedy) stream that already delivered chunks must
    not splice a second generation onto the first — the death surfaces
    and no re-prefill is counted."""
    _fake_pools(decode_replicas=2, chunk_sleep_s=0.25, chunks=6)
    two = _two_stage(max_reprefills=3)
    with pytest.raises(Exception):
        # no temperature field: the engine default (0.7) samples
        for chunk in two.stream({"prompt": "z"}):
            if not chunk.get("done"):
                from ray_tpu.serve.controller import get_controller

                info = ray_tpu.get(
                    get_controller().get_deployment_info.remote(
                        "FakeDecode"), timeout=10)
                for rep in info["replicas"]:
                    st = ray_tpu.get(rep.stats.remote(), timeout=10)
                    if st["pid"] == chunk["pid"]:
                        ray_tpu.kill(rep)
                        break
    assert two.stats["reprefills"] == 0


# ---------------------------------------------------------------------------
# engine-level handoff (jax tiny model — slow tier)
# ---------------------------------------------------------------------------


def _tiny_engines(n=2, **kw):
    import jax

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.models.llama import LlamaConfig, llama_init

    cfg = LlamaConfig.tiny()
    params = llama_init(jax.random.PRNGKey(0), cfg)
    return [LLMEngine(cfg, params, batch_slots=4, max_len=128,
                      block_size=8, **kw) for _ in range(n)]


def _drain(eng, collect=None):
    out = {}
    while eng.has_unfinished():
        for o in eng.step():
            out[o.request_id] = o
    if collect is not None:
        collect.update(out)
    return out


@pytest.mark.slow
class TestEngineHandoff:
    def test_export_adopt_parity_with_colocated(self):
        from ray_tpu.llm import SamplingParams

        ref_eng, pre, dec = _tiny_engines(3)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(3, 200, size=n).tolist()
                   for n in (37, 11, 64)]
        sp = SamplingParams(temperature=0.0, max_tokens=24)
        ref = ref_eng.generate(prompts, sp)

        rids = [pre.submit(p, sp, prefill_only=True) for p in prompts]
        pre_outs = _drain(pre)
        # prefill-only requests emit exactly their first sampled token
        assert all(len(pre_outs[r].token_ids) <= 1 for r in rids)
        handoffs = [pre.export_kv(r) for r in rids]
        pre.blocks.assert_integrity()
        dec_ids = [dec.adopt_prefilled(h) for h in handoffs]
        assert all(d is not None for d in dec_ids)
        res = _drain(dec)
        for r, d in zip(ref, dec_ids):
            assert res[d].token_ids == r.token_ids
            assert res[d].text == r.text
        dec.blocks.assert_integrity()
        assert dec.handoff_stats["adopted"] == 3
        assert pre.handoff_stats["exported"] == 3

    def test_shipped_blocks_never_alias_either_pool(self):
        """Mutate the prefill pool AFTER export (more traffic) and the
        decode pool AFTER adopt — the other side's outputs must not
        change (the gather/scatter produce owned buffers)."""
        from ray_tpu.llm import SamplingParams

        ref_eng, pre, dec = _tiny_engines(3)
        rng = np.random.default_rng(1)
        prompt = rng.integers(3, 200, size=30).tolist()
        sp = SamplingParams(temperature=0.0, max_tokens=20)
        ref = ref_eng.generate([prompt], sp)[0]

        rid = pre.submit(prompt, sp, prefill_only=True)
        _drain(pre)
        handoff = pre.export_kv(rid)
        # churn the prefill pool: every block gets rewritten
        pre.generate([rng.integers(3, 200, size=40).tolist()
                      for _ in range(4)],
                     SamplingParams(temperature=0.0, max_tokens=30))
        did = dec.adopt_prefilled(handoff)
        out = _drain(dec)[did]
        assert out.token_ids == ref.token_ids

    def test_int8_kv_ship_round_trip(self):
        """int8 pools ship values AND scales; parity vs an int8
        colocated engine on the CPU backend (satellite)."""
        from ray_tpu.llm import SamplingParams

        ref_eng, pre, dec = _tiny_engines(3, kv_cache_dtype="int8")
        rng = np.random.default_rng(2)
        prompts = [rng.integers(3, 200, size=25).tolist()
                   for _ in range(2)]
        sp = SamplingParams(temperature=0.0, max_tokens=16)
        ref = ref_eng.generate(prompts, sp)

        rids = [pre.submit(p, sp, prefill_only=True) for p in prompts]
        _drain(pre)
        handoffs = [pre.export_kv(r) for r in rids]
        for h in handoffs:
            assert set(h["kv"]) == {"k", "v", "k_scale", "v_scale"}
            assert h["kv_cache_dtype"] == "int8"
        dec_ids = [dec.adopt_prefilled(h) for h in handoffs]
        res = _drain(dec)
        for r, d in zip(ref, dec_ids):
            assert res[d].token_ids == r.token_ids

    def test_kv_dtype_mismatch_rejected(self):
        from ray_tpu.llm import SamplingParams

        pre, dec = _tiny_engines(2)
        dec_int8 = _tiny_engines(1, kv_cache_dtype="int8")[0]
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        rid = pre.submit(list(range(3, 30)), sp, prefill_only=True)
        _drain(pre)
        h = pre.export_kv(rid)
        with pytest.raises(ValueError):
            dec_int8.adopt_prefilled(h)
        dec_int8.blocks.assert_integrity()  # rejection leaked nothing
        assert dec.adopt_prefilled(h) is not None

    def test_oversized_handoff_for_smaller_decode_table_rejected(self):
        """A handoff from a larger-max_len prefill engine fails THAT
        request with ValueError (caller falls back) instead of crashing
        the decode engine loop scattering past its table width."""
        import jax

        from ray_tpu.llm.engine import LLMEngine
        from ray_tpu.llm import SamplingParams
        from ray_tpu.models.llama import LlamaConfig, llama_init

        cfg = LlamaConfig.tiny()
        params = llama_init(jax.random.PRNGKey(0), cfg)
        pre = LLMEngine(cfg, params, batch_slots=2, max_len=256,
                        block_size=8)
        dec = LLMEngine(cfg, params, batch_slots=2, max_len=64,
                        block_size=8)
        sp = SamplingParams(temperature=0.0, max_tokens=4)
        rid = pre.submit(list(range(3, 123)), sp, prefill_only=True)
        _drain(pre)
        h = pre.export_kv(rid)
        with pytest.raises(ValueError, match="exceeds"):
            dec.adopt_prefilled(h)
        dec.blocks.assert_integrity()
        assert not dec.has_unfinished()

    def test_adopt_pool_pressure_returns_none(self):
        import jax

        from ray_tpu.llm.engine import LLMEngine
        from ray_tpu.llm import SamplingParams
        from ray_tpu.models.llama import LlamaConfig, llama_init

        cfg = LlamaConfig.tiny()
        params = llama_init(jax.random.PRNGKey(0), cfg)
        pre = LLMEngine(cfg, params, batch_slots=2, max_len=128,
                        block_size=8)
        # tiny decode pool: 4 usable blocks
        dec = LLMEngine(cfg, params, batch_slots=2, max_len=128,
                        block_size=8, num_blocks=5)
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        rid = pre.submit(list(range(3, 70)), sp, prefill_only=True)
        _drain(pre)
        h = pre.export_kv(rid)  # needs 9 blocks
        assert dec.adopt_prefilled(h) is None
        assert dec.handoff_stats["adopt_failures"] == 1
        dec.blocks.assert_integrity()

    def test_adopted_prefix_serves_local_prefix_hits(self):
        """Grafted chain keys make the SHIPPED prefix hit for future
        local prompts — the prefix cache composes across the handoff."""
        from ray_tpu.llm import SamplingParams

        pre, dec = _tiny_engines(2)
        rng = np.random.default_rng(3)
        base = rng.integers(3, 200, size=32).tolist()
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        rid = pre.submit(base, sp, prefill_only=True)
        _drain(pre)
        did = dec.adopt_prefilled(pre.export_kv(rid))
        _drain(dec)
        assert dec.blocks.stats["prefix_hits"] == 0
        # a local prompt sharing the shipped prefix reuses those blocks
        dec.generate([base[:24] + rng.integers(3, 200, size=8).tolist()],
                     sp)
        assert dec.blocks.stats["prefix_hits"] == 1
        assert dec.blocks.stats["prefix_blocks_reused"] >= 2
        dec.blocks.assert_integrity()
        assert did is not None

    def test_abort_releases_export_and_adopt_queue(self):
        from ray_tpu.llm import SamplingParams

        pre, dec = _tiny_engines(2)
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        rid = pre.submit(list(range(3, 40)), sp, prefill_only=True)
        _drain(pre)
        assert rid in pre._exports
        assert pre.abort(rid) is True  # abandoned before the ship
        pre.blocks.assert_integrity()
        assert pre._exports == {}

        rid2 = pre.submit(list(range(3, 40)), sp, prefill_only=True)
        _drain(pre)
        h = pre.export_kv(rid2)
        did = dec.adopt_prefilled(h)
        assert dec.abort(did) is True  # abandoned before a slot opened
        dec.blocks.assert_integrity()
        assert not dec.has_unfinished()


# ---------------------------------------------------------------------------
# engine satellites: chunked-prefill refcounts + prefix reuse
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestChunkedPrefillAccounting:
    def test_abort_between_chunks_releases_pins_and_lru_evicts(self):
        """Satellite audit: ``Request.blocks`` / ``chunk_blocks`` refs
        are HELD across admissions — an abort between chunks must
        release them so the LRU can evict every block again."""
        from ray_tpu.llm import SamplingParams

        (eng,) = _tiny_engines(1, prefill_chunk=16)
        sp = SamplingParams(temperature=0.0, max_tokens=8)
        long_prompt = list(np.random.default_rng(4).integers(
            3, 200, size=100))
        rid = eng.submit([int(t) for t in long_prompt], sp)
        eng.step()  # one chunk prefilled and PINNED, request still queued
        req = eng._queue[0]
        assert req.request_id == rid and req.chunk_blocks
        pinned = list(req.chunk_blocks)
        assert all(eng.blocks.refs.get(b, 0) >= 1 for b in pinned)
        assert eng.abort(rid) is True
        eng.blocks.assert_integrity()
        # every pinned block is reclaimable: allocating the whole pool
        # must succeed (retired chunk blocks evict from the LRU)
        capacity = eng.blocks.available()
        got = [eng.blocks.alloc() for _ in range(capacity)]
        assert all(b is not None for b in got)
        assert eng.blocks.available() == 0
        for b in got:
            eng.blocks.release(b)
        eng.blocks.assert_integrity()

    def test_abort_mid_chunk_then_traffic_continues(self):
        """After an abort between chunks, unrelated requests admit and
        complete with correct accounting (no phantom refs starving the
        pool)."""
        from ray_tpu.llm import SamplingParams

        ref_eng, eng = _tiny_engines(2, prefill_chunk=16)
        sp = SamplingParams(temperature=0.0, max_tokens=12)
        rng = np.random.default_rng(5)
        long_prompt = rng.integers(3, 200, size=100).tolist()
        short = rng.integers(3, 200, size=12).tolist()
        ref = ref_eng.generate([short], sp)[0]

        rid = eng.submit(long_prompt, sp)
        eng.step()
        eng.abort(rid)
        out = eng.generate([short], sp)[0]
        assert out.token_ids == ref.token_ids
        eng.blocks.assert_integrity()

    def test_preemption_of_chunk_pinned_queue_head(self):
        """Decode pressure forfeits a queued prompt's chunk pins
        (``_yield_chunk_pins``) — verify the forfeited request still
        completes correctly afterwards and nothing leaks."""
        import jax

        from ray_tpu.llm.engine import LLMEngine
        from ray_tpu.llm import SamplingParams
        from ray_tpu.models.llama import LlamaConfig, llama_init

        cfg = LlamaConfig.tiny()
        params = llama_init(jax.random.PRNGKey(0), cfg)
        ref_eng = LLMEngine(cfg, params, batch_slots=2, max_len=128,
                            block_size=8)
        eng = LLMEngine(cfg, params, batch_slots=2, max_len=128,
                        block_size=8, num_blocks=20, prefill_chunk=16)
        rng = np.random.default_rng(6)
        long_prompt = rng.integers(3, 200, size=90).tolist()
        short = rng.integers(3, 200, size=10).tolist()
        sp_long = SamplingParams(temperature=0.0, max_tokens=8)
        sp_short = SamplingParams(temperature=0.0, max_tokens=40)
        ref_short = ref_eng.generate([short], sp_short)[0]
        ref_long = ref_eng.generate([long_prompt], sp_long)[0]

        sid = eng.submit(short, sp_short)
        lid = eng.submit(long_prompt, sp_long)
        outs = _drain(eng)
        assert outs[sid].token_ids == ref_short.token_ids
        assert outs[lid].token_ids == ref_long.token_ids
        eng.blocks.assert_integrity()

    def test_prefix_reuse_across_chunked_admissions(self):
        """Satellite: a second prompt sharing the first's prefix re-hits
        the chunked prefill's registered blocks — admissions after
        chunking keep the prefix cache warm."""
        from ray_tpu.llm import SamplingParams

        ref_eng, eng = _tiny_engines(2, prefill_chunk=16)
        rng = np.random.default_rng(7)
        base = rng.integers(3, 200, size=64).tolist()
        tail = rng.integers(3, 200, size=12).tolist()
        sp = SamplingParams(temperature=0.0, max_tokens=10)
        ref = ref_eng.generate([base + tail], sp)[0]

        eng.generate([base], sp)
        hits0 = eng.blocks.stats["prefix_hits"]
        out = eng.generate([base + tail], sp)[0]
        assert out.token_ids == ref.token_ids
        assert eng.blocks.stats["prefix_hits"] == hits0 + 1
        assert eng.blocks.stats["prefix_blocks_reused"] >= 64 // 8 - 1
        eng.blocks.assert_integrity()


# ---------------------------------------------------------------------------
# serve-level e2e (tiny engine replicas — slow tier)
# ---------------------------------------------------------------------------


def _llm_body(max_tokens=16):
    return {"prompt": "the quick brown fox jumps over the lazy dog",
            "max_tokens": max_tokens, "temperature": 0.0}


@pytest.fixture
def emulated_cluster(no_cluster, monkeypatch):
    """A fresh cluster whose raylet-spawned replica workers INHERIT the
    ICI emulation env (the session cluster's workers predate it, so
    channels there negotiate tier C)."""
    monkeypatch.setenv("RAY_TPU_ICI_EMULATE", "1")
    ray_tpu.init(num_cpus=8, num_tpus=0)
    yield
    serve.shutdown()


@pytest.mark.slow
class TestServeDisaggregated:
    def test_matches_colocated_unary_and_stream(self, emulated_cluster):
        from ray_tpu.llm.serving import (build_disaggregated_llm_deployment,
                                         build_llm_deployment,
                                         disaggregated_handle)

        ek = {"model": "tiny", "batch_slots": 4, "max_len": 128}
        body = _llm_body()
        colo = serve.run(build_llm_deployment(ek), name="colo",
                         route_prefix="/colo")
        ref = colo.remote(body).result(timeout=300)
        serve.delete("LLMServer")

        ingress = serve.run(build_disaggregated_llm_deployment(ek),
                            name="llm", route_prefix="/llm")
        out = ingress.remote(body).result(timeout=300)
        assert out == ref
        two = disaggregated_handle()
        assert two.call(body, timeout=300) == ref
        chunks = list(two.stream(body))
        assert chunks[-1]["done"]
        assert chunks[-1]["generated_text"] == ref["generated_text"]
        text = "".join(c.get("text", "") for c in chunks
                       if not c.get("done"))
        assert text == ref["generated_text"]
        # the handoff really rode the channel plane (no silent fallback)
        from ray_tpu.serve.router import DeploymentHandle

        pre_stats = DeploymentHandle("LLMPrefill").stats.remote().result(
            timeout=30)
        assert pre_stats["handoff"]["exported"] >= 3
        tiers = {s["tier"] for s in pre_stats["shipper"].values()}
        assert tiers == {TIER_DEVICE}
        dec_stats = DeploymentHandle("LLMDecode").stats.remote().result(
            timeout=30)
        assert dec_stats["handoff"]["adopted"] >= 3
        assert dec_stats["fallback_reprefills"] == 0

    def test_missing_handoff_falls_back_to_local_prefill(
            self, serve_shutdown):
        """A tokenless handoff (ship failed) or one that never lands
        must degrade to a local re-prefill on the decode replica — the
        request still completes, counted."""
        from ray_tpu.llm.serving import LLMDecodeServer

        srv = LLMDecodeServer._target({"model": "tiny", "batch_slots": 2,
                                       "max_len": 128})
        try:
            srv.HANDOFF_WAIT_S = 0.2
            body = _llm_body(max_tokens=8)
            out = srv.decode({"handoff_id": "never-shipped"}, body)
            assert out["num_generated_tokens"] == 8
            assert srv._fallback_reprefills == 1
            out2 = srv.decode({"handoff_id": None}, body)
            assert out2 == out  # deterministic greedy fallback
            chunks = list(srv.decode_stream({"handoff_id": None}, body))
            assert chunks[-1]["done"]
            assert chunks[-1]["generated_text"] == out["generated_text"]
            assert srv._fallback_reprefills == 3
        finally:
            srv._stop = True

    def test_handoff_fault_site_delay_forces_fallback(self,
                                                      serve_shutdown):
        from ray_tpu.llm.serving import LLMDecodeServer
        from ray_tpu.util import fault_injection as fi

        srv = LLMDecodeServer._target({"model": "tiny", "batch_slots": 2,
                                       "max_len": 128})
        try:
            srv.HANDOFF_WAIT_S = 0.1
            with fi.armed("llm.handoff", nth=1, exc="delay:0.2"):
                out = srv.decode({"handoff_id": "late"},
                                 _llm_body(max_tokens=4))
                fired = fi.call_count("llm.handoff")
            assert out["num_generated_tokens"] == 4
            assert srv._fallback_reprefills == 1
            assert fired == 1
        finally:
            srv._stop = True

    def test_decode_replica_death_mid_stream_reprefills(
            self, serve_shutdown, monkeypatch):
        """The satellite chaos test, real engines: kill the decode
        replica serving a stream — the request re-prefills on a healthy
        pair, is counted, and honors its deadline."""
        from ray_tpu.llm.serving import (build_disaggregated_llm_deployment,
                                         disaggregated_handle)
        from ray_tpu.serve.controller import get_controller

        # decode_window=1 keeps the decode loop slow enough (one host
        # sync per token) that the kill lands while generation is still
        # in flight — a finished engine would have every stream ref
        # already produced and nothing left to fail
        ek = {"model": "tiny", "batch_slots": 4, "max_len": 128,
              "decode_window": 1}
        serve.run(build_disaggregated_llm_deployment(
            ek, decode_replicas=2), name="llm", route_prefix="/llm")
        two = disaggregated_handle(max_reprefills=3)
        two.call(_llm_body(max_tokens=4), timeout=300)  # warm both paths
        replicas = ray_tpu.get(
            get_controller().get_deployment_info.remote("LLMDecode"),
            timeout=30)["replicas"]

        body = _llm_body(max_tokens=96)
        got = []
        killed = False
        t0 = time.monotonic()
        with serve.request_scope(timeout_s=120.0):
            for chunk in two.stream(body):
                got.append(chunk)
                if not killed and not chunk.get("done"):
                    # kill the decode replica carrying the stream
                    busiest = max(
                        replicas,
                        key=lambda r: ray_tpu.get(
                            r.get_queue_len.remote(), timeout=10))
                    ray_tpu.kill(busiest)
                    killed = True
        assert killed
        assert time.monotonic() - t0 < 120.0  # deadline honored
        assert two.stats["reprefills"] >= 1   # counted
        assert got[-1]["done"]
        # greedy decode: the retried stream reproduces the same text
        text = "".join(c.get("text", "") for c in got
                       if not c.get("done"))
        assert text == got[-1]["generated_text"]
