"""Ingest-pipeline tests: block-prefetch lookahead, locality-aware
streaming split, double-buffered H2D staging, and teardown hygiene.

Covers the pipelined data plane end to end (reference model:
``python/ray/data/tests/test_iterator.py`` + the output-splitter
locality tests): lookahead preserves block order and propagates
mid-stream errors in position; abandoning an iterator leaks no producer
threads; ``streaming_split(locality_hints=...)`` routes bundles to their
co-located consumer on a real two-node cluster; a node death mid-stream
falls back to lineage reconstruction; and the CPU smoke bench proves the
overlap (pipelined >= 1.5x forced-serial, consumer-blocked strictly
below total block-fetch time).
"""

import gc
import os
import signal
import threading
import time

import numpy as np
import pytest

import ray_tpu
import ray_tpu.data as rd
from ray_tpu.data.block import BlockMetadata, batch_to_block
from ray_tpu.data.context import DataContext
from ray_tpu.data.iterator import DataIterator, _ShuffleBuffer
from ray_tpu.data.operators import OutputSplitter, PhysicalOperator, RefBundle


def _bundles_from_blocks(n_blocks: int, rows: int, pad_cols: int = 0):
    """n_blocks put-blocks of ``rows`` rows with globally increasing ids."""
    bundles = []
    for i in range(n_blocks):
        batch = {"id": np.arange(i * rows, (i + 1) * rows)}
        if pad_cols:
            batch["payload"] = np.ones((rows, pad_cols), np.float64)
        block = batch_to_block(batch)
        meta = BlockMetadata.for_block(block)
        bundles.append(RefBundle([(ray_tpu.put(block), meta)]))
    return bundles


def _source_of(bundles, delay_s: float = 0.0, fail_after: int = None,
               exc: BaseException = None):
    def source():
        for i, b in enumerate(bundles):
            if fail_after is not None and i == fail_after:
                raise exc
            if delay_s:
                time.sleep(delay_s)
            yield b
    return source


def _ingest_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("rtpu-data")]


def _wait_ingest_threads_gone(baseline: int, timeout: float = 15.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        gc.collect()
        if len(_ingest_threads()) <= baseline:
            return True
        time.sleep(0.1)
    return False


# -- lookahead ordering + error propagation -----------------------------------


def test_lookahead_preserves_block_order(ray_start):
    bundles = _bundles_from_blocks(20, 32)
    it = DataIterator(_source_of(bundles))
    ids = []
    for b in it.iter_batches(batch_size=32, prefetch_batches=2):
        ids.extend(b["id"].tolist())
    assert ids == list(range(20 * 32)), "lookahead reordered blocks"
    d = it.ingest_stats.to_dict()
    assert d["blocks"] == 20 and d["batches"] == 20
    assert d["bytes_fetched"] > 0
    # the human-readable report renders without error
    assert "Ingest pipeline stats" in it.stats()


def test_lookahead_propagates_midstream_source_error(ray_start):
    """A source failure surfaces at its stream position: every earlier
    batch is delivered first, then the original exception raises."""
    bundles = _bundles_from_blocks(6, 16)
    boom = RuntimeError("upstream exploded")
    it = DataIterator(_source_of(bundles, fail_after=4, exc=boom))
    got = []
    with pytest.raises(RuntimeError, match="upstream exploded"):
        for b in it.iter_batches(batch_size=16, prefetch_batches=2):
            got.extend(b["id"].tolist())
    assert got == list(range(4 * 16)), "batches before the error were lost"


def test_lookahead_propagates_block_task_error(ray_start):
    """An errored block ref (failed producing task) raises from the
    consumer at that block's position, not from the lookahead thread."""

    @ray_tpu.remote
    def bad_block():
        raise ValueError("bad block payload")

    bundles = _bundles_from_blocks(3, 8)
    bad_meta = BlockMetadata(num_rows=8, size_bytes=256)
    bundles.insert(2, RefBundle([(bad_block.remote(), bad_meta)]))
    it = DataIterator(_source_of(bundles))
    got = []
    with pytest.raises(Exception, match="bad block payload"):
        for b in it.iter_batches(batch_size=8, prefetch_batches=2):
            got.extend(b["id"].tolist())
    assert got == list(range(2 * 8))


def test_forced_serial_fallback_still_works(ray_start):
    """lookahead_bytes=0 is the A/B baseline: same results, no threads."""
    ctx = DataContext.get_current()
    saved = ctx.iterator_lookahead_bytes
    ctx.iterator_lookahead_bytes = 0
    try:
        bundles = _bundles_from_blocks(5, 16)
        it = DataIterator(_source_of(bundles))
        ids = [v for b in it.iter_batches(batch_size=16, prefetch_batches=0)
               for v in b["id"].tolist()]
        assert ids == list(range(5 * 16))
        d = it.ingest_stats.to_dict()
        # serial: every stall is on the consumer, so blocked == fetch total
        assert d["consumer_blocked_s"] >= d["block_fetch_s"]
    finally:
        ctx.iterator_lookahead_bytes = saved


# -- abandonment hygiene ------------------------------------------------------


def test_early_abandon_leaves_no_threads(ray_start):
    """A consumer that breaks after one batch must not leave lookahead or
    prefetch producer threads alive (the pre-PR leak: blocked in q.put)."""
    baseline = len(_ingest_threads())
    bundles = _bundles_from_blocks(30, 64)
    it = DataIterator(_source_of(bundles, delay_s=0.005))
    for b in it.iter_batches(batch_size=64, prefetch_batches=2):
        break  # abandon with the pipeline full and the source mid-stream
    del it, b
    assert _wait_ingest_threads_gone(baseline), (
        f"leaked ingest threads: {_ingest_threads()}")


def test_early_abandon_dataset_iterator_stops_executor(ray_start):
    """Abandoning a Dataset-backed iterator must also wind down the
    streaming executor's control thread — its end-of-stream sentinel put
    must not block forever on the full, never-drained output queue."""
    baseline = len(_ingest_threads())
    it = rd.range(5000, parallelism=50).iterator()
    for b in it.iter_batches(batch_size=10, prefetch_batches=2):
        break
    del it, b
    assert _wait_ingest_threads_gone(baseline, timeout=20), (
        f"leaked ingest/executor threads: {_ingest_threads()}")


def test_early_abandon_jax_iterator_leaves_no_threads(ray_start):
    baseline = len(_ingest_threads())
    bundles = _bundles_from_blocks(30, 64)
    it = DataIterator(_source_of(bundles, delay_s=0.005))
    gen = it.iter_jax_batches(batch_size=64, prefetch_batches=2,
                              drop_last=False)
    next(gen)
    gen.close()  # train-failure path: the generator is closed explicitly
    del gen, it
    assert _wait_ingest_threads_gone(baseline), (
        f"leaked ingest threads: {_ingest_threads()}")


# -- device staging -----------------------------------------------------------


def test_iter_jax_batches_device_buffer_depth(ray_start):
    """The device-side buffer holds exactly prefetch_batches staged
    batches while the consumer is the slow stage (acceptance criterion:
    asserted via the stats report)."""
    import jax.numpy as jnp

    # the high-water mark needs the producer to outpace the consumer;
    # under suite load the producer threads can be starved, so escalate
    # the consumer's slowness until the buffer demonstrably fills
    d = None
    for step_s in (0.03, 0.1, 0.3):
        bundles = _bundles_from_blocks(10, 32)
        it = DataIterator(_source_of(bundles))
        total = 0.0
        for b in it.iter_jax_batches(batch_size=32, prefetch_batches=2,
                                     dtypes={"id": np.float32},
                                     drop_last=False):
            assert b["id"].dtype == jnp.float32
            total += float(b["id"].sum())
            time.sleep(step_s)  # slow consumer: buffer fills behind us
        assert total == float(np.arange(10 * 32).sum())
        d = it.ingest_stats.to_dict()
        assert d["device_buffer_capacity"] == 2
        assert d["h2d_s"] > 0.0
        if d["device_prefetch_depth"] == 2:
            break
    assert d["device_prefetch_depth"] == 2, (
        f"device buffer never reached its depth: {d}")


# -- local shuffle buffer -----------------------------------------------------


def test_shuffle_buffer_stays_topped_up():
    """Chunked sampling: the buffer never drains below min_rows while the
    stream is live (no full-drain latency spike), and every row comes out
    exactly once."""
    buf = _ShuffleBuffer(min_rows=64, seed=7, chunk_rows=16)
    out = []
    for i in range(12):
        block = batch_to_block({"id": np.arange(i * 16, (i + 1) * 16)})
        for sampled in buf.add(block):
            assert sampled.num_rows <= 16, "drained more than one chunk"
            assert buf._rows >= 64, "buffer drained below min_rows mid-stream"
            out.extend(sampled.column("id").to_pylist())
    for sampled in buf.flush():
        out.extend(sampled.column("id").to_pylist())
    assert sorted(out) == list(range(12 * 16))
    assert out != sorted(out), "buffer produced no shuffling"


def test_local_shuffle_through_iterator_complete_and_shuffled(ray_start):
    ds = rd.range(200, parallelism=4)
    ids = [v for b in ds.iter_batches(batch_size=20,
                                      local_shuffle_buffer_size=80,
                                      local_shuffle_seed=11)
           for v in b["id"].tolist()]
    assert sorted(ids) == list(range(200))
    assert ids != list(range(200))


def test_get_local_object_locations(ray_start):
    """The experimental no-RPC location probe backing the ingest ledger's
    cross-node accounting: sealed shm objects map to their node, inline
    objects to None."""
    from ray_tpu.experimental import get_local_object_locations

    big = ray_tpu.put(np.ones(512 * 1024, np.uint8))  # shm-resident
    small = ray_tpu.put(7)                            # inline
    # put() hands the location to the IO loop thread; a get() is queued
    # behind it, so the table is written by the time it returns
    ray_tpu.get([big, small])
    locs = get_local_object_locations([big, small])
    me = ray_tpu.get_runtime_context().get_node_id()
    assert locs[big] == me
    assert locs[small] is None


# -- locality-aware split routing (unit) --------------------------------------


def _forged_bundle(node: str, rows: int = 64, size: int = 4096):
    meta = BlockMetadata(num_rows=rows, size_bytes=size, exec_node_id=node)
    return RefBundle([(None, meta)])


def test_output_splitter_prefers_colocated_consumer():
    src = PhysicalOperator("src", [])
    sp = OutputSplitter(src, 2, locality_hints=["nodeA", "nodeB"])
    for node, want in (("nodeA", 0), ("nodeB", 1), ("nodeA", 0),
                       ("nodeB", 1)):
        b = _forged_bundle(node)
        sp.add_input(b)
        assert sp.queues[want][-1] is b, f"{node} misrouted"
    stats = sp.split_stats()
    assert stats["locality_hits"] == 4 and stats["locality_misses"] == 0
    # unknown producer falls back to fewest-rows, counted as a miss
    sp.add_input(_forged_bundle(None))
    assert sp.split_stats()["locality_misses"] == 1


def test_output_splitter_bounds_skew():
    """The co-located consumer is skipped once it runs ahead of the
    least-loaded one by more than the configured skew budget."""
    ctx = DataContext.get_current()
    saved = ctx.locality_split_max_skew_rows
    ctx.locality_split_max_skew_rows = 100
    try:
        src = PhysicalOperator("src", [])
        sp = OutputSplitter(src, 2, locality_hints=["nodeA", "nodeB"])
        for _ in range(4):  # all prefer rank 0; 64 rows each
            sp.add_input(_forged_bundle("nodeA"))
        stats = sp.split_stats()
        assert stats["rows_per_output"][1] > 0, (
            "skew bound never forced a spill to the other consumer")
        assert stats["locality_misses"] > 0
        assert max(stats["rows_per_output"]) - \
            min(stats["rows_per_output"]) <= 100 + 64
    finally:
        ctx.locality_split_max_skew_rows = saved


def test_ingest_telemetry_retires_on_final_publish(ray_start):
    """Per-iterator telemetry must not accumulate forever: the final
    publish drops the iterator's gauge tag series and sweeps KV records
    past the panel's stale window (incl. iterators that died silently)."""
    import json as json_mod

    from ray_tpu.data.iterator import IngestStats, _gauges
    from ray_tpu.experimental.internal_kv import (_internal_kv_get_prefix,
                                                  _internal_kv_put)

    stale = {"ts": time.time() - 3600, "iterator": "it-dead", "done": False}
    _internal_kv_put(b"iter/it-dead", json_mod.dumps(stale).encode(),
                     namespace="data")

    s = IngestStats()
    s._t_start -= 5.0  # old enough that the final publish isn't throttled
    s._publish_metrics(s.to_dict())
    g = _gauges["data_ingest_block_wait_s"]
    assert any(t.get("iterator") == s.iterator_id for t, _ in g.snapshot())

    s.maybe_publish(final=True)
    recs = _internal_kv_get_prefix("iter/", namespace="data")
    assert "iter/it-dead" not in recs, "stale record survived the sweep"
    assert f"iter/{s.iterator_id}" in recs, "final record must stay visible"
    assert not any(t.get("iterator") == s.iterator_id
                   for t, _ in g.snapshot()), "gauge series not retired"


def test_split_stats_merge_is_idempotent():
    """The coordinator's counters are cumulative totals — folding them in
    repeatedly (stats() per epoch, the periodic publish) must not
    multiply the reported hit rate."""
    from ray_tpu.data.iterator import IngestStats

    s = IngestStats()
    for _ in range(3):
        s.merge_split_stats({"locality_hits": 10, "locality_misses": 2})
    d = s.to_dict()
    assert d["locality_hits"] == 10 and d["locality_misses"] == 2


def test_streaming_split_rejects_bad_hints(ray_start):
    with pytest.raises(ValueError, match="locality_hints"):
        rd.range(10).streaming_split(2, locality_hints=["only-one"])


# -- locality-aware split (two real nodes) ------------------------------------


def test_streaming_split_locality_two_nodes(no_cluster):
    """With locality_hints on a two-node cluster, the majority of bundles
    route to their co-located consumer and the consumers pull measurably
    fewer cross-node bytes than the locality-free baseline (acceptance
    criterion)."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    # defined inside the test so cloudpickle ships them by value — the
    # cluster's workers cannot import the pytest-loaded test module
    @ray_tpu.remote
    class ShardConsumer:
        def consume(self, it):
            rows = 0
            for b in it.iter_batches(batch_size=64, prefetch_batches=2):
                rows += len(b["id"])
            return rows, it.ingest_stats.to_dict()

    def pad_payload(b):
        # ~256KB blocks: above the inline threshold, so cross-node pulls
        # are real transfers the ingest ledger can account
        return {"id": b["id"], "payload": np.ones((len(b["id"]), 512),
                                                  np.float64)}

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    try:
        cluster.connect()
        # separate session dir -> own shm arena: cross-node gets travel
        # the real chunked transfer plane
        worker = cluster.add_node(num_cpus=2, separate_session=True)
        cluster.wait_for_nodes()
        alive = [n["node_id"] for n in ray_tpu.nodes() if n["alive"]]
        worker_id = worker.node_id
        head_id = next(n for n in alive if n != worker_id)

        def run(hints):
            ds = rd.range(1024, parallelism=16).map_batches(pad_payload)
            its = ds.streaming_split(2, locality_hints=hints)
            actors = [
                ShardConsumer.options(
                    scheduling_strategy=NodeAffinitySchedulingStrategy(
                        node_id=nid, soft=False)).remote()
                for nid in (head_id, worker_id)]
            out = ray_tpu.get(
                [a.consume.remote(its[i]) for i, a in enumerate(actors)],
                timeout=180)
            for a in actors:
                ray_tpu.kill(a)
            rows = sum(r for r, _ in out)
            xnode = sum(s["bytes_cross_node"] for _, s in out)
            # splitter counters ride the terminal next_bundle reply into
            # each consumer's ingest stats (coordinator-global totals) —
            # a post-drain split_stats RPC would race the coordinator's
            # self-retirement timer, the old suite-load flake
            split = out[0][1]
            return rows, xnode, split

        rows, xnode_loc, split = run([head_id, worker_id])
        assert rows == 1024
        total = split["locality_hits"] + split["locality_misses"]
        assert total >= 16
        assert split["locality_hits"] > total / 2, (
            f"locality routing below majority: {split}")

        rows, xnode_base, _ = run(None)
        assert rows == 1024
        assert xnode_base > 0, (
            "locality-free baseline pulled nothing cross-node — "
            "the comparison is vacuous")
        assert xnode_loc < xnode_base, (
            f"locality hints did not reduce cross-node bytes "
            f"({xnode_loc} vs {xnode_base})")
    finally:
        cluster.shutdown()


# -- chaos: node death mid-lookahead ------------------------------------------


@pytest.mark.slow
def test_node_death_mid_lookahead_recovers_via_lineage(no_cluster):
    """The lookahead window holds refs whose only sealed copies live on a
    node that dies mid-iteration; the in-order get inside the prefetcher
    must fall back to lineage reconstruction on a replacement node and
    deliver every block's correct contents."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    try:
        cluster.connect()
        side = cluster.add_node(num_cpus=4, resources={"side": 4})
        cluster.wait_for_nodes()

        rows = 256

        @ray_tpu.remote(resources={"side": 1})
        def produce(i):
            return batch_to_block({
                "id": np.arange(i * rows, (i + 1) * rows),
                "payload": np.ones((rows, 512), np.float64)})

        n_blocks = 8
        refs = [produce.remote(i) for i in range(n_blocks)]
        # completion only — the sole sealed copies stay on the side node
        ready, _ = ray_tpu.wait(refs, num_returns=n_blocks, timeout=120,
                                fetch_local=False)
        assert len(ready) == n_blocks
        bundles = [
            RefBundle([(r, BlockMetadata(num_rows=rows,
                                         size_bytes=rows * 512 * 8))])
            for r in refs]

        ctx = DataContext.get_current()
        saved = (ctx.iterator_lookahead_bytes,
                 ctx.iterator_lookahead_max_blocks)
        # narrow window: only ~2 blocks are pulled ahead, so the node
        # dies while most of the stream is still remote-only
        ctx.iterator_lookahead_bytes = 1
        ctx.iterator_lookahead_max_blocks = 2
        try:
            it = DataIterator(_source_of(bundles))
            got = []
            for k, b in enumerate(it.iter_batches(batch_size=rows,
                                                  prefetch_batches=0)):
                got.extend(b["id"].tolist())
                if k == 0:
                    os.kill(side.proc.pid, signal.SIGKILL)
                    side.proc.wait(timeout=10)
                    # replacement capacity for the lineage re-execution
                    cluster.add_node(num_cpus=4, resources={"side": 4})
            assert got == list(range(n_blocks * rows))
        finally:
            (ctx.iterator_lookahead_bytes,
             ctx.iterator_lookahead_max_blocks) = saved
    finally:
        cluster.shutdown()


# -- the pipelined iterator overlaps a slow source with the consumer's step ---


def _ingest_once(bundles, *, pipelined, rows, block_delay_s, step_delay_s):
    """One pass over ``bundles`` through a DataIterator whose source
    sleeps ``block_delay_s`` a bundle (a slow upstream) and whose consumer
    sleeps ``step_delay_s`` a batch (a training step); forced serial =
    lookahead and prefetch off.  Returns (wall seconds, ingest ledger)."""
    def source():
        for b in bundles:
            time.sleep(block_delay_s)
            yield b

    ctx = DataContext.get_current()
    saved = ctx.iterator_lookahead_bytes
    ctx.iterator_lookahead_bytes = saved if pipelined else 0
    try:
        it = DataIterator(source)
        t0 = time.perf_counter()
        n = 0
        for _batch in it.iter_batches(
                batch_size=rows, prefetch_batches=2 if pipelined else 0):
            time.sleep(step_delay_s)
            n += 1
        assert n > 0
        return time.perf_counter() - t0, it.ingest_stats.to_dict()
    finally:
        ctx.iterator_lookahead_bytes = saved


def _compare_ingest(*, blocks, rows, block_delay_s, step_delay_s):
    rng = np.random.default_rng(0)
    bundles = []
    for _ in range(blocks):
        block = batch_to_block({"x": rng.standard_normal((rows, 8)),
                                "y": rng.integers(0, 10, rows)})
        bundles.append(RefBundle(
            [(ray_tpu.put(block), BlockMetadata.for_block(block))]))
    kw = dict(rows=rows, block_delay_s=block_delay_s,
              step_delay_s=step_delay_s)
    serial_wall, serial = _ingest_once(bundles, pipelined=False, **kw)
    pipe_wall, pipe = _ingest_once(bundles, pipelined=True, **kw)
    return {"speedup": serial_wall / pipe_wall,
            "serial_ingest": serial, "pipelined_ingest": pipe}


def test_pipelined_ingest_beats_forced_serial(ray_start):
    """Acceptance criterion: on a synthetic slow source the pipelined
    iterator sustains >= 1.5x the forced-serial throughput, and the stats
    ledger proves the overlap (consumer-blocked strictly below total
    block-fetch time)."""
    result = None
    for attempt in range(3):  # pipelining is timing-sensitive under load
        result = _compare_ingest(blocks=12, rows=256,
                                 block_delay_s=0.04, step_delay_s=0.04)
        if result["speedup"] >= 1.5:
            break
    assert result["speedup"] >= 1.5, result
    pipe = result["pipelined_ingest"]
    assert pipe["consumer_blocked_s"] < pipe["block_fetch_total_s"], (
        f"no overlap: consumer blocked {pipe['consumer_blocked_s']:.3f}s "
        f"vs fetch total {pipe['block_fetch_total_s']:.3f}s")
    # the serial baseline shows NO overlap (blocked >= source wait), so
    # the comparison above is meaningful
    serial = result["serial_ingest"]
    assert serial["consumer_blocked_s"] >= serial["source_wait_s"] * 0.9
