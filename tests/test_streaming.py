"""Streaming generator returns (VERDICT round-1 item #3).

Reference: ``num_returns="streaming"`` / ``ObjectRefGenerator``
(``python/ray/_raylet.pyx:279``) with consumer-driven backpressure.
"""

import time

import pytest

import ray_tpu
from ray_tpu.exceptions import TaskError


def test_basic_streaming(ray_isolated):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * i

    out = [ray_tpu.get(ref) for ref in gen.remote(5)]
    assert out == [0, 1, 4, 9, 16]


def test_streaming_is_incremental(ray_isolated):
    """Early items are consumable long before the generator finishes —
    the whole point vs materialize-then-return."""

    @ray_tpu.remote(num_returns="streaming")
    def slow_gen():
        for i in range(4):
            yield i
            if i < 3:
                time.sleep(1.5)

    it = slow_gen.remote()
    t0 = time.time()
    first = ray_tpu.get(next(it))
    first_latency = time.time() - t0
    assert first == 0
    rest = [ray_tpu.get(r) for r in it]
    total = time.time() - t0
    assert rest == [1, 2, 3]
    # first item arrived well before the ~4.5s full run completed
    assert first_latency < total - 1.0


def test_streaming_empty_and_error(ray_isolated):
    @ray_tpu.remote(num_returns="streaming")
    def empty():
        return
        yield  # pragma: no cover

    assert list(empty.remote()) == []

    @ray_tpu.remote(num_returns="streaming")
    def boom():
        yield 1
        yield 2
        raise RuntimeError("mid-stream failure")

    it = boom.remote()
    assert ray_tpu.get(next(it)) == 1
    assert ray_tpu.get(next(it)) == 2
    with pytest.raises(TaskError, match="mid-stream failure"):
        next(it)


def test_streaming_many_items_incrementally(ray_isolated):
    """The VERDICT acceptance shape: a 100-block producer consumed
    incrementally, with consumer-lag backpressure keeping the producer
    from racing unboundedly ahead."""

    @ray_tpu.remote(num_returns="streaming")
    def blocks():
        import os

        for i in range(100):
            yield (i, os.urandom(1024))

    opts = blocks.options(_generator_backpressure_num_objects=8)
    seen = []
    for ref in opts.remote():
        i, payload = ray_tpu.get(ref)
        seen.append(i)
        assert len(payload) == 1024
    assert seen == list(range(100))


def test_streaming_actor_method(ray_isolated):
    @ray_tpu.remote
    class Tokenizer:
        def stream(self, text):
            for tok in text.split():
                yield tok

        def ping(self):
            return "pong"

    t = Tokenizer.remote()
    assert ray_tpu.get(t.ping.remote()) == "pong"
    toks = [ray_tpu.get(r) for r in
            t.stream.options(num_returns="streaming").remote("a b c d")]
    assert toks == ["a", "b", "c", "d"]
    # actor is healthy and ordered afterwards
    assert ray_tpu.get(t.ping.remote()) == "pong"


def test_streaming_async_iteration(ray_isolated):
    import asyncio

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield from range(3)

    async def consume():
        out = []
        async for ref in gen.remote():
            out.append(await ref)
        return out

    from ray_tpu._private.worker import get_global_worker

    worker = get_global_worker()
    assert worker.run_coro(consume()) == [0, 1, 2]


def test_data_streaming_read_incremental(ray_isolated):
    """Data tier on streaming generators: blocks from ONE slow read task
    surface downstream before the task finishes (VERDICT item #3's Data
    acceptance shape)."""
    import pyarrow as pa

    from ray_tpu import data as rdata
    from ray_tpu.data.context import DataContext
    from ray_tpu.data.datasource import Datasource, ReadTask
    from ray_tpu.data.block import BlockMetadata

    class SlowBlocks(Datasource):
        def __init__(self, n_blocks, delay):
            self._n = n_blocks
            self._delay = delay

        def estimate_inmemory_data_size(self):
            return self._n * 8

        def get_read_tasks(self, parallelism):
            def read():
                for i in range(self._n):
                    if i:
                        time.sleep(self._delay)
                    yield pa.table({"v": [i]})

            return [ReadTask(read, BlockMetadata(
                num_rows=self._n, size_bytes=self._n * 8,
                schema=pa.schema([("v", pa.int64())])))]

    ctx = DataContext.get_current()
    old = ctx.execution_options.preserve_order
    ctx.execution_options.preserve_order = False
    try:
        ds = rdata.read_datasource(SlowBlocks(6, 0.8), parallelism=1)
        t0 = time.time()
        arrival = []
        values = []
        for batch in ds.iter_batches(batch_size=None):
            arrival.append(time.time() - t0)
            values.append(int(batch["v"][0]))
        assert sorted(values) == list(range(6))
        # first block consumable well before the ~4s full read finished
        assert arrival[0] < arrival[-1] - 1.0, arrival
    finally:
        ctx.execution_options.preserve_order = old


def test_serve_streaming_handle_and_sse(ray_isolated):
    """Serve over streaming generators: handle.remote_streaming yields
    items as the replica produces them, and the HTTP proxy exposes the
    same stream as Server-Sent Events."""
    import json
    import urllib.request

    from ray_tpu import serve

    @serve.deployment
    class Narrator:
        def __call__(self, body):
            for i in range(int(body.get("n", 3))):
                yield {"chunk": i}

    serve.run(Narrator.bind())
    handle = serve.get_deployment_handle("Narrator")
    items = list(handle.remote_streaming({"n": 4}))
    assert items == [{"chunk": 0}, {"chunk": 1}, {"chunk": 2}, {"chunk": 3}]

    serve.start(http_options={"host": "127.0.0.1", "port": 18437})
    with urllib.request.urlopen(
            "http://127.0.0.1:18437/Narrator?stream=1", timeout=60) as r:
        assert r.headers["Content-Type"].startswith("text/event-stream")
        body = r.read().decode()
    events = [json.loads(line[len("data: "):])
              for line in body.splitlines() if line.startswith("data: ")]
    assert events == [{"chunk": 0}, {"chunk": 1}, {"chunk": 2}]


def test_serve_stream_batch_is_taken_apart_by_the_handle(ray_isolated):
    """A generator method may yield the items it has ready at once as ONE
    ``serve.StreamBatch``; the handle (and so the SSE proxy) hands them
    out one by one, and a plain list stays one item."""
    import json
    import urllib.request

    from ray_tpu import serve

    serve.shutdown()  # an earlier test's proxy died with its cluster

    @serve.deployment
    class Windows:
        def __call__(self, body):
            yield {"chunk": 0}
            yield serve.StreamBatch({"chunk": i} for i in (1, 2, 3))
            yield serve.StreamBatch()
            yield [4, 5]

    serve.run(Windows.bind())
    want = [{"chunk": 0}, {"chunk": 1}, {"chunk": 2}, {"chunk": 3}, [4, 5]]
    handle = serve.get_deployment_handle("Windows")
    assert list(handle.remote_streaming({})) == want

    serve.start(http_options={"host": "127.0.0.1", "port": 18439})
    with urllib.request.urlopen(
            "http://127.0.0.1:18439/Windows?stream=1", timeout=60) as r:
        body = r.read().decode()
    assert [json.loads(line[len("data: "):]) for line in body.splitlines()
            if line.startswith("data: ")] == want
    serve.shutdown()


def test_llm_token_streaming(ray_isolated):
    """LLM serving streams tokens as decoded (VERDICT item #3's llm
    acceptance shape): chunks arrive with increasing indexes and the
    final summary matches the concatenated text."""
    import jax.numpy as jnp

    from ray_tpu import serve
    from ray_tpu.llm.serving import build_llm_deployment
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.tiny(num_layers=2, dtype=jnp.float32)
    serve.run(build_llm_deployment({"cfg": cfg, "batch_slots": 2,
                                    "max_len": 64}), name="llm")
    handle = serve.get_deployment_handle("LLMServer")
    chunks = list(handle.stream.remote_streaming(
        {"prompt": "hi", "max_tokens": 6, "temperature": 0.0}))
    assert chunks[-1].get("done") is True
    toks = [c for c in chunks if "token_id" in c]
    assert toks and [c["index"] for c in toks] == list(range(len(toks)))
    assert chunks[-1]["num_generated_tokens"] > 0
    # incremental chunks concatenate to exactly the final text
    assert chunks[-1]["generated_text"] == "".join(c["text"] for c in toks)


def test_llm_server_streams_a_window_as_one_batch():
    """A decode window's tokens of a request leave the replica as ONE
    ``serve.StreamBatch`` (one streamed return, not sixteen); taken apart
    they are the chunks a token-by-token stream gives."""
    from ray_tpu import serve
    from ray_tpu.llm.serving import LLMServer

    server = LLMServer._target({"model": "tiny", "batch_slots": 2,
                                "max_len": 96})
    try:
        body = {"prompt": "window", "max_tokens": 40, "temperature": 0.0}
        items = list(server.stream(body))
        assert any(isinstance(it, serve.StreamBatch) and len(it) > 1
                   for it in items)
        assert len(items) < 20  # 40 tokens in windows of 16, not 40 items
        chunks = [c for it in items for c in
                  (it if isinstance(it, serve.StreamBatch) else [it])]
        toks = [c for c in chunks if "token_id" in c]
        assert [c["index"] for c in toks] == list(range(len(toks)))
        done = chunks[-1]
        assert done["done"] and done == {**server(body), "done": True}
        assert "".join(c["text"] for c in toks) == done["generated_text"]
    finally:
        server._stop = True


def test_engine_host_teardown_never_blocks_the_collecting_thread(monkeypatch):
    """``_EngineHost.__del__`` runs wherever the collector does; its
    best-effort KV delete must not block that thread (it deadlocked a
    thread that held the span buffer's lock, which the RPC loop wanted)."""
    import threading
    import types

    from ray_tpu.experimental import internal_kv
    from ray_tpu.llm.serving import _EngineHost

    release, called = threading.Event(), []

    def blocking_del(key, namespace=None):
        called.append((key, threading.current_thread().name))
        release.wait(30)

    monkeypatch.setattr(internal_kv, "_internal_kv_del", blocking_del)
    host = types.SimpleNamespace(_deployment="d", _replica_id="r",
                                 _stop=False)
    t0 = time.monotonic()
    _EngineHost._teardown_engine_host(host)
    assert host._stop and time.monotonic() - t0 < 5.0
    for _ in range(100):
        if called:
            break
        time.sleep(0.05)
    release.set()
    assert called == [(b"engine/d/r", "llm-stats-drop")]
    # a half-built host (no replica id yet) is torn down without raising
    _EngineHost._teardown_engine_host(types.SimpleNamespace())


class _Bytes:
    def decode(self, ids):
        return bytes(ids).decode("utf-8", errors="replace")


class _Words:  # text that depends on what precedes: no leading space
    def decode(self, ids):
        return " ".join(str(i) for i in ids)


@pytest.mark.parametrize("tokenizer,ids", [
    (_Bytes(), list("h\u00e9llo \u20ac \U0001f600!".encode())),
    (_Bytes(), [0x61, 0x80, 0x80, 0x62, 0xE2, 0x82, 0x63, 0xF0, 0x9F]),
    (_Words(), [7, 11, 0, 255, 3, 3, 19]),
], ids=["multibyte", "invalid-bytes", "context-dependent"])
@pytest.mark.parametrize("window", [1, 4])
def test_stream_decodes_incrementally(tokenizer, ids, window):
    """``_stream_tokens`` decodes only the ids since the text it streamed
    last, yet its chunks (and the final flush) add up to the decode of the
    whole answer, and a multi-byte character is never cut."""
    import queue
    import threading
    import time
    import types

    from ray_tpu.llm.serving import _EngineHost

    full = tokenizer.decode(ids)
    host = types.SimpleNamespace(
        engine=types.SimpleNamespace(tokenizer=tokenizer),
        _loop=types.SimpleNamespace(is_alive=lambda: True),
        _deployment="d")
    slot = {"event": threading.Event(),
            "output": types.SimpleNamespace(error=None, text=full,
                                            token_ids=ids)}
    tq = queue.Queue()
    for a in range(1, len(ids), window):
        tq.put(ids[a:a + window])
    slot["event"].set()
    items = list(_EngineHost._stream_tokens(
        host, 0, slot, tq, time.time() + 60, ids[:1]))
    chunks = [c for it in items for c in (it if isinstance(it, list)
                                          else [it])]
    assert chunks[-1] == {"done": True, "generated_text": full,
                          "num_generated_tokens": len(ids)}
    texts = [c["text"] for c in chunks[:-1]]
    assert "".join(texts) == full
    assert [c["index"] for c in chunks[:-1]] == list(range(len(texts)))
    if "\ufffd" not in full:
        assert not any("\ufffd" in t for t in texts)


def test_streaming_generator_not_serializable(ray_isolated):
    @ray_tpu.remote(num_returns="streaming")
    def gen():
        yield 1

    it = gen.remote()
    import pickle

    with pytest.raises(TypeError, match="owner process"):
        pickle.dumps(it)
    list(it)  # drain
