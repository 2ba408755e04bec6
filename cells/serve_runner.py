"""Runner for a served traffic mix: ``serve.run(build_llm_deployment(...))``
behind the HTTP proxy, load from this (the driving) process.

The untraced run deploys exactly what a user deploys.  The traced run
deploys a subclass that adds ``start_profile``/``stop_profile`` and
nothing else (``traced_server.py``): the profiler has to live in the
process that holds the chip, and the program has no hook for one.
"""

import os
import socket
import threading
import time

from cells import loadgen

# the first request of a cold replica waited 110 s for weights and
# compiles at 7B (PR 21); every prefill bucket compiles behind it
WARMUP_REQUEST_LIMIT_S = 900.0
REPLICA_CALL_LIMIT_S = 120.0
DRAIN_LIMIT_S = 90.0
REFERENCE_LIMIT_S = 900.0


# ------------------------------------------------------- on the chip, after

def reference_check(family, model, seed, samples, pad_to):
    """Runs in a task that holds the chip after ``serve.shutdown()``: the
    same seeded weights, the family's plain reference over prompt +
    returned tokens, and for every returned token how far its reference
    logit lies under that position's largest."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cells import families

    fam = families.load(family)
    reference = fam.reference()
    params = fam.init(jax.random.PRNGKey(seed), fam.config(model))

    @jax.jit
    def gaps(params, tokens):
        lg = reference.logits(params, tokens[:-1], model)
        chosen = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - chosen

    rows = []
    for prompt, ids in samples:
        seq = np.zeros(pad_to + 1, np.int32)
        n, m = len(prompt), len(ids)
        seq[:n + m] = prompt + ids
        g = np.asarray(gaps(params, jnp.asarray(seq)))[n - 1:n + m - 1]
        rows.append({"n_prompt": n, "n_out": m,
                     "worst_gap": float(g.max()),
                     "mean_gap": float(g.mean()),
                     "exact": int((g == 0).sum())})
    return {"platform": jax.devices()[0].platform, "rows": rows}


# ------------------------------------------------------------ the runner

class ServeRun:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cluster = ctx["cluster"]
        self.traffic = ctx["traffic"]
        self.model = ctx["model"]
        self.family = ctx["family"]
        self.engine = dict(ctx["engine"])
        self.rehearse = ctx["rehearse"]
        self.seed = ctx["seed"] % (2 ** 31 - 1)
        self.replica = None

    # -- deployment ---------------------------------------------------------

    def deploy(self, traced: bool):
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.llm import build_llm_deployment
        from ray_tpu.serve.controller import get_controller

        from cells.tokenizer import IdTokenizer

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        self.addr = ("127.0.0.1", port, "/llm")
        serve.start(http_options={
            "host": "127.0.0.1", "port": port,
            "request_timeout_s": WARMUP_REQUEST_LIMIT_S})
        self.cluster.serve_started = True
        kwargs = dict(self.engine, cfg=self.family.config(self.model),
                      seed=self.seed,
                      tokenizer=IdTokenizer(self.model["vocab_size"]))
        app = build_llm_deployment(
            kwargs, num_tpus_per_replica=0 if self.rehearse else 1)
        if traced:
            from cells.traced_server import traced
            app.deployment._target = traced(app.deployment._target)
        serve.run(app, route_prefix="/llm")
        info = ray_tpu.get(get_controller().get_deployment_info.remote(
            app.deployment.name), timeout=REPLICA_CALL_LIMIT_S)
        self.replica = info["replicas"][0]

    def call(self, method, *args):
        import ray_tpu

        return ray_tpu.get(
            self.replica.handle_request.remote(method, args, {}),
            timeout=REPLICA_CALL_LIMIT_S)

    # -- warm-up ------------------------------------------------------------

    def warm_up(self):
        """One request per prefill bucket and per decode-window length the
        traffic can reach (the engine compiles one program per power-of-two
        prompt length and one small one per window length), then the mix's
        own traffic for ``ramp_s`` so the window starts in steady state."""
        import numpy as np

        w = self.traffic["warmup"]
        rng = np.random.default_rng(self.seed + 1)
        vocab = self.model["vocab_size"]
        t0 = time.monotonic()
        lens = [(b, w["tokens"]) for b in w["prompt_lengths"]]
        lens += [(w["prompt_lengths"][0], k) for k in w["window_lengths"]]
        for i, (n_prompt, n_out) in enumerate(lens):
            req = {"i": -1 - i, "max_tokens": n_out,
                   "prompt": rng.integers(0, vocab, n_prompt).tolist()}
            rec = loadgen.send(*self.addr, req, self.traffic["stream"],
                               WARMUP_REQUEST_LIMIT_S, time.monotonic)
            if not rec["ok"]:
                raise RuntimeError(f"warm-up request {n_prompt}+{n_out} "
                                   f"failed: {rec['error']}")
            if i == 0:
                print(f"cells: first request (weights, first compiles) "
                      f"{time.monotonic() - t0:.1f}s", flush=True)
        print(f"cells: {len(lens)} warm-up requests "
              f"{time.monotonic() - t0:.1f}s", flush=True)

    # -- the window ---------------------------------------------------------

    def run_window(self, seconds, traced, trace_dir):
        traffic = self.traffic
        ramp = float(traffic["warmup"]["ramp_s"])
        reqs = loadgen.make_requests(traffic, self.ctx["seed"],
                                     self.model["vocab_size"],
                                     ramp + seconds)
        load = loadgen.Load(traffic, reqs, *self.addr)
        polls, stop_poll = [], threading.Event()
        cache_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]

        def cache_entries():
            try:
                return {f for f in os.listdir(cache_dir)
                        if f.endswith("-cache")}
            except OSError:
                return set()

        load.start(until_s=ramp + seconds)
        w0 = load.t0 + ramp
        w1 = w0 + seconds
        time.sleep(max(0.0, w0 - time.monotonic()))
        wall_window = time.time()
        cache_before = cache_entries()

        def poll():
            while not stop_poll.wait(0.5):
                t = time.monotonic()
                st = self.call("stats")
                polls.append({"t": t, "queued": st["queued"],
                              "slot_occupancy": st["slot_occupancy"],
                              "blocks_total": st["blocks_total"],
                              "blocks_used": st["blocks_total"]
                              - st["blocks_available"]})
        if traced:
            threading.Thread(target=poll, daemon=True).start()
            tr = traffic["trace"]
            time.sleep(max(0.0, w0 + tr["start_s"] - time.monotonic()))
            self.call("start_profile", trace_dir)
            time.sleep(tr["seconds"])
            self.call("stop_profile")
        time.sleep(max(0.0, w1 - time.monotonic()))
        stop_poll.set()
        new_programs = sorted(cache_entries() - cache_before)
        # open loop: arrivals ended at w1.  Closed loop: no client sends
        # again, and what is in flight comes back, because its tokens are
        # pro-rated to the window (loadgen.tokens_in_window)
        load.stop()
        stats = self.call("stats")
        drained = load.join(DRAIN_LIMIT_S)
        records = load.snapshot()
        return {"records": records, "w0": w0, "w1": w1, "drained": drained,
                "wall_window": wall_window, "polls": polls,
                "new_programs": new_programs, "stats": stats, "load": load}

    # -- after --------------------------------------------------------------

    def check_answers(self, good, sent):
        import ray_tpu

        ref = self.traffic["reference"]
        picked = sorted(good, key=lambda r: r["i"])[:ref["requests"]]
        by_i = {r["i"]: r for r in sent}
        samples = [(by_i[r["i"]]["prompt"], r["ids"]) for r in picked]
        task = ray_tpu.remote(reference_check).options(
            num_tpus=0 if self.rehearse else 1)
        return ray_tpu.get(task.remote(
            self.ctx["config"]["family"], self.model, self.seed, samples,
            self.engine["max_len"]),
            timeout=REFERENCE_LIMIT_S)


def run(ctx):
    """One run of a serve cell; returns the runner's result for run.py."""
    from ray_tpu import serve

    sr = ServeRun(ctx)
    traced = bool(ctx["trace"])
    t0 = time.monotonic()
    sr.deploy(traced)
    sr.warm_up()
    print(f"cells: deployed and warm {time.monotonic() - t0:.1f}s",
          flush=True)
    win = sr.run_window(ctx["seconds"], traced, ctx["trace_dir"])
    red = loadgen.reduce_window(win["records"], sr.traffic,
                                win["w0"], win["w1"])
    print("cells: " + describe(red, ctx["seconds"]), flush=True)
    st = win["stats"]
    print(f"cells: at the window's end {st['slots_used']} slots busy, "
          f"{st['queued']} queued, "
          f"{st['blocks_total'] - st['blocks_available']} of "
          f"{st['blocks_total']} cache blocks held", flush=True)
    print(f"cells: blocks free {st['blocks_free']}, cached "
          f"{st['blocks_cached']}; prefix cache {st['prefix_cache']}",
          flush=True)
    errors = sorted({r["error"] for r in win["records"] if r["error"]})
    print(f"cells: {len(win['records'])} requests came back, "
          f"{red['attempted']} in the window, {red['failed']} failed"
          + (f"; errors: {errors[:3]}" if errors else ""), flush=True)
    serve.shutdown()
    sr.cluster.serve_started = False
    check = sr.check_answers(red["good"], win["load"].reqs) \
        if red["good"] else None
    dev = win["stats"]["devices"]
    out = {
        "kind": "serve", "attempted": red["attempted"],
        "failed": red["failed"], "window_s": ctx["seconds"],
        "wall_window": win["wall_window"], "reduced": red,
        "polls": [p for p in win["polls"]
                  if win["w0"] <= p["t"] < win["w1"]],
        "new_programs": win["new_programs"], "drained": win["drained"],
        "reference": check,
        "device": {"platform": dev[0]["kind"] if dev else None,
                   "kind": dev[0]["device_kind"] if dev else None,
                   "count": len(dev),
                   "memory_peak_bytes": max(
                       (d.get("peak_bytes_in_use") or 0 for d in dev),
                       default=0)},
        "engine_stats": {k: win["stats"][k] for k in (
            "queued", "slots_used", "blocks_total", "blocks_available",
            "prefix_cache")},
    }
    return out


def describe(red, seconds):
    """What else the window showed, for the lines before the last."""
    q = loadgen.quantile
    if red.get("ttft_ms"):
        norm = red["norm_latency_ms"]
        return (f"TTFT p50 {q(red['ttft_ms'], 0.5):.0f} p95 "
                f"{q(red['ttft_ms'], 0.95):.0f} ms, TPOT p50 "
                f"{q(red['tpot_ms'], 0.5):.1f} p95 "
                f"{q(red['tpot_ms'], 0.95):.1f} ms, latency a token mean "
                f"{sum(norm) / len(norm):.1f} ms over "
                f"{len(red['ttft_ms'])} requests; "
                f"{red['output_tokens'] / seconds:.1f} tokens/s; the "
                f"generator ran late by p95 "
                f"{q(red['lag_ms'], 0.95):.1f} ms")
    if "output_tokens_at_completion" in red:
        return (f"{red['output_tokens'] / seconds:.1f} tokens/s pro-rated "
                f"to the window, "
                f"{red['output_tokens_at_completion'] / seconds:.1f} "
                f"counted at completion")
    return f"{red['output_tokens'] / seconds:.1f} tokens/s"
