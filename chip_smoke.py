"""Chip smoke: the train and serve loops, end to end, on the TPU.

    python chip_smoke.py                # one chip
    python chip_smoke.py --chips 4      # one four-chip host
    python chip_smoke.py --rehearse     # tiny shapes on the CPU, labelled so

The quickest proof that the system still starts on the chip.  It goes
through the entry points a user calls — ``ray_tpu.init()`` with the TPU
resource auto-detected, ``JaxTrainer.fit()``, ``serve.run`` behind the HTTP
proxy — at the full width of models the repo supports, with random weights
from a seed.  The driver (this process) never initializes a JAX backend:
on a TPU host that would take the chips away from the workers it starts.

Phases, each its own ``init()`` ... ``shutdown()``; the next one starts
only once no process of the previous is alive:

1. kernels + train: ``flash_attention`` forward and backward against
   ``reference_attention`` at three shapes, then 2 warm-up + 5 steps of the
   702M Llama-shaped configuration (``TRAIN_MODEL``, batch 16 x seq 1024)
   through ``JaxTrainer`` — with ``--chips 4`` on one chip, on four as
   ``fsdp``, and on four as ``fsdp_tp``;
2. serve: Llama-2-7B (bf16) behind ``serve`` over HTTP — one warm-up,
   8 concurrent requests, one streamed — with ``--chips 4`` as two
   one-chip replicas.

Exit code 0 and a last stdout line ``{"ok": true, "device": {...}}`` only
if every check passed on ``platform == "tpu"``.  No TPU means a non-zero
exit and no result; ``--rehearse`` is a debugging aid, never a fallback.
What it prints is smoke output, not a measurement.
"""

import argparse
import json
import math
import os
import signal
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

# 702M parameters (hidden 1536, 16 layers, 12 heads, MLP 6144, vocab
# 32000) at batch 16 x seq 1024: small enough to reach its first step in
# seconds on one chip, and the same on four.  The printed step time is a
# sign of life, not a record: the benchmark is ``cells/``.
TRAIN_MODEL = dict(vocab_size=32000, hidden_size=1536, num_layers=16,
                   num_heads=12, num_kv_heads=12, mlp_dim=6144,
                   max_seq_len=1024)
TRAIN_BATCH, TRAIN_SEQ = 16, 1024
WARMUP_STEPS, STEPS = 2, 5

# the published Llama-2-7B widths and depth in bf16: 13.5 GB of weights
# plus a 1.1 GB KV pool on a 16 GB chip
SERVE_ENGINE = {"model": "llama2_7b", "batch_slots": 8, "max_len": 256}
SERVE_VOCAB = 32000
PROMPT_TOKENS, NEW_TOKENS, CONCURRENT = 64, 32, 8
STREAM_PROMPT = (
    "The quick brown fox jumps over the lazy dog near the quiet river "
    "bank while seven bright lanterns sway above the old stone bridge")

# (name, batch, seq, q heads, kv heads, head dim): the train shape; a GQA
# shape (the n_rep reduction in the backward grid); a length that is not a
# multiple of the block (the pad-and-mask path)
KERNEL_SHAPES = [("mha_b16_s1024", 16, 1024, 12, 12, 128),
                 ("gqa_32q_8kv", 2, 1024, 32, 8, 128),
                 ("s1000_unaligned", 2, 1000, 12, 12, 128)]
# Max abs error over the tensor's scale (max(1, max|reference|)).  The
# reference is float32 math at "highest" matmul precision on the same
# bf16 inputs; the kernel rounds P to bf16 before PV and dS before the
# dQ/dK products, and its outputs to bf16 — a handful of 2^-8 (4e-3)
# roundings per element, so 2e-2 of scale.  (tests/test_ops.py holds the
# interpreter to the same figure.)
KERNEL_TOL = 2e-2
# Step-1 loss on four chips against one chip: same seeded params and
# global batch, so only the reduction order and bf16 matmul tiling
# differ — a few 1e-3 on a loss of ~10.4.
LOSS_TOL = 2e-2
# bytes_in_use across a mesh's devices: params, optimizer state and batch
# are all sharded, so the largest may exceed the smallest by this much
MEMORY_BAND = 1.25

REHEARSAL = dict(
    train_model=dict(vocab_size=256, hidden_size=64, num_layers=2,
                     num_heads=4, num_kv_heads=2, mlp_dim=128,
                     max_seq_len=128, dtype="float32"),
    train_seq=64,
    serve_engine={"model": "tiny", "batch_slots": 8, "max_len": 64},
    serve_vocab=256, prompt_tokens=16, new_tokens=8,
    stream_prompt="the quick brown fox",
    kernel_shapes=[("mha", 2, 128, 4, 4, 32), ("gqa", 2, 128, 4, 2, 32),
                   ("unaligned", 2, 100, 4, 4, 32)])


# --------------------------------------------------------------- worker side

def check_kernels(shapes):
    """Runs in a worker that holds a chip: flash fwd+bwd vs the reference."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import reference_attention
    from ray_tpu.ops.pallas.flash_attention import flash_attention

    on_tpu = jax.default_backend() == "tpu"
    dtype = jnp.bfloat16 if on_tpu else jnp.float32
    rows = []
    for name, b, s, h, kvh, d in shapes:
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32).astype(dtype)
        k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32).astype(dtype)
        v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32).astype(dtype)
        # a random cotangent: with loss = sum(out), dO is constant and a
        # transposed or mis-indexed dO block would go unnoticed
        w = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)

        def run(attn):
            def loss(q, k, v):
                out = attn(q, k, v)
                return jnp.sum(out.astype(jnp.float32) * w), out
            return jax.jit(jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True))

        flash = run(flash_attention)
        lowered = flash.lower(q, k, v).as_text()
        (_, out), grads = flash(q, k, v)
        with jax.default_matmul_precision("highest"):
            (_, out_r), grads_r = run(reference_attention)(
                *(x.astype(jnp.float32) for x in (q, k, v)))
        err = {}
        for label, a, r in zip(("out", "dq", "dk", "dv"),
                               (out, *grads), (out_r, *grads_r)):
            a, r = a.astype(jnp.float32), r.astype(jnp.float32)
            scale = jnp.maximum(1.0, jnp.max(jnp.abs(r)))
            err[label] = float(jnp.max(jnp.abs(a - r)) / scale)
        rows.append({"shape": name, "err": err,
                     "mosaic": "tpu_custom_call" in lowered})
    return {"platform": jax.default_backend(), "rows": rows}


def train_loop(config):
    """``train_loop_per_worker``: build, step, report once."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.models.training import default_optimizer, make_llama_trainer
    from ray_tpu.parallel.xla_warnings import sharding_warning_capture

    model = dict(config["model"])
    if "dtype" in model:
        model["dtype"] = jnp.dtype(model["dtype"])
    cfg = LlamaConfig(**model)
    mesh = train.get_context().get_mesh()
    t0 = time.perf_counter()
    with sharding_warning_capture() as warn:
        tr = make_llama_trainer(
            cfg, mesh, optimizer=default_optimizer(warmup=1,
                                                   decay_steps=1000))
        state = tr.init_state(jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (config["batch"], config["seq"] + 1), 0,
            cfg.vocab_size)
        batch = tr.shard_batch({"tokens": tokens})
        jax.block_until_ready((state, batch))
        init_s = time.perf_counter() - t0
        losses, step_s = [], []
        for _ in range(config["warmup"] + config["steps"]):
            t0 = time.perf_counter()
            state, m = tr.step(state, batch)
            jax.block_until_ready((state, m))
            step_s.append(time.perf_counter() - t0)
            losses.append(float(m["loss"]))
    timed = step_s[config["warmup"]:]
    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    train.report({
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": {a: int(n) for a, n in mesh.shape.items()},
        "losses": losses,
        "step_ms": [round(s * 1e3, 1) for s in timed],
        "step_ms_median": round(float(np.median(timed)) * 1e3, 1),
        # first call of the step: trace + compile (or cache read) + run
        "compile_s": round(step_s[0] - float(np.median(timed)), 2),
        "init_s": round(init_s, 2),
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        "resharding_warnings": warn["count"],
        "mosaic_flash_calls": tr.compile(state, batch).as_text().count(
            "tpu_custom_call"),
    })


# --------------------------------------------------------------- driver side

class Smoke:
    def __init__(self, chips: int, rehearse: bool):
        self.chips = chips
        self.rehearse = rehearse
        self.failures = []
        self.device = None  # as the first worker that held a chip saw it

    def check(self, ok, what: str):
        print(("  ok   " if ok else "  FAIL ") + what, flush=True)
        if not ok:
            self.failures.append(what)

    # -- cluster lifetime ---------------------------------------------------

    def start(self):
        import ray_tpu

        if self.rehearse:
            ray_tpu.init(num_cpus=8)
            return
        ray_tpu.init()  # the TPU resource comes from detection
        found = ray_tpu.cluster_resources().get("TPU", 0)
        if found < self.chips:
            ray_tpu.shutdown()
            raise SystemExit(
                f"chip_smoke: detected {found:g} TPU chip(s), need "
                f"{self.chips} — no result (use --rehearse to debug on "
                f"the CPU)")

    def stop(self):
        """shutdown(), then wait until every process of the session is
        gone: the next phase's workers need its chips."""
        import ray_tpu

        session = ray_tpu._node_services.session_dir
        ray_tpu.shutdown()
        deadline = time.monotonic() + 30
        while (pids := _session_pids(session)) and \
                time.monotonic() < deadline:
            time.sleep(0.2)
        for pid in pids:
            os.kill(pid, signal.SIGKILL)
        self.check(not pids, f"no process of the session outlives "
                   f"shutdown() (left: {pids})")

    # -- phase 1: kernels + train -------------------------------------------

    def kernels(self):
        import ray_tpu

        shapes = REHEARSAL["kernel_shapes"] if self.rehearse \
            else KERNEL_SHAPES
        task = ray_tpu.remote(check_kernels).options(
            num_tpus=0 if self.rehearse else 1)
        got = ray_tpu.get(task.remote(shapes), timeout=600)
        for row in got["rows"]:
            worst = max(row["err"].values())
            print(f"  kernel {row['shape']}: err/scale "
                  + " ".join(f"{k}={v:.1e}" for k, v in row["err"].items()),
                  flush=True)
            self.check(worst <= KERNEL_TOL and math.isfinite(worst),
                       f"flash_attention {row['shape']} agrees with the "
                       f"reference (worst {worst:.1e} <= {KERNEL_TOL})")
            if not self.rehearse:
                self.check(row["mosaic"], f"flash_attention "
                           f"{row['shape']} lowered to a Mosaic call")
        if not self.rehearse:
            self.check(got["platform"] == "tpu",
                       f"kernel task ran on tpu ({got['platform']})")

    def train(self, preset: str, chips: int):
        from ray_tpu.parallel.mesh import MESH_AXES, resolve_mesh_config
        from ray_tpu.train import JaxTrainer, ScalingConfig

        tag = f"train[{preset} x{chips}]"
        config = dict(
            model=REHEARSAL["train_model"] if self.rehearse else TRAIN_MODEL,
            batch=TRAIN_BATCH,
            seq=REHEARSAL["train_seq"] if self.rehearse else TRAIN_SEQ,
            warmup=WARMUP_STEPS, steps=STEPS)
        result = JaxTrainer(
            train_loop, train_loop_config=config,
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=not self.rehearse,
                chips_per_worker=chips, mesh=preset)).fit()
        if result.error is not None:
            self.check(False, f"{tag} fit() failed: {result.error!r}")
            return None
        m = result.metrics
        print(f"  {tag}: {m['platform']} {m['device_kind']!r} "
              f"x{m['device_count']} mesh="
              f"{ {a: n for a, n in m['mesh'].items() if n > 1} or 1} "
              f"init {m['init_s']}s compile {m['compile_s']}s step "
              f"{m['step_ms_median']} ms {m['step_ms']} loss "
              f"{m['losses'][0]:.4f}->{m['losses'][-1]:.4f} peak HBM "
              f"{_gb(m['peak_bytes_in_use'])} GB", flush=True)
        want = dict(zip(MESH_AXES, resolve_mesh_config(preset).resolve(
            chips if not self.rehearse else m["device_count"])))
        self.check(m["mesh"] == want,
                   f"{tag} mesh is the one requested ({m['mesh']})")
        self.check(all(math.isfinite(x) for x in m["losses"])
                   and m["losses"][-1] < m["losses"][0],
                   f"{tag} loss finite and falling")
        self.check(m["resharding_warnings"] == 0,
                   f"{tag} compiled with no resharding warning "
                   f"({m['resharding_warnings']})")
        if self.rehearse:
            return m
        self.check(m["platform"] == "tpu" and m["device_count"] == chips,
                   f"{tag} ran on {chips} tpu device(s)")
        # the forward and the backward kernel: interpret mode or the jnp
        # reference would leave no custom call in the compiled step
        self.check(m["mosaic_flash_calls"] >= 2,
                   f"{tag} compiled step holds the Mosaic flash kernels "
                   f"({m['mosaic_flash_calls']} custom calls)")
        used = [b for b in m["bytes_in_use"] if b]
        self.check(len(used) == chips
                   and max(used) <= MEMORY_BAND * min(used),
                   f"{tag} bytes_in_use balanced over devices "
                   f"({_gb(m['bytes_in_use'])} GB)")
        if self.device is None:
            self.device = {"platform": m["platform"],
                           "kind": m["device_kind"],
                           "count": m["device_count"]}
        return m

    def phase_train(self):
        print("phase 1: kernels + train", flush=True)
        self.start()
        try:
            self.kernels()
            if self.chips == 1:
                self.train("fsdp", 1)
                return
            one = self.train("fsdp", 1)
            self.device = None  # report the four-chip view
            for preset in ("fsdp", "fsdp_tp"):
                m = self.train(preset, self.chips)
                if one and m:
                    gap = abs(m["losses"][0] - one["losses"][0])
                    self.check(gap <= LOSS_TOL,
                               f"train[{preset} x{self.chips}] step-1 "
                               f"loss matches one chip (|d|={gap:.1e} "
                               f"<= {LOSS_TOL})")
        finally:
            self.stop()

    # -- phase 2: serve -----------------------------------------------------

    def phase_serve(self):
        import numpy as np

        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.llm import build_llm_deployment
        from ray_tpu.llm.bpe import BPETokenizer
        from ray_tpu.serve.controller import get_controller

        print("phase 2: serve", flush=True)
        r = REHEARSAL if self.rehearse else None
        engine = r["serve_engine"] if r else SERVE_ENGINE
        vocab = r["serve_vocab"] if r else SERVE_VOCAB
        n_prompt = r["prompt_tokens"] if r else PROMPT_TOKENS
        n_new = r["new_tokens"] if r else NEW_TOKENS
        stream_prompt = r["stream_prompt"] if r else STREAM_PROMPT
        replicas = 2 if self.chips > 1 else 1
        # prompts are token ids from a seed, distinct from their first
        # token on (no shared prefix: one prefill shape); the streamed one
        # is text, sized into the same prefill bucket, so the tracked BPE
        # vocabulary is on the path
        rng = np.random.default_rng(0)
        prompts = rng.integers(3, vocab, (CONCURRENT + 1, n_prompt)).tolist()
        n_stream = len(BPETokenizer().encode(stream_prompt))
        self.check(self.rehearse or n_prompt // 2 < n_stream <= n_prompt,
                   f"streamed prompt ({n_stream} tokens) shares the "
                   f"{n_prompt}-token prefill bucket")

        self.start()
        try:
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                port = s.getsockname()[1]
            url = f"http://127.0.0.1:{port}/llm"
            # the first request waits out weight init and the compiles
            serve.start(http_options={"host": "127.0.0.1", "port": port,
                                      "request_timeout_s": 900.0})
            serve.run(build_llm_deployment(
                engine, num_replicas=replicas,
                num_tpus_per_replica=0 if self.rehearse else 1),
                route_prefix="/llm")

            def body(prompt):
                return {"prompt": prompt, "max_tokens": n_new,
                        "temperature": 0.0}

            t0 = time.monotonic()
            code, out = _post(url, body(prompts[0]))
            warm_s = time.monotonic() - t0
            self.check(code == 200 and out.get("num_generated_tokens")
                       == n_new, f"warm-up request: {code} {out}")

            answers = [None] * CONCURRENT
            def ask(i):
                answers[i] = _post(url, body(prompts[i + 1]))
            threads = [threading.Thread(target=ask, args=(i,))
                       for i in range(CONCURRENT)]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            burst_s = time.monotonic() - t0
            good = [a for a in answers if a and a[0] == 200
                    and a[1].get("num_generated_tokens") == n_new]
            self.check(len(good) == CONCURRENT,
                       f"{CONCURRENT} concurrent requests each returned "
                       f"200 with {n_new} tokens ({len(good)} did: "
                       f"{[a for a in answers if a not in good][:2]})")

            events = _post_sse(url + "?stream=1&method=stream",
                               body(stream_prompt))
            done = events[-1] if events else {}
            self.check(done.get("done") is True
                       and done.get("num_generated_tokens") == n_new,
                       f"streamed request yielded {n_new} tokens "
                       f"({len(events) - 1} text events, last: {done})")
            print(f"  serve: warm-up {warm_s:.1f}s (weights + compiles), "
                  f"{CONCURRENT} concurrent in {burst_s:.2f}s", flush=True)

            # each replica, asked directly: what it holds, what it served
            info = ray_tpu.get(get_controller().get_deployment_info.remote(
                "LLMServer"), timeout=30)
            seen_chips = []
            for rep in info["replicas"]:
                served = ray_tpu.get(rep.stats.remote(), timeout=30)["total"]
                st = ray_tpu.get(rep.handle_request.remote("stats", (), {}),
                                 timeout=60)
                devs = st["devices"]
                print(f"  replica {st['replica']}: served {served}, "
                      f"devices {devs}", flush=True)
                self.check(served > 0, f"replica {st['replica']} answered")
                if self.rehearse:
                    continue
                self.check(len(devs) == 1 and devs[0]["kind"] == "tpu"
                           and (devs[0].get("peak_bytes_in_use") or 0) > 0,
                           f"replica {st['replica']} holds one tpu device "
                           f"and reports peak HBM "
                           f"({_gb([d.get('peak_bytes_in_use') for d in devs])}"
                           f" GB)")
                seen_chips.append(devs[0]["chips"])
            self.check(len(info["replicas"]) == replicas,
                       f"{replicas} replica(s) up")
            if replicas > 1 and not self.rehearse:
                self.check(len(set(seen_chips)) == replicas,
                           f"replicas hold different chips ({seen_chips})")
            serve.shutdown()
        finally:
            self.stop()


def _gb(values):
    return [round(v / 1e9, 2) if v else None for v in values]


def _post(url, body, timeout=900.0):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode("utf-8", "replace")[:500]}
    except OSError as e:
        return 0, {"error": repr(e)}


def _post_sse(url, body, timeout=900.0):
    """POST, read the Server-Sent Events to the end, return their data."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    events = []
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            for line in resp:
                if line.startswith(b"data: "):
                    events.append(json.loads(line[6:]))
    except OSError as e:
        events.append({"error": repr(e)})
    return events


def _session_pids(session_dir: str):
    """Live processes started for one session: the head names the session
    directory on its command line; the zygote — and every worker forked
    from it — in its environment."""
    needle, pids = session_dir.encode(), []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            with open(f"/proc/{entry}/cmdline", "rb") as f:
                blob = f.read()
            with open(f"/proc/{entry}/environ", "rb") as f:
                blob += f.read()
            with open(f"/proc/{entry}/stat", "rb") as f:
                zombie = f.read().rsplit(b")", 1)[1].split()[0] == b"Z"
        except OSError:
            continue
        if needle in blob and not zombie:
            pids.append(int(entry))
    return pids


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on JAX_PLATFORMS=cpu: a debugging "
                         "aid, labelled as such, never a fallback")
    args = ap.parse_args()
    if args.rehearse:
        print("REHEARSAL on the CPU at toy shapes: not a chip result",
              flush=True)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")

    from ray_tpu._private import native_store
    from ray_tpu._private.accelerators import jax_backend_initialized
    from ray_tpu._private.node import ensure_compile_cache_env

    def on_alarm(signum, frame):
        raise TimeoutError("chip_smoke exceeded its time limit")
    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(1150)  # the contract allows 1200 s, compiles included

    t0 = time.monotonic()
    smoke = Smoke(args.chips, args.rehearse)
    print(f"compile cache: {ensure_compile_cache_env()}", flush=True)
    smoke.check(native_store.available(),
                "native object store built from the tracked sources")
    smoke.phase_train()
    smoke.phase_serve()
    smoke.check(not jax_backend_initialized(),
                "the driver never initialized a JAX backend")
    print(f"total {time.monotonic() - t0:.0f}s", flush=True)
    if smoke.failures:
        print(json.dumps({"ok": False, "failures": smoke.failures}))
        return 1
    if args.rehearse:
        print(json.dumps({"ok": True, "rehearsal": True}))
        return 0
    print(json.dumps({"ok": True, "device": smoke.device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
