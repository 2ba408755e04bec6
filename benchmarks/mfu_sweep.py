"""MFU lever sweep (VERDICT r4 weak #5 / next #8): the three cheapest
untried levers, each measured on the real chip against the bench.py
baseline config —

  1. remat 'save_attn_mlp' (save the swiglu activation too: backward
     stops replaying the gate/up matmuls);
  2. gradient accumulation at larger EFFECTIVE batch (activation memory
     stays per-microbatch);
  3. int8 embedding gather (micro-benchmark of the lookup itself —
     training-step embedding cost is bounded first, so the micro result
     bounds the whole lever).

Usage: python benchmarks/mfu_sweep.py [--steps 8]
Prints one JSON line per configuration.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ray_tpu._private.bench_emit import emit_final_record, emit_record_line

import jax
import jax.numpy as jnp


def step_time(tr, state, batch, steps: int):
    """Chained-steps slope timing: two run lengths with one host
    readback each, so the fixed per-run cost cancels."""
    for _ in range(2):  # compile + settle
        state, m = tr.step(state, batch)
        float(m["loss"])

    def run(n):
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(n):
            state, m = tr.step(state, batch)
        float(m["loss"])
        return time.perf_counter() - t0

    n1, n2 = max(steps // 4, 1), steps
    t1, t2 = run(n1), run(n2)
    return (t2 - t1) / (n2 - n1), state


def run_cfg(name, cfg, batch, seq, steps, accum=1, extra=None):
    import sys

    sys.path.insert(0, ".")
    from bench import peak_flops_per_chip, train_flops_per_step
    from ray_tpu.models.training import default_optimizer, make_llama_trainer
    from ray_tpu.parallel import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(dp=-1))
    tr = make_llama_trainer(
        cfg, mesh, optimizer=default_optimizer(warmup=1, decay_steps=1000),
        accum_steps=accum)
    state = tr.init_state(jax.random.PRNGKey(0))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)
    b = tr.shard_batch({"tokens": tokens})
    try:
        dt, state = step_time(tr, state, b, steps)
    except Exception as e:  # noqa: BLE001 — OOM/compile reject is a RESULT
        emit_record_line({"config": name, "error": repr(e)[:300]})
        return
    flops = train_flops_per_step(cfg, batch, seq)
    mfu = flops / dt / peak_flops_per_chip()
    emit_record_line({
        "config": name, "batch": batch, "seq": seq, "accum": accum,
        "step_ms": round(dt * 1e3, 1), "mfu_pct": round(mfu * 100, 2),
        "tokens_per_s": round(batch * seq / dt),
    })
    del tr, state, b


def int8_gather_micro(steps=20):
    """The embedding-gather lever in isolation: bf16 table gather vs
    int8 table gather + dequant, at bench shapes."""
    vocab, hidden, b, s = 32000, 1536, 16, 1024
    key = jax.random.PRNGKey(0)
    table = jax.random.normal(key, (vocab, hidden), jnp.bfloat16)
    scale = jnp.max(jnp.abs(table), axis=1, keepdims=True).astype(
        jnp.float32) / 127.0
    table_q = jnp.clip(
        table.astype(jnp.float32) / scale, -127, 127).astype(jnp.int8)
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, vocab)

    @jax.jit
    def bf16_gather(t, ix):
        return t[ix].astype(jnp.bfloat16).sum()

    @jax.jit
    def int8_gather(tq, sc, ix):
        return (tq[ix].astype(jnp.bfloat16)
                * sc[ix].astype(jnp.bfloat16)).sum()

    def timeit(fn, *args):
        float(fn(*args))  # compile
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        float(out)
        return (time.perf_counter() - t0) / steps

    t_bf16 = timeit(bf16_gather, table, toks)
    t_int8 = timeit(int8_gather, table_q, scale, toks)
    emit_record_line({
        "config": "embed_gather_micro",
        "bf16_ms": round(t_bf16 * 1e3, 3),
        "int8_ms": round(t_int8 * 1e3, 3),
        "speedup": round(t_bf16 / t_int8, 2),
    })


def multichip_sweep():
    """Sweep every ScalingConfig mesh preset over all visible devices
    through the trainer path (bench.run_multichip): one JSON line per
    preset with the mesh it resolved to, MFU / tokens/s, the per-preset
    SPMD resharding-warning count and the step-time breakdown — the
    sweep shows at a glance which mesh layouts are CLEAN, not just
    which are fast."""
    import sys

    sys.path.insert(0, ".")
    from bench import run_multichip
    from ray_tpu.parallel import MESH_PRESETS

    for preset in sorted(MESH_PRESETS):
        rec = run_multichip(preset=preset)
        d = rec["detail"]
        bd = d.get("step_time_breakdown") or {}
        emit_record_line({
            "config": f"multichip_{preset}",
            "metric": rec["metric"], "value": rec["value"],
            "unit": rec["unit"],
            "mesh": d.get("mesh"),
            "tokens_per_s": d.get("tokens_per_s"),
            "step_ms": d.get("step_ms"),
            "xla_sharding_warnings": d.get("xla_sharding_warnings"),
            "step_time_breakdown": {
                "buckets_s": bd.get("buckets_s"),
                "coverage": bd.get("coverage"),
                "step_wall_s": bd.get("step_wall_s"),
            } if bd and "error" not in bd else bd,
            "sharding_ab": d.get("sharding_ab"),
        })


def main():
    import dataclasses

    from ray_tpu.models.llama import LlamaConfig

    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument(
        "--multichip", action="store_true",
        help="sweep mesh presets over all visible devices via the "
             "sharded trainer path instead of the single-chip levers")
    args = ap.parse_args()

    if args.multichip:
        multichip_sweep()
        emit_final_record({"benchmark": "mfu_sweep",
                           "mode": "multichip", "done": True})
        return

    base = LlamaConfig(
        vocab_size=32000, hidden_size=1536, num_layers=16, num_heads=12,
        num_kv_heads=12, mlp_dim=6144, max_seq_len=1024,
    )
    seq = 1024
    # 1) baseline (bench.py config)
    run_cfg("baseline_b16", base, 16, seq, args.steps)
    # 2) remat variant
    run_cfg("save_attn_mlp_b16",
            dataclasses.replace(base, remat_policy="save_attn_mlp"),
            16, seq, args.steps)
    # 3) accumulation at larger effective batch
    run_cfg("accum2_b32", base, 32, seq, args.steps, accum=2)
    run_cfg("accum4_b64", base, 64, seq, args.steps, accum=4)
    # 4) combined best-guess
    run_cfg("save_attn_mlp_accum2_b32",
            dataclasses.replace(base, remat_policy="save_attn_mlp"),
            32, seq, args.steps, accum=2)
    # 5) embedding-gather micro
    int8_gather_micro()
    emit_final_record({"benchmark": "mfu_sweep", "mode": "single_chip",
                       "done": True})


if __name__ == "__main__":
    main()
