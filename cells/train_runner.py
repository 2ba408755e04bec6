"""Runner for a training job: ``JaxTrainer(train_loop, ...).fit()`` with
the benchmark's own loop (``train_worker.py``) on the cell's chips."""

import math


def run(ctx):
    from ray_tpu.train import JaxTrainer, ScalingConfig

    from cells.train_worker import train_loop

    config, traffic, model = ctx["config"], ctx["traffic"], ctx["model"]
    result = JaxTrainer(
        train_loop,
        train_loop_config={
            "family": config["family"], "model": model,
            "traffic": traffic, "seed": ctx["seed"],
            "seconds": ctx["seconds"], "rehearse": ctx["rehearse"],
            "trace_dir": ctx["trace_dir"] if ctx["trace"] else None},
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=not ctx["rehearse"],
            chips_per_worker=ctx["chips"],
            mesh=config["scaling"]["mesh"])).fit()
    if result.error is not None:
        raise RuntimeError(f"fit() failed: {result.error!r}") \
            from result.error
    m = result.metrics
    steps = len(m["step_s"])
    tokens = steps * traffic["batch"] * traffic["seq"]
    losses = m["losses"]
    parts = ", ".join(
        f"{name} {p['s']:.1f}s ({p['programs_s']:.1f}s of it building or "
        f"loading programs)" for name, p in m["setup_parts"].items())
    cluster = ctx["cluster"]
    print(f"cells: before the worker's loop "
          f"{m['wall_enter'] - ctx['t0']:.1f}s (to init() "
          f"{cluster.init_wall - ctx['t0']:.1f}s, init() "
          f"{cluster.started_wall - cluster.init_wall:.1f}s, fit() to the "
          f"loop {m['wall_enter'] - cluster.started_wall:.1f}s); "
          f"worker reached its chip in "
          f"{m['wall_reached'] - m['wall_enter']:.1f}s, {parts}; "
          f"{steps} steps in "
          f"{m['window_s']:.2f}s; loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
          f"mesh {m['mesh']}; reference {m['reference']}; first step "
          f"{m['first_step']}", flush=True)
    tol, first = traffic["reference"], m["first_step"]
    checks = {
        "loss finite and falling": all(map(math.isfinite, losses))
        and losses[-1] < losses[0],
        "no program built or loaded inside the window":
        m["programs_in_window"] == 0,
        "logits agree with the reference": all(
            r["logit_err"] <= tol["logit_err_tol"] for r in m["reference"]),
        "loss agrees with the reference": all(
            abs(r["loss_system"] - r["loss_reference"]) <= tol["loss_tol"]
            for r in m["reference"]),
        "the first step's loss on its batch is the reference's":
        abs(first["loss_step"] - first["loss_reference"]) <= tol["loss_tol"],
        "the first step's gradient points where the reference's does":
        first["grad_rows"] >= tol["grad_rows_min"]
        and first["grad_one_minus_cos"] <= tol["grad_one_minus_cos_tol"],
        "parameters and Adam's moments are kept in "
        f"{model['param_dtype']}":
        first["state_dtypes"] == [model["param_dtype"]],
    }
    return {
        "kind": "train", "attempted": steps, "failed": 0,
        "window_s": m["window_s"], "wall_window": m["wall_window"],
        "tokens": tokens, "step_s": m["step_s"], "checks": checks,
        "device": {"platform": m["platform"], "kind": m["device_kind"],
                   "count": m["device_count"],
                   "memory_peak_bytes": max(
                       b or 0 for b in m["peak_bytes_in_use"])},
    }
