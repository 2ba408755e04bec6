"""The benchmark's ``train_loop_per_worker``: runs inside the worker that
``JaxTrainer`` binds to the chips, and measures there.

Set-up (counted in ``setup_s``): reach the chip, build the trainer on the
mesh ``JaxTrainer`` formed, make the state and one batch on the device
from the seed, the reference's numbers on the same parameters and batch
(``reference_check``), the first step and what it left behind set against
them (``step_check``), the other warm-up steps.  Then the window: steps
until ``seconds`` have passed, each ended by ``block_until_ready``.
"""

import time


def _model_config(model: dict):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    kw = dict(model)
    for key in ("dtype", "param_dtype"):
        if key in kw:
            kw[key] = jnp.dtype(kw[key])
    return LlamaConfig(**kw)


def reference_check(cfg, model, mesh, params, tokens, n_seq):
    """Before the first step, on the parameters it starts from: the
    program's forward pass (``llama_apply``) against the plain reference's
    logits on ``n_seq`` sequences; the reference's loss on every sequence
    of the batch; and the reference's gradient, by float32 autodiff, with
    respect to the embedded tokens of sequence 0."""
    import jax
    import jax.numpy as jnp

    from cells import reference
    from ray_tpu.models.llama import llama_apply

    with mesh:
        sys_logits = jax.jit(
            lambda p, t: llama_apply(p, t, cfg, mesh=mesh))(
                params, tokens[:n_seq, :-1])
    ref_fn = jax.jit(lambda p, t: reference.logits(p, t, model))
    ref_loss = jax.jit(lambda p, t: reference.loss(p, t, model))
    ref_grad = jax.jit(
        lambda p, t: reference.embedding_gradient(p, t, model))

    @jax.jit
    def compare(sys_lg, ref_lg, targets):
        def nll(lg):
            logp = jax.nn.log_softmax(lg, axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, targets[:, None], axis=-1))
        err = jnp.max(jnp.abs(sys_lg - ref_lg)) / jnp.maximum(
            1.0, jnp.max(jnp.abs(ref_lg)))
        return err, nll(sys_lg), nll(ref_lg)

    rows, losses = [], []
    for i in range(tokens.shape[0]):
        if i < n_seq:
            ref_lg = ref_fn(params, tokens[i, :-1])
            err, l_sys, l_ref = compare(sys_logits[i], ref_lg,
                                        tokens[i, 1:])
            rows.append({"logit_err": float(err),
                         "loss_system": float(l_sys),
                         "loss_reference": float(l_ref)})
            losses.append(float(l_ref))
        else:
            losses.append(float(ref_loss(params, tokens[i])))
    return {"rows": rows, "batch_loss": sum(losses) / len(losses),
            "embedding_gradient": ref_grad(params, tokens[0])}


def step_check(state, metrics, tokens, ref):
    """After the first ``tr.step``: the step program's own loss on the
    whole batch beside the reference's, and its gradient beside the
    reference's.  The program does not hand its gradient out, but Adam's
    first moment after one step is the (clipped) gradient times a
    constant.  Compared on the rows of the embedding table whose token
    occurs once in the batch, in sequence 0: such a row's gradient is what
    flowed back to that position through the loss, the head and every
    layer (the flash kernel's backward pass, the recomputation), which is
    the reference's gradient with respect to that embedded token.  Adam's
    update does not change with the gradient's scale, so the direction is
    compared: 1 - cosine.  Also the types the state is kept in."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    adam = [s for s in jax.tree.leaves(
        state["opt_state"], is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(s, "mu")]
    inputs = np.asarray(tokens)[:, :-1]
    counts = np.bincount(inputs.ravel())
    once = np.nonzero(counts[inputs[0]] == 1)[0]
    g_sys = adam[0].mu["embed"][inputs[0][once]].astype(jnp.float32)
    g_ref = ref["embedding_gradient"][once]
    cos = jnp.sum(g_sys * g_ref) / (
        jnp.linalg.norm(g_sys) * jnp.linalg.norm(g_ref))
    kept = state["params"], adam[0].mu, adam[0].nu
    return {"loss_step": float(metrics["loss"]),
            "loss_reference": ref["batch_loss"],
            "grad_rows": int(len(once)),
            "grad_one_minus_cos": float(1.0 - cos),
            "grad_norm": float(metrics["grad_norm"]),
            "state_dtypes": sorted({str(x.dtype)
                                    for x in jax.tree.leaves(kept)})}


def train_loop(config):
    wall_enter = time.time()
    import jax

    devices = jax.devices()
    wall_reached = time.time()
    if not config["rehearse"] and devices[0].platform != "tpu":
        raise RuntimeError(
            f"the worker sees platform {devices[0].platform!r}, not tpu")

    from ray_tpu import train
    from ray_tpu.models.training import default_optimizer, make_llama_trainer

    programs = [0]  # programs built or loaded from the cache, ever

    def on_event(name, *a, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            programs[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    model, traffic = config["model"], config["traffic"]
    cfg = _model_config(model)
    mesh = train.get_context().get_mesh()
    t0 = time.perf_counter()
    tr = make_llama_trainer(
        cfg, mesh, optimizer=default_optimizer(**traffic["optimizer"]))
    key = jax.random.PRNGKey(config["seed"] % (2 ** 31 - 1))
    state = tr.init_state(jax.random.fold_in(key, 0))
    tokens = jax.random.randint(
        jax.random.fold_in(key, 1),
        (traffic["batch"], traffic["seq"] + 1), 0, cfg.vocab_size)
    batch = tr.shard_batch({"tokens": tokens})
    jax.block_until_ready((state, batch))
    build_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    n_seq = min(traffic["batch"], len(devices))
    ref = reference_check(cfg, model, mesh, state["params"],
                          batch["tokens"], n_seq)
    reference_s = time.perf_counter() - t0

    losses = []
    t0 = time.perf_counter()
    for i in range(traffic["warmup_steps"]):
        state, m = tr.step(state, batch)
        jax.block_until_ready((state, m))
        losses.append(float(m["loss"]))
        if i == 0:
            first_step = step_check(state, m, batch["tokens"], ref)
            del ref["embedding_gradient"]
    warmup_s = time.perf_counter() - t0

    trace_on = False
    trace_at = traffic["trace"]["start_step"] if config["trace_dir"] else -1
    programs_before = programs[0]
    step_s = []
    wall_window = time.time()
    w0 = time.perf_counter()
    while True:
        if len(step_s) == trace_at:
            jax.profiler.start_trace(config["trace_dir"])
            trace_on = True
        ts = time.perf_counter()
        state, m = tr.step(state, batch)
        jax.block_until_ready((state, m))
        now = time.perf_counter()
        step_s.append(now - ts)
        losses.append(float(m["loss"]))
        if trace_on and len(step_s) == trace_at + traffic["trace"]["steps"]:
            jax.profiler.stop_trace()
            trace_on = False
        elif now - w0 >= config["seconds"] and not trace_on:
            break
    window_s = time.perf_counter() - w0
    programs_in_window = programs[0] - programs_before

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    train.report({
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": {a: int(n) for a, n in mesh.shape.items()},
        "wall_enter": wall_enter, "wall_reached": wall_reached,
        "wall_window": wall_window,
        "build_s": build_s, "reference_s": reference_s,
        "warmup_s": warmup_s,
        "window_s": window_s, "step_s": step_s, "losses": losses,
        "programs_in_window": programs_in_window,
        "reference": ref["rows"], "first_step": first_step,
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
    })
