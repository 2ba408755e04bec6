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


def reference_programs(fam, cfg, model, mesh):
    """The set-up's comparison programs (``tools/compile_for_v5e.py``
    compiles them too).  Over a group of sequences ``[g, s + 1]``: the
    program's forward pass (the family's ``apply``); the reference's
    logits set against given ones, inside one program so that they never
    leave it (handed out by ``logits``, for ``tools/control.py``, they
    are gathered onto every chip); the reference's loss.  The
    reference is written for one sequence and mapped over the group,
    which is sharded as the batch is, one sequence a chip: the chips work
    side by side.  For one sequence: the reference's gradient with
    respect to the embedded tokens, by float32 autodiff (over a group it
    would keep 9.7 GB of temporaries a chip beside the four-chip cell's
    state, by the compiler's count)."""
    import jax
    import jax.numpy as jnp

    ref = fam.reference()

    def compare(sys_lg, ref_lg, tokens):
        def nll(lg):
            logp = jax.nn.log_softmax(lg, axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, tokens[1:, None], axis=-1))
        err = jnp.max(jnp.abs(sys_lg - ref_lg)) / jnp.maximum(
            1.0, jnp.max(jnp.abs(ref_lg)))
        return err, nll(sys_lg), nll(ref_lg)

    def over_group(one):
        return jax.jit(lambda p, *groups: jax.vmap(
            lambda *seqs: one(p, *seqs))(*groups))

    return {
        "system": jax.jit(lambda p, group: fam.apply(
            p, group[:, :-1], cfg, mesh)),
        "logits": over_group(lambda p, seq: ref.logits(p, seq[:-1], model)),
        "compare": over_group(lambda p, lg, seq: compare(
            lg, ref.logits(p, seq[:-1], model), seq)),
        "loss": over_group(lambda p, seq: ref.loss(p, seq, model)),
        "gradient": jax.jit(
            lambda p, seq: ref.embedding_gradient(p, seq, model)),
    }


def seeded(tr, cfg, traffic, seed):
    """The state and the one batch of a run, on the devices, from its
    seed."""
    import jax

    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    state = tr.init_state(jax.random.fold_in(key, 0))
    tokens = jax.random.randint(
        jax.random.fold_in(key, 1),
        (traffic["batch"], traffic["seq"] + 1), 0, cfg.vocab_size)
    return state, tr.shard_batch({"tokens": tokens})


def reference_check(programs, mesh, params, tokens, group):
    """Before the first step, on the parameters it starts from: the
    program's logits against the reference's on the first ``group``
    sequences; the reference's loss on every sequence of the batch, a
    group at a time; and the reference's gradient for sequence 0."""
    import jax

    def rows(i):  # spread like the batch: one sequence a chip
        return jax.device_put(tokens[i:i + group], tokens.sharding)

    first = rows(0)
    with mesh:
        sys_logits = programs["system"](params, first)
    err, l_sys, l_ref = programs["compare"](params, sys_logits, first)
    out = [{"logit_err": float(e), "loss_system": float(a),
            "loss_reference": float(b)}
           for e, a, b in zip(err, l_sys, l_ref)]
    losses = [r["loss_reference"] for r in out]
    for i in range(group, tokens.shape[0], group):
        losses += [float(x) for x in programs["loss"](params, rows(i))]
    return {"rows": out, "batch_loss": sum(losses) / len(losses),
            "embedding_gradient": jax.block_until_ready(
                programs["gradient"](params, tokens[0]))}


def seen_once(tokens):
    """Sequence 0's input ids, and its positions whose token occurs once
    in the whole batch."""
    import numpy as np

    inputs = np.asarray(tokens)[:, :-1]
    counts = np.bincount(inputs.ravel())
    return inputs[0], np.nonzero(counts[inputs[0]] == 1)[0]


def one_minus_cos(a, b):
    import jax.numpy as jnp

    return float(1.0 - jnp.sum(a * b) / (
        jnp.linalg.norm(a) * jnp.linalg.norm(b)))


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

    adam = [s for s in jax.tree.leaves(
        state["opt_state"], is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(s, "mu")]
    ids, once = seen_once(tokens)
    g_sys = adam[0].mu["embed"][ids[once]].astype(jnp.float32)
    kept = state["params"], adam[0].mu, adam[0].nu
    return {"loss_step": float(metrics["loss"]),
            "loss_reference": ref["batch_loss"],
            "grad_rows": int(len(once)),
            "grad_one_minus_cos": one_minus_cos(
                g_sys, ref["embedding_gradient"][once]),
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

    from cells import families

    # programs built or loaded from the cache, ever, and the seconds taken
    built = {"n": 0, "s": 0.0}

    def on_event(name, seconds, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            built["n"] += 1
            built["s"] += seconds
    jax.monitoring.register_event_duration_secs_listener(on_event)

    def clock():
        return time.perf_counter(), built["s"]

    def since(mark):
        """Seconds of a part of set-up, and how many of them went into
        building programs or reading them from the cache."""
        return {"s": time.perf_counter() - mark[0],
                "programs_s": built["s"] - mark[1]}

    model, traffic = config["model"], config["traffic"]
    fam = families.load(config["family"])
    cfg = fam.config(model)
    mesh = train.get_context().get_mesh()
    mark = clock()
    tr = fam.make_trainer(cfg, mesh, traffic["optimizer"])
    state, batch = seeded(tr, cfg, traffic, config["seed"])
    jax.block_until_ready((state, batch))
    parts = {"build": since(mark)}

    mark = clock()
    ref = reference_check(
        reference_programs(fam, cfg, model, mesh), mesh, state["params"],
        batch["tokens"], min(traffic["batch"], len(devices)))
    parts["reference"] = since(mark)

    losses = []
    mark = clock()
    for i in range(traffic["warmup_steps"]):
        state, m = tr.step(state, batch)
        jax.block_until_ready((state, m))
        losses.append(float(m["loss"]))
        if i == 0:
            first_step = step_check(state, m, batch["tokens"], ref)
            del ref["embedding_gradient"]
    parts["warmup"] = since(mark)

    trace_on = False
    trace_at = traffic["trace"]["start_step"] if config["trace_dir"] else -1
    programs_before = built["n"]
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
    programs_in_window = built["n"] - programs_before

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    train.report({
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": {a: int(n) for a, n in mesh.shape.items()},
        "wall_enter": wall_enter, "wall_reached": wall_reached,
        "wall_window": wall_window,
        "setup_parts": parts,
        "window_s": window_s, "step_s": step_s, "losses": losses,
        "programs_in_window": programs_in_window,
        "reference": ref["rows"], "first_step": first_step,
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
    })
