"""The control of the comparison that decides a train cell's ``correct``,
on the chip at the cell's own size, outside any timed window:

    chiprun --chips 4 -- python3 cells/tools/control.py train-fsdp4-s4096 <seed> ...

(The cell has to be in ``BENCHMARK.json``: for a parked one, give the file
the entries of ``cells/parked/<cell>.json`` first.)
One process that holds the cell's chips (no cluster).  For each seed the
parameters and the batch as the cell makes them; the program's logits and
loss set against the reference's as a run sets them; and the control's:
the reference itself with the operands of every weight product rounded to
float8_e4m3fn (``cells/reference.py``), the precision below the bfloat16
the configurations state.  The control's gradient for sequence 0 is set
against the reference's on the rows a run compares.  The program's own
gradient is not read here (it takes the step program and Adam's state):
a run prints it.  Limits are set from these readings and the runs' (PERF.md).
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from cells import run as cells_run, train_worker  # noqa: E402

CONTROL = "float8_e4m3fn"


def readings(ctx, seeds):
    import jax

    from ray_tpu.parallel.mesh import create_mesh, resolve_mesh_config

    devices = jax.devices()
    fam, model, traffic = ctx["family"], ctx["model"], ctx["traffic"]
    mesh = create_mesh(resolve_mesh_config(
        ctx["config"]["scaling"]["mesh"]).clamp_to(len(devices)))
    cfg = fam.config(model)
    tr = fam.make_trainer(cfg, mesh, traffic["optimizer"])
    sound = train_worker.reference_programs(fam, cfg, model, mesh)
    control = train_worker.reference_programs(
        fam, cfg, dict(model, control_dtype=CONTROL), mesh)
    group = min(traffic["batch"], len(devices))
    print(f"cells: limits {traffic['reference']['logit_err_tol']} (logits), "
          f"{traffic['reference']['loss_tol']} (loss), "
          f"{traffic['reference']['grad_one_minus_cos_tol']} (1 - cosine)",
          flush=True)
    for seed in seeds:
        state, batch = train_worker.seeded(tr, cfg, traffic, seed)
        params, tokens = state["params"], batch["tokens"]
        del state  # Adam's moments are not needed here
        first = jax.device_put(tokens[:group], tokens.sharding)
        rows = {}
        for who in ("program", "control"):
            with mesh:
                lg = (sound["system"] if who == "program"
                      else control["logits"])(params, first)
            err, mine, ref = sound["compare"](params, lg, first)
            rows[who] = {
                "logit_err": [float(e) for e in err],
                "loss_diff": [abs(float(a) - float(b))
                              for a, b in zip(mine, ref)]}
        del lg
        _, once = train_worker.seen_once(tokens)
        rows["control"]["grad_one_minus_cos"] = train_worker.one_minus_cos(
            control["gradient"](params, tokens[0])[once],
            sound["gradient"](params, tokens[0])[once])
        rows["control"]["grad_rows"] = int(len(once))
        print(f"cells: control seed {seed} {rows}", flush=True)


def main():
    name, seeds = sys.argv[1], [int(s) for s in sys.argv[2:]]
    _, cell, ctx = cells_run.prepare(name, 0, 1, 0, False)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cell["chips"]:
        raise SystemExit(f"cells: {len(devices)} {devices[0].platform} "
                         f"device(s), the cell needs {cell['chips']} TPU")
    readings(ctx, seeds)


if __name__ == "__main__":
    main()
