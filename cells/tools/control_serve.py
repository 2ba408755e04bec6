"""The control of the comparison that decides a serve cell's ``correct``,
on the chip at the cell's own widths, outside any timed window:

    chiprun -- python3 cells/tools/control_serve.py <serve cell> <length> <seed> ...

One process that holds the cell's chip (no cluster).  A run's ``correct``
asks of every returned token how far its reference logit lies under that
position's largest (``serve_runner.reference_check``; the traffic file's
``logit_gap_tol``).  Here, for each seed, on the seeded parameters and one
seeded sequence of ``length`` tokens, the same question is asked of two
stand-ins for the engine, each choosing its token at every position of the
same sequence:

* ``program``: the family's ``apply`` (the program's own forward in the
  precision the configuration states, without the cache): the sound
  reading, beside the runs' own (which go through the cache);
* ``control``: the reference itself with the operands of every weight
  product rounded to float8_e4m3fn (the family's reference takes
  ``control_dtype``), the precision below the bfloat16 the configuration
  states: it has to read not correct.

The limit is set between the two readings (PERF.md section 6).
``--rehearse`` runs the family's toy shapes on the CPU, to try the tool.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from cells import run as cells_run  # noqa: E402

CONTROL = "float8_e4m3fn"


def readings(ctx, length, seeds):
    import jax
    import jax.numpy as jnp

    fam, model = ctx["family"], ctx["model"]
    reference = fam.reference()
    cfg = fam.config(model)
    control = dict(model, control_dtype=CONTROL)

    @jax.jit
    def gaps(params, tokens):
        want = reference.logits(params, tokens, model)
        top = jnp.max(want, axis=-1)

        def under(choice):
            return top - jnp.take_along_axis(want, choice[:, None], -1)[:, 0]
        sound = fam.apply(params, tokens[None], cfg, None)[0]
        wrong = reference.logits(params, tokens, control)
        return (under(jnp.argmax(sound, -1)), under(jnp.argmax(wrong, -1)),
                jnp.max(jnp.abs(sound - want)), jnp.max(jnp.abs(wrong - want)),
                jnp.max(jnp.abs(want)))

    tol = ctx["traffic"]["reference"]["logit_gap_tol"]
    print(f"cells: limit {tol} (a returned token's reference logit under "
          f"the position's largest); sequences of {length}", flush=True)
    for seed in seeds:
        key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
        params = fam.init(key, cfg)
        tokens = jax.random.randint(jax.random.fold_in(key, 1), (length,), 0,
                                    model["vocab_size"])
        sound, wrong, e_sound, e_wrong, scale = gaps(params, tokens)
        row = {"program": {"worst_gap": float(sound.max()),
                           "mean_gap": float(sound.mean()),
                           "exact": int((sound == 0).sum()),
                           "logit_err": float(e_sound)},
               "control": {"worst_gap": float(wrong.max()),
                           "mean_gap": float(wrong.mean()),
                           "exact": int((wrong == 0).sum()),
                           "over_the_limit": int((wrong > tol).sum()),
                           "logit_err": float(e_wrong)},
               "logit_scale": float(scale)}
        print(f"cells: control seed {seed} {row}", flush=True)
        del params


def main():
    args = [a for a in sys.argv[1:] if a != "--rehearse"]
    rehearse = "--rehearse" in sys.argv
    name, length = args[0], int(args[1])
    seeds = [int(s) for s in args[2:]]
    _, cell, ctx = cells_run.prepare(name, 0, 1, 0, rehearse)

    import jax

    devices = jax.devices()
    if not rehearse and (devices[0].platform != "tpu"
                         or len(devices) != cell["chips"]):
        raise SystemExit(f"cells: {len(devices)} {devices[0].platform} "
                         f"device(s), the cell needs {cell['chips']} TPU")
    readings(ctx, length, seeds)


if __name__ == "__main__":
    main()
