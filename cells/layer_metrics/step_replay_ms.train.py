"""Layer: model.  Milliseconds a train step spends in rematerialised
instructions: those traced under ``jax.checkpoint``'s replay
(``rematted_computation`` in ``op_name``) and the ``.remat`` twins XLA
makes itself, under ``train.step``."""

from cells import parts


def read(ctx):
    return parts.replay_ms(ctx, "train.step")
