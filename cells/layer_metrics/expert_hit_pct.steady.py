"""Layer: model.  Of the held experts, the share that got at least one
token in a decode step (mean over steps and layers): how much of the expert
weights a step has to read."""

from cells import expert_counters


def read(ctx):
    share = expert_counters.hit_share(ctx)
    return None if share is None else 100.0 * share
