"""Layer: train loop.  Median host time of a step to
``block_until_ready`` over the window's steps."""

import statistics


def read(ctx):
    steps = ctx["run"].get("step_s")
    return statistics.median(steps) * 1e3 if steps else None
