"""Layer: model.  Operations the forward and backward passes need for one
step (the family's ``train_flops_per_step``; recomputation not counted) over the
median step time, over chips x the bf16 peak of the table.  From the
median step and not the window, so that the traced run's profiler start
and stop do not dilute it."""

import statistics


def read(ctx):
    run, t = ctx["run"], ctx["traffic"]
    if run["kind"] != "train" or ctx["peaks"] is None:
        return None
    per_step = ctx["family"].train_flops_per_step(
        ctx["model"], t["batch"], t["seq"])
    achieved = per_step / statistics.median(run["step_s"])
    return 100.0 * achieved / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
