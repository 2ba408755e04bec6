"""Layer: model.  (token, pick) pairs that land on held experts in a decode
step, a layer (mean over steps and layers): the rows the grouped expert
products compute."""

from cells import expert_counters


def read(ctx):
    s = expert_counters.sums(ctx)
    if not s or not s["steps"]:
        return None
    return s["pairs"] / (s["steps"] * ctx["model"]["num_layers"])
