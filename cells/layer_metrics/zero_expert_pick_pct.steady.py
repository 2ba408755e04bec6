"""Layer: model.  The share of a token's picks that went to zero-compute
experts in the decode steps (zero_experts / (num_experts + zero_experts) if
routing is even): the model's varying compute a token."""

from cells import expert_counters


def read(ctx):
    s, m = expert_counters.sums(ctx), ctx["model"]
    if not s or not s["token_steps"]:
        return None
    return 100.0 * s["zero"] / (s["token_steps"] * m["experts_per_token"]
                                * m["num_layers"])
