"""What the expert layer's three counters say over the traced decode
windows: sums over ``engine.fetch_window``'s ``moe_*`` stats (one per
window: pairs on held experts, held experts hit, zero-compute picks, each
summed over the window's ``k`` steps and the model's layers), beside ``k``
and ``active``.  Shared by the ``expert_*`` / ``zero_expert_*`` /
``decode_step_roofline`` readers.  A program that writes no such stat (a
model without experts, a commit before them) gives ``None``."""

from cells import spans


def sums(ctx):
    """{"pairs", "hit", "zero", "steps", "token_steps"} over the traced
    windows, or None."""
    rows = [e[3] for e in spans.named(spans.of_run(ctx) or {},
                                      "engine.fetch_window")
            if "moe_pairs_held" in e[3]]
    if not rows:
        return None
    return {"pairs": sum(r["moe_pairs_held"] for r in rows),
            "hit": sum(r["moe_experts_hit"] for r in rows),
            "zero": sum(r["moe_zero_picks"] for r in rows),
            "steps": sum(r["k"] for r in rows),
            "token_steps": sum(r["k"] * r["active"] for r in rows)}


def hit_share(ctx):
    """Of the held experts of every layer, the share that got at least one
    token in a decode step (0..1)."""
    s, m = sums(ctx), ctx["model"]
    if not s or not s["steps"]:
        return None
    return s["hit"] / (s["steps"] * m["num_layers"] * m["held_experts"])


def live_tokens(ctx):
    """Mean cached positions a decode step attends over, all slots
    together: ``live_tokens`` at the window's start plus what the window's
    own steps add (``active * (k + 1) / 2`` on average)."""
    rows = [e[3] for e in spans.named(spans.of_run(ctx) or {},
                                      "engine.dispatch_window")
            if "live_tokens" in e[3]]
    if not rows:
        return None
    return sum(r["live_tokens"] + r["active"] * (r["k"] + 1) / 2
               for r in rows) / len(rows)
