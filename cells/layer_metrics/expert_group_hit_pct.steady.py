"""Layer: model.  Of the live tokens of a decode step and expert layer, the
share whose kept groups (the ``topk_group`` best of the router's
``n_group``) include a group this chip's experts lie in:
``moe_group_tokens`` of ``engine.fetch_window`` over ``k * active *
num_layers`` of the traced windows.  What group-limited routing is for: it
bounds the chips a token travels to (expected: ``topk_group / n_group``)."""

from cells import spans


def read(ctx):
    rows = [e[3] for e in spans.named(spans.of_run(ctx) or {},
                                      "engine.fetch_window")
            if "moe_group_tokens" in e[3]]
    token_layers = sum(r["k"] * r["active"] for r in rows) \
        * ctx["model"]["num_layers"]
    if not token_layers:
        return None
    return 100.0 * sum(r["moe_group_tokens"] for r in rows) / token_layers
