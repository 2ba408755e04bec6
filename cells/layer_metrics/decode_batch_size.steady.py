"""Layer: serve loop.  Mean of ``active`` over ``engine.dispatch_window``: the
requests a decode window decodes for."""

from cells import spans


def read(ctx):
    return spans.mean_stat(ctx, "engine.dispatch_window", "active")
