"""Layer: serve loop.  Idle time of chip 0 while the engine thread was in
``engine.admit``, ``engine.prepare_window``, ``engine.dispatch_window`` or
``engine.verify`` (self time, innermost span winning), in percent of the
traced window: the host getting the next programs ready."""

from cells import spans


def read(ctx):
    return spans.idle_share_pct(ctx, "host_prepare")
