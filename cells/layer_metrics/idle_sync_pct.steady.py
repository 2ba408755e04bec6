"""Layer: serve loop.  Idle time of chip 0 while the engine thread was in
``engine.first_tokens`` or ``engine.fetch_window``, in percent of the traced
window: the host waiting for, and copying back, what the device made."""

from cells import spans


def read(ctx):
    return spans.idle_share_pct(ctx, "sync")
