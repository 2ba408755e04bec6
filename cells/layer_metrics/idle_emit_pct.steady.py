"""Layer: serve loop.  Idle time of chip 0 while the engine thread was in
``engine.emit``, ``engine.retire`` or ``serve.deliver``, in percent of the
traced window: handing tokens and finished answers to their waiters."""

from cells import spans


def read(ctx):
    return spans.idle_share_pct(ctx, "emit")
