"""Layer: serve loop.  Idle time of chip 0 under none of the serve loop's
phases (``engine.step``'s own self time included), in percent of the traced
window.  With the four other ``idle_*_pct.steady`` it sums to
``device_idle_pct.steady``."""

from cells import spans


def read(ctx):
    return spans.idle_share_pct(ctx, "unattributed")
