"""Layer: serve loop.  Idle time of chip 0 while the engine thread was
between two steps (``serve.lock_wait``, ``serve.publish_stats``,
``serve.settle``, ``serve.idle``), in percent of the traced window."""

from cells import spans


def read(ctx):
    return spans.idle_share_pct(ctx, "between_steps")
