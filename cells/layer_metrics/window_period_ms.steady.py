"""Layer: serve loop.  Median time from one ``engine.dispatch_window`` to the
next: what a decode window costs end to end (k decode steps, other
requests' prefills, the host's serial section, idle)."""

from cells import spans


def read(ctx):
    return spans.window_period_ms(ctx)
