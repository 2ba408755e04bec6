"""Layer: serve loop.  Median time from the end of ``engine.fetch_window`` to
the start of the next ``engine.dispatch_window``: the serial host section of
a decode window, during which the device has nothing queued."""

from cells import spans


def read(ctx):
    return spans.window_host_ms(ctx)
