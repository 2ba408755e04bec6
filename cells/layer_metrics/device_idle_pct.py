"""Layer: device.  1 - union of the device-operation intervals / the
traced window (first to last device operation), mean over chips."""

from cells import trace


def read(ctx):
    return trace.idle_pct(ctx["trace"], ctx["trace_window_s"])
