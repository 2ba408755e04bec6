"""Layer: serve loop.  Milliseconds of backend compile or cache load inside
the traced window: the summed ``ms`` of its ``xla.build`` instants."""

from cells import startup


def read(ctx):
    return startup.build_ms_in_trace(ctx)
