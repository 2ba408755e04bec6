"""Layer: serve loop.  Idle time of chip 0 that overlaps an ``xla.build``'s
``[end - ms, end]`` on any thread, in percent of the traced window: the
part of ``device_idle_pct.steady`` spent while a program was being built,
under whichever of the five ``idle_*`` groups it falls."""

from cells import startup


def read(ctx):
    return startup.idle_build_pct(ctx)
