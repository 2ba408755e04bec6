"""Layer: model step.  Milliseconds a decode step spends under the name
scopes ``head`` (final norm, the vocabulary product) and ``sample`` of
``engine.decode``."""

from cells import parts


def read(ctx):
    return parts.part_ms(ctx, "engine.decode",
                         lambda part: part in ("head", "sample"))
