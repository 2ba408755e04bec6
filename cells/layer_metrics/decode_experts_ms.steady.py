"""Layer: model step.  Milliseconds a decode step spends under the name
scopes ``router``, ``experts`` and ``experts.combine`` of
``engine.decode``: the whole expert layer, its sort and its kernel (or
grouped products) included."""

from cells import parts


def read(ctx):
    return parts.part_ms(
        ctx, "engine.decode",
        lambda part: part in ("router", "experts", "experts.combine"))
