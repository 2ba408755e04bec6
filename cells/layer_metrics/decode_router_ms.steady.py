"""Layer: model step.  Milliseconds a decode step spends under the name
scope ``router`` of ``engine.decode``: the scores over all outputs, the
groups' scores, the groups kept and the picks (a sigmoid router limited to
groups is several top-k's a layer where a softmax one is one)."""

from cells import parts


def read(ctx):
    return parts.part_ms(ctx, "engine.decode",
                         lambda part: part == "router")
