"""Layer: model step.  Milliseconds a decode step spends under the name
scope ``ffn`` (a dense MLP: the dense model's, LongCat's dense blocks) of
``engine.decode``."""

from cells import parts


def read(ctx):
    return parts.part_ms(ctx, "engine.decode", lambda part: part == "ffn")
