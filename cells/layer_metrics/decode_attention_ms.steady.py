"""Layer: model step.  Milliseconds a decode step spends under the name
scopes ``attn.*`` (projections and rotary, the cache write, the paged or
latent kernel, the output product) of ``engine.decode``: self time on
chip 0 over the decode program's executions."""

from cells import parts


def read(ctx):
    return parts.part_ms(ctx, "engine.decode",
                         lambda part: part.startswith("attn."))
