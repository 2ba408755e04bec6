"""Layer: model step.  Self time of chip 0 under the name scope
``engine.prefill`` (the prefill programs' own instructions and what XLA
put between them), in percent of the traced window: the prefills' share of
the chip, other requests' token gaps among it."""

from cells import parts


def read(ctx):
    return parts.program_pct(ctx, "engine.prefill")
