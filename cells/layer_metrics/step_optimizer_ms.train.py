"""Layer: train loop.  Milliseconds a train step spends under the name
scope ``optimizer`` of ``train.step`` (clipping, AdamW, the parameters'
update, the gradient's norm)."""

from cells import parts


def read(ctx):
    return parts.part_ms(ctx, "train.step", lambda part: part == "optimizer")
