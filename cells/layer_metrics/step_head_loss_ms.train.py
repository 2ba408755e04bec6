"""Layer: model.  Milliseconds a train step spends under the name scopes
``head`` and ``loss`` of ``train.step``: forward, backward and XLA's twin of
the head's product together."""

from cells import parts


def read(ctx):
    return parts.part_ms(ctx, "train.step",
                         lambda part: part in ("head", "loss"))
