"""Layer: model step.  Of the decode program's device time, the share of
instructions that carry no part of the vocabulary (XLA's own copies and
twins, and whatever a scope does not reach): the coverage of the name
scopes, to stay under 5."""

from cells import parts


def read(ctx):
    got = parts.checked(ctx, "engine.decode")
    return None if got is None else \
        100.0 * got.get(parts.UNSCOPED, 0.0) / sum(got.values())
