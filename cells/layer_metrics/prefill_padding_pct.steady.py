"""Layer: serve loop.  Of the token positions the traced prefill programs
ran (each admission's ``bucket``), the share that was padding: 100 x (1 -
sum of ``prefilled_tokens`` / sum of ``bucket``) over the admissions
matched to a prefill execution.  The cost of the bucket rule."""

from cells import parts


def read(ctx):
    return parts.prefill_padding_pct(ctx)
