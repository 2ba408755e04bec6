"""Layer: model step.  Device time of the traced prefill executions over
the prompt tokens they prefilled (``prefilled_tokens`` of the
``engine.admit`` span that launched each: the suffix's true length), in
microseconds a token.  Padding to the bucket is in the time and not in the
tokens."""

from cells import parts


def read(ctx):
    return parts.prefill_us_per_token(ctx)
