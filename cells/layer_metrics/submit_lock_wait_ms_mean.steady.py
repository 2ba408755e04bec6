"""Layer: serve loop.  Mean time a request thread waited for the
replica's lock before it could hand the engine its request
(``serve.lock_wait`` with ``who=submit``)."""

from cells import spans


def read(ctx):
    return spans.mean_duration_ms(ctx, "serve.lock_wait", who="submit")
