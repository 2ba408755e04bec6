"""Layer: serve loop.  Mean of ``queue_wait_ms`` over the
``engine.admit`` spans that admitted a request (``kind=full``): submit to
admission.  A mean over the few admissions of the traced seconds, not a
percentile."""

from cells import spans


def read(ctx):
    return spans.mean_stat(ctx, "engine.admit", "queue_wait_ms", kind="full")
