"""Layer: model.  Seconds of ``model.init`` until its weights are ready on
the device (``engine.startup.weights``): ``startup_weights_s`` of the trace's
last ``serve.publish_stats``."""

from cells import startup


def read(ctx):
    return startup.snapshot_stat(ctx, "startup_weights_s")
