"""Layer: serve loop.  Seconds from ``_build_engine``'s entry to the end of
``LLMEngine.__init__`` in the replica (the ``engine.startup`` span):
``startup_total_s`` of the trace's last ``serve.publish_stats``."""

from cells import startup


def read(ctx):
    return startup.snapshot_stat(ctx, "startup_total_s")
