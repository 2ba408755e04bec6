"""Layer: model step.  Seconds the replica has spent tracing, lowering,
building or loading programs since it started: (``build_ms`` + ``load_ms`` +
``lower_ms``) / 1000 of the trace's last ``serve.publish_stats``, from the
process's build ledger (nested phases counted once)."""

from cells import startup


def read(ctx):
    return startup.setup_programs_s(ctx)
