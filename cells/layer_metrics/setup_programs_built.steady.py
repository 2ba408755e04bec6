"""Layer: model step.  Programs the replica's backend compiled because the
compile cache did not hold them (``built`` of the trace's last
``serve.publish_stats``): 0 on a warm machine, and what separates a first
run's set-up from a later one's."""

from cells import startup


def read(ctx):
    return startup.snapshot_stat(ctx, "built")
