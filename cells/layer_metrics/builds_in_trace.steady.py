"""Layer: serve loop.  Programs built or loaded inside the traced window, on
any thread of the replica: the count of ``xla.build`` instants between the
first and the last device operation.  0 is the aim, and a number wherever
the trace has spans."""

from cells import startup


def read(ctx):
    return startup.builds_in_trace(ctx)
