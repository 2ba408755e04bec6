"""Layer: process model.  Seconds of the driving process's
``ray_tpu.init()``: its own ``init`` span, read from the process's span
buffer after ``shutdown()`` (its children ``init.start_head`` with the
head's parts, ``init.gcs``, ``init.raylet``, ``init.connect`` say where they
went: ``cells/tools/dump_startup.py``)."""

from cells import startup


def read(ctx):
    return startup.init_s(ctx)
