"""Layer: process model.  Harness clock from ``ray_tpu.init()`` until the
cell's chips are all held by a worker (their device nodes open in some
process), watched from outside through ``/proc``."""


def read(ctx):
    return ctx["chip_reach_s"]
