"""Layer: model step.  Median device time of one execution of the decode
program in the trace."""

import statistics

from cells import trace


def read(ctx):
    if ctx["trace"] is None:
        return None
    runs = trace.decode_program_s(ctx["trace"])
    return statistics.median(runs) * 1e3 if runs else None
