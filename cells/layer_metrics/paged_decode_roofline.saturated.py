"""Layer: model step.  The decode program's share of its roofline: the
bytes one step has to move (every weight but the embedding table once,
and the live keys and values once: the family's ``decode_step_bytes``, live
tokens from the polled block count) over the HBM peak, over the median
device time of the program in the trace.  One token a slot: ~30
operations a byte, far left of the ridge (240): memory bounds."""

import statistics

from cells import trace


def read(ctx):
    polls = ctx["run"].get("polls")
    if ctx["trace"] is None or ctx["peaks"] is None or not polls:
        return None
    runs = trace.decode_program_s(ctx["trace"])
    if not runs:
        return None
    live = (sum(p["blocks_used"] for p in polls) / len(polls)
            * ctx["engine"]["block_size"])
    least = (ctx["family"].decode_step_bytes(ctx["model"], live)
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / statistics.median(runs)
