"""Layer: model step.  The decode program's share of its roofline: the
bytes one step has to move (the family's ``decode_step_bytes``: every
weight outside the embedding and the experts once, the expert weights
times the measured share of held experts that got a token, the live cached
rows once; live tokens from ``engine.dispatch_window``) over the HBM peak,
over the median device time of the program in the trace.  One token a slot
is some tens of operations a byte, far left of the ridge (240): memory
bounds."""

import statistics

from cells import trace
from cells import expert_counters


def read(ctx):
    if ctx["trace"] is None or ctx["peaks"] is None:
        return None
    runs = trace.decode_program_s(ctx["trace"])
    live = expert_counters.live_tokens(ctx)
    share = expert_counters.hit_share(ctx)
    if not runs or live is None or share is None:
        return None
    least = (ctx["family"].decode_step_bytes(ctx["model"], live, share)
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / statistics.median(runs)
