"""Layer: model step.  The decode program's share of its HBM roofline for a
family whose step's bytes are counted by layer type (``phi4flash``): the
family's ``decode_step_bytes(model, live_by_type)``: every weight once (the
tied table once, as the head), each storing type's live rows times the
layers that READ it, the live requests' state records read and written;
``live_tokens_<type>`` from ``engine.dispatch_window`` (the full type also
sees what the window's own steps add) over the HBM peak, over the median
device time of the decode program in the trace.  One token a slot is some
tens of operations a byte, far left of the ridge (240): memory bounds.  The
share of the WHOLE step: a later claim in this cell is held to it."""

import statistics

from cells import state_counters, trace


def read(ctx):
    fam = ctx["family"]
    if (ctx["trace"] is None or ctx["peaks"] is None
            or not hasattr(fam, "state_update_bytes")):
        return None
    runs = trace.decode_program_s(ctx["trace"])
    live = state_counters.live_by_type(ctx)
    if not runs or live is None:
        return None
    least = (fam.decode_step_bytes(ctx["model"], live)
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / statistics.median(runs)
