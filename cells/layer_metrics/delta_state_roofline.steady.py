"""Layer: kernels.  The Gated DeltaNet layers' one-position update at decode
against its roofline: the bytes a decode step has to move for the live
requests' records (the family's ``state_update_bytes``: every DeltaNet
layer's float32 matrix state and convolution tail read once and written
once; ``live_tokens_state`` of ``engine.dispatch_window``) over the HBM
peak, over the device time a decode step spends under the name scopes
``gdn.update`` and ``gdn.conv`` (the third level, inside ``attn.core``).
It reads an XLA fusion or a Pallas kernel alike: the yardstick is the
scope, not an instruction's name.  Seven operations an element of the
state, 8 bytes moved for it: memory bounds.  A program that writes no such
scope or stat (another model, a commit before it) gives ``None``."""

from cells import spans, state_counters

SCOPES = ("gdn.update", "gdn.conv")


def live_records(ctx):
    """Mean records a decode step updates over the traced windows."""
    rows = [e[3]["live_tokens_state"]
            for e in spans.named(spans.of_run(ctx) or {},
                                 "engine.dispatch_window")
            if "live_tokens_state" in e[3]]
    return sum(rows) / len(rows) if rows else None


def read(ctx):
    fam = ctx["family"]
    if (ctx["trace"] is None or ctx.get("peaks") is None
            or not hasattr(fam, "delta_scan_work")):
        return None
    ms = state_counters.detail_ms(ctx, "engine.decode", lambda w: w in SCOPES)
    records = live_records(ctx)
    if not ms or not records:
        return None
    least = (fam.state_update_bytes(ctx["model"], records)
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
