"""Layer: kernels.  The state-space layers' one-position update at decode
against its roofline: the bytes a decode step has to move for the live
requests' records (the family's ``state_update_bytes``: every state-space
layer's float32 state and convolution tail read and written;
``live_tokens_state`` of ``engine.dispatch_window``) over the HBM peak,
over the device time a decode step spends under the name scopes
``ssm.update`` and ``ssm.conv`` (the third level, inside ``attn.core``).
It reads an XLA fusion or a Pallas kernel alike: the yardstick is the
scope, not an instruction's name.  A few operations a byte: memory
bounds."""

from cells import state_counters

SCOPES = ("ssm.update", "ssm.conv")


def read(ctx):
    fam = ctx["family"]
    if (ctx["trace"] is None or ctx["peaks"] is None
            or not hasattr(fam, "state_update_bytes")):
        return None
    ms = state_counters.detail_ms(ctx, "engine.decode", lambda w: w in SCOPES)
    live = state_counters.live_by_type(ctx)
    if not ms or live is None:
        return None
    least = (fam.state_update_bytes(ctx["model"], live["state"])
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
