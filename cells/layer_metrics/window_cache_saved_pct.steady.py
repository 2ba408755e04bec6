"""Layer: serve loop.  What giving back the window layers' blocks saves:
100 x (1 - blocks the window type's pool holds / blocks the same live
sequences would hold there with no release), over the traced decode
windows.  The full type's pool holds a sequence's every block, which is
what the window type's would hold too; both counts ride on
``engine.dispatch_window`` (``blocks_held_<type>``, from the block
managers).  0 would mean that the traffic never passes the window."""

from cells import spans


def read(ctx):
    rows = [e[3] for e in spans.named(spans.of_run(ctx) or {},
                                      "engine.dispatch_window")
            if "blocks_held_window" in e[3]]
    whole = sum(r["blocks_held_full"] for r in rows)
    if not whole:
        return None
    return 100.0 * (1.0 - sum(r["blocks_held_window"] for r in rows) / whole)
