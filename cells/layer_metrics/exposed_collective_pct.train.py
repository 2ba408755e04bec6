"""Layer: sharding.  Time a collective runs on a chip while no other
operation does, over the traced window; mean over chips."""

from cells import trace


def read(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["trace_window_s"]:
        return None
    chips = list(tr["device"])
    exposed = sum(trace.exposed_collective_ns(tr, c) for c in chips)
    return 100.0 * exposed / len(chips) / 1e9 / ctx["trace_window_s"]
