"""Layer: serve loop.  What the block manager's runs are worth to the
paged decode kernel: of the pages its slots hold live at a decode window's
launch, summed over the pools of positions, the share that lies in groups
the kernel copies with one descriptor (a slot's consecutive table entries
name adjacent blocks of the pool, the group is live whole, and so is every
other group of its compute block).  Both counts ride on
``engine.dispatch_window`` (``pages_live``, ``pages_in_runs``, from the
engine's host tables); summed over the traced windows.  0 would mean a pool
so churned or so full that no slot is handed a whole run; an engine that
writes neither count (the parent of PR 50) reads nothing."""

from cells import spans


def read(ctx):
    rows = [e[3] for e in spans.named(spans.of_run(ctx) or {},
                                      "engine.dispatch_window")
            if "pages_live" in e[3]]
    live = sum(r["pages_live"] for r in rows)
    if not live:
        return None
    return 100.0 * sum(r["pages_in_runs"] for r in rows) / live
