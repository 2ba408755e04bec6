"""Layer: serve loop.  Mean share of the paged cache's blocks that hold a
live request's keys and values (``LLMEngine.stats()``: blocks_total less
blocks_available, over blocks_total), same poll as ``queue_depth.steady``.
The configuration's pool is sized to the traffic (its file says how), and
this is the reading that keeps that true."""


def read(ctx):
    polls = ctx["run"].get("polls")
    if not polls:
        return None
    return 100.0 * sum(p["blocks_used"] / p["blocks_total"]
                       for p in polls) / len(polls)
