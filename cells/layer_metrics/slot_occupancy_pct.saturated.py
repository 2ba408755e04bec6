"""Layer: serve loop.  Mean of ``LLMEngine.stats()["slot_occupancy"]``,
same poll as ``queue_depth.steady``."""


def read(ctx):
    polls = ctx["run"].get("polls")
    if not polls:
        return None
    return 100.0 * sum(p["slot_occupancy"] for p in polls) / len(polls)
