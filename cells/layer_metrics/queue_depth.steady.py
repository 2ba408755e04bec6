"""Layer: serve loop.  Mean of ``LLMEngine.stats()["queued"]`` polled at
about 2 Hz through the replica's handle during the window."""


def read(ctx):
    polls = ctx["run"].get("polls")
    return sum(p["queued"] for p in polls) / len(polls) if polls else None
