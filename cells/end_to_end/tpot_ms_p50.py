"""Median over the requests due in the window of (last token - first
token) / (tokens - 1).  Per request, not per gap: the engine hands tokens
out once per decode window of 16 steps, so raw gaps are bimodal."""

from cells.loadgen import quantile


def read(ctx):
    values = ctx["run"].get("reduced", {}).get("tpot_ms")
    return quantile(values, 0.5) if values else None
