"""Layer: serve loop.  95th percentile (nearest rank) over the requests
due in the window of first streamed token received minus the time the
request was DUE.  What a chat user feels first, and no bound holds it yet:
with ~75 requests in a window and tokens handed out once per ~1 s decode
window it spreads 8.5% between runs of identical arrivals (PERF.md
section 6), so it stands here, unbounded, beside the bounded median TPOT."""

from cells.loadgen import quantile


def read(ctx):
    values = ctx["run"].get("reduced", {}).get("ttft_ms")
    return quantile(values, 0.95) if values else None
