"""Layer: serve loop.  95th percentile over requests of (last token -
first token) / (tokens - 1): the requests whose decoding other requests'
prefills stalled most.  Spreads 4.5% between runs (PERF.md section 6);
the median is the bounded end-to-end metric."""

from cells.loadgen import quantile


def read(ctx):
    values = ctx["run"].get("reduced", {}).get("tpot_ms")
    return quantile(values, 0.95) if values else None
