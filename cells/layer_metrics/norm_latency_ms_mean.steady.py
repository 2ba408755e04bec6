"""Layer: serve loop.  Mean over the requests due in the window of (last
token received - the time the request was DUE) / output tokens: the
normalised latency of the Orca and vLLM papers.  It holds the whole of what
a chat user waits for (queueing, admission at a decode-window boundary,
prefill, decoding stalled by other requests' prefills).  It was an
end-to-end metric with a 10% bound until the driver's check read its middle
half of 6 runs 9.3 and 5.9 ms wide on a median of 74.9 ms (12% and 8%;
PERF.md section 6): no bound the contract allows holds that, so it stands
here, unbounded, beside the TTFT and TPOT tails."""


def read(ctx):
    values = ctx["run"].get("reduced", {}).get("norm_latency_ms")
    return sum(values) / len(values) if values else None
