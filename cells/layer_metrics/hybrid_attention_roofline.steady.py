"""Layer: kernels.  The dense arm of the paged decode kernel
(``ops/pallas/paged_attention.py``) as ``phi4flash`` calls it, once a
READING layer (the window layers their own pool, the full layer and every
cross-attention layer the one full pool), against its roofline: the bytes
the calls of ONE decode step have to read (the family's
``hybrid_attention_bytes``: a window layer at most its window, the full
pool once a reading layer; ``live_tokens_<type>`` of
``engine.dispatch_window``) over the HBM peak, over the kernel's device
time a step in the trace.  The kernel is the Mosaic call whose result is
``[slots, heads, 2 * head_dim]`` (a pair of heads is one head of twice the
width).  Four queries share a row: left of the ridge (240), memory bounds.
The kernel copies whole pages and the window's first page whole, so 100% is
out of reach by a page a slot and layer."""

from cells import state_counters, trace


def read(ctx):
    fam, m, e = ctx["family"], ctx["model"], ctx["engine"]
    if (ctx["trace"] is None or ctx["peaks"] is None
            or not hasattr(fam, "hybrid_attention_bytes")):
        return None
    shape = f"{e['batch_slots']},{m['num_heads']},{2 * m['head_dim']}"
    seconds, count = trace.op_time_s(
        ctx["trace"], rf"= \w+\[{shape}\][^=]*custom-call\(.*" + trace.MOSAIC)
    live = state_counters.live_by_type(ctx)
    if not count or live is None:
        return None
    steps = count / sum(fam.reading_layers(m).values())
    least = (fam.hybrid_attention_bytes(m, live)
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / steps)
