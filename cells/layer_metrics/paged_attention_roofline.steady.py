"""Layer: kernels.  The dense arm of the paged decode kernel
(``ops/pallas/paged_attention.py``), called once a layer with the layer
type's own pool, table and window, against its roofline: the bytes the
calls of ONE decode step have to read (each layer its type's live rows
once: a full layer the whole context, a window layer at most its window;
``live_tokens_<type>`` of ``engine.dispatch_window``, the family's
``paged_attention_bytes``) over the HBM peak, over the kernel's device time
a step in the trace.  The kernel is the Mosaic call whose result is
``[slots, heads, head_dim]`` (the grouped expert products are Mosaic calls
too, with 2-D results).  7 query heads share a row: some tens of operations
a byte, left of the ridge (240): memory bounds.  The kernel copies whole
pages and the window's first page whole, so 100% is out of reach by a
page a slot and layer."""

from cells import spans, trace


def live_by_kind(ctx):
    """{type: mean cached positions a step of that layer type attends
    over, all slots together}; a full layer also sees what the window's
    own steps add, a window layer at its window does not."""
    rows = [e[3] for e in spans.named(spans.of_run(ctx) or {},
                                      "engine.dispatch_window")
            if "live_tokens_full" in e[3]]
    if not rows:
        return None
    n = len(rows)
    return {"full": sum(r["live_tokens_full"]
                        + r["active"] * (r["k"] + 1) / 2 for r in rows) / n,
            "window": sum(r["live_tokens_window"] for r in rows) / n}


def read(ctx):
    fam, m, e = ctx["family"], ctx["model"], ctx["engine"]
    if (ctx["trace"] is None or ctx["peaks"] is None
            or not hasattr(fam, "paged_attention_bytes")):
        return None
    shape = f"{e['batch_slots']},{m['num_heads']},{m['head_dim']}"
    seconds, count = trace.op_time_s(
        ctx["trace"], rf"= \w+\[{shape}\][^=]*custom-call\(.*" + trace.MOSAIC)
    live = live_by_kind(ctx)
    if not count or live is None:
        return None
    steps = count / m["num_layers"]
    least = (fam.paged_attention_bytes(m, live)
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / steps)
