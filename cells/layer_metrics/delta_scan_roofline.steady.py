"""Layer: kernels.  The Gated DeltaNet layers' scan over a prompt in the
traced prefills against its roofline: what the recurrence itself asks for
the traced prompts' TRUE lengths, whatever implements it (the family's
``delta_scan_work``: 7 operations an element of a value head's state and
position against the bf16 peak, ``q``, ``k``, ``v``, ``alpha``, ``beta`` in
and ``o`` out against the HBM peak, the larger), over the device time a
prefill spends under the name scope ``gdn.scan`` (inside ``attn.core``).  A
chunked form does more products than that count, a bucket's padding is in
the time and not in the yield, and products at ``"highest"`` precision
take six passes: the share says how much all of it costs.  Prompts and
executions are matched as ``prefill_us_per_token.steady`` matches them
(``parts.matched_prefills``); the mean prompt against the mean execution.
A program that writes no such scope (another model, a commit before it)
gives ``None``."""

from cells import parts, state_counters


def read(ctx):
    fam = ctx["family"]
    if (ctx["trace"] is None or ctx.get("peaks") is None
            or not hasattr(fam, "delta_scan_work")):
        return None
    ms = state_counters.detail_ms(ctx, "engine.prefill",
                                  lambda w: w == "gdn.scan")
    rows = parts.matched_prefills(ctx)
    if not ms or not rows:
        return None
    ops, moved = fam.delta_scan_work(
        ctx["model"], sum(r[1] for r in rows) / len(rows))
    p = ctx["peaks"]
    least = max(ops / p["bf16_flops_per_s"], moved / p["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)
