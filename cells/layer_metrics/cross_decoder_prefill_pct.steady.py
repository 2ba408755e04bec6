"""Layer: model.  Of the positions a prompt's prefill ran the self-decoder
for, the share it ran the cross-decoder for, in percent:
``prefill_cross_positions`` over ``prefill_positions``, the model's two
counters as ``engine.first_tokens`` carries them (summed over the traced
admissions).  A decoder-hybrid-decoder's cross-decoder writes nothing to
any cache, so a prefill needs it for the prompt's last position alone: one
position a prompt (under 1% at these prompts), 100 if a change loses
that."""

from cells import spans


def read(ctx):
    rows = [e[3] for e in spans.named(spans.of_run(ctx) or {},
                                      "engine.first_tokens")
            if "prefill_cross_positions" in e[3]]
    whole = sum(r["prefill_positions"] for r in rows)
    if not whole:
        return None
    return 100.0 * sum(r["prefill_cross_positions"] for r in rows) / whole
