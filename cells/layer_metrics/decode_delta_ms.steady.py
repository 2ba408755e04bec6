"""Layer: model step.  Milliseconds a decode step spends under the name
scopes ``gdn.*`` (the Gated DeltaNet layers' convolution and the state's
one-position update) of ``engine.decode``: the third level, inside
``attn.core``, so it is a part of ``decode_attention_ms``.  Self time on
chip 0 over the decode program's executions; ``None`` where the program
has no such scope."""

from cells import state_counters


def read(ctx):
    return state_counters.detail_ms(ctx, "engine.decode",
                                    lambda w: w.startswith("gdn."))
