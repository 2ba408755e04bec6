"""Layer: model step.  Milliseconds a decode step spends under the name
scopes ``ssm.*`` (the convolution and the state's one-position update) and
``gmu`` (the gated memory unit's gate) of ``engine.decode``: the third
level, inside ``attn.core``, so it is a part of ``decode_attention_ms``.
Self time on chip 0 over the decode program's executions."""

from cells import state_counters


def read(ctx):
    return state_counters.detail_ms(
        ctx, "engine.decode", lambda w: w.startswith("ssm.") or w == "gmu")
