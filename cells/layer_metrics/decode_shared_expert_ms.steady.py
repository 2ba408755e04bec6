"""Layer: model step.  Milliseconds a decode step spends in the shared
expert: the instructions of ``engine.decode`` whose ``op_name`` has the
name scope ``experts.shared`` among its words (nested inside ``experts``,
whose time ``decode_experts_ms.steady`` reads whole: ``parts.PARTS`` is a
closed vocabulary and an instruction belongs to the innermost KNOWN word,
so the scope is found by its own name, as ``decode_state_ms.steady``'s
details are)."""

from cells import state_counters


def read(ctx):
    return state_counters.detail_ms(ctx, "engine.decode",
                                    lambda word: word == "experts.shared")
