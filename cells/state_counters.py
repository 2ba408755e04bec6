"""What a model with a state type (``phi4flash``) says over the traced
decode windows, shared by the readers of its cell: the positions and
records a step reads, by layer type (``engine.dispatch_window``'s
``live_tokens_<type>``), and the device time under the name scopes of the
third level (``ssm.conv``, ``ssm.update``, ``gmu``, ``diff``: inside
``attn.core``, so ``parts.PARTS`` and its readers do not see them).  A
program that writes no such stat or scope (a model without a state type, a
commit before it) gives ``None``."""

import re

from cells import parts, spans


def live_by_type(ctx):
    """{"full", "window": mean cached positions a step of that type's
    layers attends over, all slots together (the full type also sees what
    the window's own steps add, ``active * (k + 1) / 2`` on average; a
    window layer at its window does not); "state": records a step
    updates}."""
    rows = [e[3] for e in spans.named(spans.of_run(ctx) or {},
                                      "engine.dispatch_window")
            if "live_tokens_state" in e[3]]
    if not rows:
        return None
    n = len(rows)
    return {"full": sum(r["live_tokens_full"]
                        + r["active"] * (r["k"] + 1) / 2 for r in rows) / n,
            "window": sum(r["live_tokens_window"] for r in rows) / n,
            "state": sum(r["live_tokens_state"] for r in rows) / n}


def detail_ms(ctx, program, match):
    """ms an execution of ``program`` spends in instructions whose
    ``op_name`` has a word that ``match`` admits; ``None`` without the
    join, the program or such a word."""
    got, runs = parts._attributed(ctx), parts.executions(ctx, program)
    if got is None or not runs or parts.checked(ctx, program) is None:
        return None
    mine = [row[3] for row in got[0] if row[0] == program
            and any(match(w) for w in re.split(r"[/();]", row[5] or ""))]
    return sum(mine) / 1e6 / len(runs) if mine else None
