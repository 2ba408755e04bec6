"""bench-emission: benchmark entrypoints must end stdout with ONE record.

The bench harness parses the LAST line of a run's captured output
(stdout and stderr merged) as the round's record.  Hand-rolled
``print(json.dumps(...))`` endings broke that contract twice over —
unflushed-stream interleave let stderr warning chatter land after the
record, and any failure before the final print exited with a traceback
instead of a record.  A multichip record shipped without a top-level
parsed metric for five rounds because of exactly this class.

``ray_tpu._private.bench_emit`` centralizes the fix
(``emit_final_record`` flushes stderr first and writes the record
flushed; ``final_record_guard`` emits a structured error record when the
body dies; ``emit_record_line`` for intermediate per-scenario records).
This rule keeps every benchmark entrypoint on those helpers:

- a file with an ``if __name__ == "__main__"`` guard must call
  ``emit_final_record`` (or run under ``final_record_guard``) somewhere;
- bare-JSON prints — ``print(json.dumps(...))`` /
  ``sys.stdout.write(json.dumps(...))`` — are flagged wherever they
  appear in a benchmark file: they compete with the contract line and
  skip the stream-flush ordering.

Prefixed prints (``print("TAG " + json.dumps(...))``) are NOT bare-JSON
lines and stay legal.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional

from ray_tpu._private.analysis.core import (
    Checker,
    Finding,
    ParsedFile,
    dotted_name,
    register,
)

_FINAL_EMITTERS = ("emit_final_record", "final_record_guard")
_LINE_EMITTER = "emit_record_line"


def _is_main_guard(node: ast.AST) -> bool:
    if not isinstance(node, ast.If) or \
            not isinstance(node.test, ast.Compare):
        return False
    t = node.test
    sides = [t.left] + list(t.comparators)
    names = {dotted_name(s) for s in sides}
    consts = {s.value for s in sides if isinstance(s, ast.Constant)}
    return "__name__" in names and "__main__" in consts


def _bare_json_arg(call: ast.Call) -> Optional[ast.Call]:
    """The ``json.dumps(...)`` call passed DIRECTLY as an argument (a
    bare-JSON output line), if any.  String-prefixed concatenations are
    not bare lines."""
    for a in call.args:
        if isinstance(a, ast.Call) and \
                dotted_name(a.func).endswith("json.dumps"):
            return a
    return None


@register
class BenchEmissionChecker(Checker):
    rule = "bench-emission"
    description = ("benchmark entrypoints must emit their final record "
                   "via bench_emit.emit_final_record (stderr-flushed "
                   "final bare-JSON line) and never hand-print bare "
                   "JSON records")
    hint = ("route records through ray_tpu._private.bench_emit: "
            "emit_final_record(rec) for the headline (or wrap the body "
            "in final_record_guard), emit_record_line(rec) for "
            "intermediate records")

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("benchmarks/")
                and relpath.endswith(".py"))

    def check(self, pf: ParsedFile) -> Iterable[Finding]:
        out: List[Finding] = []
        guard = next((n for n in pf.tree.body if _is_main_guard(n)), None)
        if guard is None:
            return out  # importable helper module, not an entrypoint
        emits_final = False
        for node in ast.walk(pf.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name.split(".")[-1] in _FINAL_EMITTERS:
                emits_final = True
                continue
            if name == "print" or name.endswith("stdout.write"):
                dumped = _bare_json_arg(node)
                if dumped is not None:
                    out.append(self.finding(
                        pf, node,
                        "hand-printed bare-JSON record — competes with "
                        "the harness's last-line parse and skips the "
                        "stderr flush ordering"))
        if not emits_final:
            out.append(self.finding(
                pf, guard,
                "benchmark entrypoint never calls emit_final_record / "
                "final_record_guard — on any failure (or stderr "
                "interleave) the harness's last-line parse finds no "
                "record"))
        return out
