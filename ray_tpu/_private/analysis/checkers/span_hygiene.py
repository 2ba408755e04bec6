"""span-hygiene: a trace span must reach its closing path.

The tracing layer's host spans (``ray_tpu/_private/tracing.py``) are the
``span()``/``trace()`` context managers: lexical lifetime, always closed,
provided they are entered.  They are single-use generators, so the leak
this rule guards, mirroring ``thread-lifecycle``, is one stored instead of
``with``-entered: it never runs, the span it stands for is never recorded,
and nothing submitted "inside" it parents to it.

Flagged: ``... = tracing.span(...)`` / ``tracing.trace(...)`` assigned
anywhere (a local, an attribute).
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from ray_tpu._private.analysis.core import (
    Checker, Finding, ParsedFile, register)

_CM_NAMES = ("span", "trace")


def _is_span_cm(call: ast.Call) -> bool:
    """``tracing.span(...)`` / ``tracing.trace(...)``.  Bare ``span()`` /
    ``trace()`` are too common as user names: only the
    ``tracing.``-qualified forms are claimed by this rule."""
    f = call.func
    return (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
            and f.value.id == "tracing" and f.attr in _CM_NAMES)


@register
class SpanHygieneChecker(Checker):
    rule = "span-hygiene"
    description = ("trace spans must close: span()/trace() context "
                   "managers must be with-entered, never stashed")
    hint = "use `with tracing.span(...):` (or `tracing.trace(...)`)"

    def check(self, pf: ParsedFile) -> Iterable[Finding]:
        out: List[Finding] = []
        for node in ast.walk(pf.tree):
            if isinstance(node, ast.Call) and _is_span_cm(node) \
                    and isinstance(pf.parent(node), ast.Assign):
                out.append(self.finding(
                    pf, node,
                    "tracing.span()/trace() is a single-use context "
                    "manager — stashing it instead of `with`-entering "
                    "it can never close the span"))
        return out
