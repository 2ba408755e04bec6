"""raylint engine: file model, checker plugin API, suppressions, runner.

Design goals, in order:

1. **Zero deps, zero imports of checked code.**  Everything is
   ``ast``-level; the engine never imports the modules it lints, so a
   broken module can't break the linter (it gets a ``syntax-error``
   finding instead).
2. **Pluggable.**  A checker is a class with a ``rule`` id and either a
   per-file ``check(parsed_file)`` or a whole-tree
   ``check_project(project)``.  ``@register`` adds it to the registry;
   the CLI, the tier-1 test, and fixture self-tests all discover it
   from there.
3. **Suppression is a contract, not an escape hatch.**  Inline waivers
   must name the rule *and* carry a reason; the engine reports
   reasonless waivers under ``suppression-hygiene`` so a suppression
   can never silently lose its justification.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Type

# ---------------------------------------------------------------------------
# Suppressions
# ---------------------------------------------------------------------------

#: ``# raylint: disable=rule-a,rule-b -- reason text``
_SUPPRESS_RE = re.compile(
    r"#\s*raylint:\s*disable=([\w\-]+(?:\s*,\s*[\w\-]+)*)"
    r"(?:\s+--\s*(\S.*?))?\s*$")

#: pseudo-rules the engine itself owns; always active, never suppressible
META_RULES = ("syntax-error", "suppression-hygiene")


@dataclasses.dataclass
class Suppression:
    line: int
    rules: Tuple[str, ...]
    reason: Optional[str]


@dataclasses.dataclass
class Finding:
    """One lint finding: ``path:line: [rule] message``."""

    rule: str
    path: str  # repo-relative, posix separators
    line: int
    message: str
    hint: str = ""
    suppressed: bool = False
    suppress_reason: Optional[str] = None

    def to_dict(self) -> dict:
        d = {"rule": self.rule, "path": self.path, "line": self.line,
             "message": self.message, "hint": self.hint}
        if self.suppressed:
            d["suppress_reason"] = self.suppress_reason
        return d

    def render(self) -> str:
        s = f"{self.path}:{self.line}: [{self.rule}] {self.message}"
        if self.hint:
            s += f"\n    hint: {self.hint}"
        return s


# ---------------------------------------------------------------------------
# File / project model
# ---------------------------------------------------------------------------

class ParsedFile:
    """A source file parsed once and shared by every checker."""

    def __init__(self, relpath: str, src: str):
        self.relpath = relpath
        self.src = src
        self.lines = src.splitlines()
        self.tree: Optional[ast.AST] = None
        self.syntax_error: Optional[SyntaxError] = None
        try:
            self.tree = ast.parse(src)
        except SyntaxError as e:
            self.syntax_error = e
        if self.tree is not None:
            for node in ast.walk(self.tree):
                for child in ast.iter_child_nodes(node):
                    child._raylint_parent = node  # type: ignore[attr-defined]
        self.suppressions: Dict[int, Suppression] = {}
        for i, line in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(line)
            if m:
                rules = tuple(r.strip() for r in m.group(1).split(","))
                self.suppressions[i] = Suppression(i, rules, m.group(2))

    # -- AST conveniences -------------------------------------------------

    @staticmethod
    def parent(node: ast.AST) -> Optional[ast.AST]:
        return getattr(node, "_raylint_parent", None)

    @classmethod
    def ancestors(cls, node: ast.AST) -> Iterable[ast.AST]:
        cur = cls.parent(node)
        while cur is not None:
            yield cur
            cur = cls.parent(cur)

    @classmethod
    def enclosing(cls, node: ast.AST, kinds) -> Optional[ast.AST]:
        for anc in cls.ancestors(node):
            if isinstance(anc, kinds):
                return anc
        return None

    def enclosing_function(self, node: ast.AST):
        return self.enclosing(node, (ast.FunctionDef, ast.AsyncFunctionDef))

    def enclosing_class(self, node: ast.AST) -> Optional[ast.ClassDef]:
        return self.enclosing(node, ast.ClassDef)

    # -- suppression lookup ----------------------------------------------

    def suppression_for(self, line: int, rule: str) -> Optional[Suppression]:
        """A waiver covers a finding from its own line or the line above."""
        for ln in (line, line - 1):
            sup = self.suppressions.get(ln)
            if sup is not None and rule in sup.rules:
                return sup
        return None


class Project:
    """The scanned tree: parsed files plus raw access to the repo root."""

    def __init__(self, root: str, files: Dict[str, ParsedFile]):
        self.root = os.path.abspath(root)
        self.files = files

    def file(self, relpath: str) -> Optional[ParsedFile]:
        return self.files.get(relpath)

    def read_text(self, relpath: str) -> Optional[str]:
        """Raw file access for non-Python inputs (docs, configs)."""
        path = os.path.join(self.root, relpath)
        if not os.path.isfile(path):
            return None
        with open(path, encoding="utf-8") as f:
            return f.read()


# ---------------------------------------------------------------------------
# Checker plugin API
# ---------------------------------------------------------------------------

class Checker:
    """Per-file checker: visit one parsed file, yield findings.

    Subclasses set ``rule`` (the stable id used in suppressions and
    ``--rules``), ``description`` (one line, shown in the catalog), and
    ``hint`` (the fix direction attached to every finding).  Override
    ``applies_to`` to scope the rule to part of the tree.
    """

    rule: str = ""
    description: str = ""
    hint: str = ""

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("ray_tpu/")
                and not relpath.startswith("ray_tpu/_private/analysis/"))

    def check(self, pf: ParsedFile) -> Iterable[Finding]:
        raise NotImplementedError

    def finding(self, pf_or_path, node_or_line, message: str,
                hint: Optional[str] = None) -> Finding:
        path = (pf_or_path.relpath if isinstance(pf_or_path, ParsedFile)
                else pf_or_path)
        line = (node_or_line if isinstance(node_or_line, int)
                else getattr(node_or_line, "lineno", 0))
        return Finding(rule=self.rule, path=path, line=line, message=message,
                       hint=self.hint if hint is None else hint)


class ProjectChecker(Checker):
    """Whole-tree checker: cross-file / cross-format invariants."""

    def check_project(self, project: Project) -> Iterable[Finding]:
        raise NotImplementedError

    def check(self, pf: ParsedFile) -> Iterable[Finding]:  # pragma: no cover
        return ()


_REGISTRY: Dict[str, Type[Checker]] = {}


def register(cls: Type[Checker]) -> Type[Checker]:
    if not cls.rule:
        raise ValueError(f"{cls.__name__} has no rule id")
    if cls.rule in _REGISTRY or cls.rule in META_RULES:
        raise ValueError(f"duplicate rule id {cls.rule!r}")
    _REGISTRY[cls.rule] = cls
    return cls


def all_rules() -> List[str]:
    return sorted(_REGISTRY)


def get_checkers(rules: Optional[Sequence[str]] = None) -> List[Checker]:
    if rules is None:
        return [cls() for _, cls in sorted(_REGISTRY.items())]
    unknown = [r for r in rules if r not in _REGISTRY]
    if unknown:
        raise ValueError(
            f"unknown rule(s): {', '.join(sorted(unknown))} "
            f"(known: {', '.join(all_rules())})")
    return [_REGISTRY[r]() for r in rules]


# ---------------------------------------------------------------------------
# Runner
# ---------------------------------------------------------------------------

#: directories never descended into while collecting sources
_SKIP_DIRS = {"__pycache__", ".git", ".pytest_cache", "build", "dist"}

#: default scan set, relative to the repo root
DEFAULT_PATHS = ("ray_tpu", "tests", "benchmarks")


@dataclasses.dataclass
class LintResult:
    root: str
    rules: List[str]
    files_scanned: int
    findings: List[Finding]
    suppressed: List[Finding]

    @property
    def clean(self) -> bool:
        return not self.findings

    def to_json(self) -> str:
        return json.dumps({
            "version": 1,
            "root": self.root,
            "rules": self.rules,
            "files_scanned": self.files_scanned,
            "findings": [f.to_dict() for f in self.findings],
            "suppressed": [f.to_dict() for f in self.suppressed],
        }, indent=2)

    def render_human(self) -> str:
        out = [f.render() for f in self.findings]
        out.append(
            f"raylint: {len(self.findings)} finding(s), "
            f"{len(self.suppressed)} suppressed, "
            f"{self.files_scanned} file(s), {len(self.rules)} rule(s)")
        return "\n".join(out)


def _collect_files(root: str, paths: Sequence[str]) -> Dict[str, ParsedFile]:
    files: Dict[str, ParsedFile] = {}

    def add(abspath: str):
        rel = os.path.relpath(abspath, root).replace(os.sep, "/")
        if rel in files:
            return
        with open(abspath, encoding="utf-8", errors="replace") as f:
            files[rel] = ParsedFile(rel, f.read())

    for p in paths:
        abspath = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(abspath):
            add(abspath)
            continue
        for dirpath, dirnames, filenames in os.walk(abspath):
            dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
            for name in sorted(filenames):
                if name.endswith(".py"):
                    add(os.path.join(dirpath, name))
    return files


def run_lint(root: str, paths: Optional[Sequence[str]] = None,
             rules: Optional[Sequence[str]] = None) -> LintResult:
    """Run the suite; raises ``ValueError`` on unknown rule ids and lets
    checker crashes propagate (the CLI maps both to exit code 2)."""
    root = os.path.abspath(root)
    checkers = get_checkers(rules)
    requested = paths if paths is not None else DEFAULT_PATHS
    scan, missing = [], []
    for p in requested:
        abspath = p if os.path.isabs(p) else os.path.join(root, p)
        (scan if os.path.exists(abspath) else missing).append(p)
    if paths is not None and missing:
        # a typoed explicit path must not silently lint nothing and
        # report "clean"; only the DEFAULT_PATHS set is best-effort
        raise ValueError(
            f"path(s) not found under {root}: {', '.join(missing)}")
    project = Project(root, _collect_files(root, scan))

    raw: List[Finding] = []
    for rel, pf in sorted(project.files.items()):
        if pf.syntax_error is not None:
            raw.append(Finding(
                rule="syntax-error", path=rel,
                line=pf.syntax_error.lineno or 0,
                message=f"file does not parse: {pf.syntax_error.msg}"))
    for checker in checkers:
        if isinstance(checker, ProjectChecker):
            raw.extend(checker.check_project(project))
        else:
            for rel, pf in sorted(project.files.items()):
                if pf.tree is not None and checker.applies_to(rel):
                    raw.extend(checker.check(pf))

    findings: List[Finding] = []
    suppressed: List[Finding] = []
    bad_waivers = set()  # (path, line) of reasonless disables, report once
    for f in raw:
        pf = project.file(f.path)
        sup = (pf.suppression_for(f.line, f.rule)
               if pf is not None and f.rule not in META_RULES else None)
        if sup is not None and sup.reason:
            f.suppressed = True
            f.suppress_reason = sup.reason
            suppressed.append(f)
        elif sup is not None:
            findings.append(f)
            if (f.path, sup.line) not in bad_waivers:
                bad_waivers.add((f.path, sup.line))
                findings.append(Finding(
                    rule="suppression-hygiene", path=f.path, line=sup.line,
                    message=("suppression without a reason — every waiver "
                             "must justify itself"),
                    hint="# raylint: disable=<rule> -- <why this is safe>"))
        else:
            findings.append(f)

    # waiver hygiene holds even where no finding currently fires: a bare
    # reasonless disable, or one naming a rule that doesn't exist, is
    # reported on its own — otherwise the documented "reasons are
    # mandatory" contract would only bind waivers that happen to be hit
    active = {c.rule for c in checkers}
    known = set(_REGISTRY) | set(META_RULES)
    for rel, pf in sorted(project.files.items()):
        if rel.startswith("ray_tpu/_private/analysis/"):
            continue  # the linter's own sources are grammar examples
        for sup in pf.suppressions.values():
            key = (rel, sup.line)
            unknown = sorted(r for r in sup.rules if r not in known)
            if unknown:
                findings.append(Finding(
                    rule="suppression-hygiene", path=rel, line=sup.line,
                    message=(f"suppression names unknown rule(s): "
                             f"{', '.join(unknown)}"),
                    hint=f"known rules: {', '.join(sorted(known))}"))
            if not sup.reason and key not in bad_waivers \
                    and any(r in active for r in sup.rules):
                bad_waivers.add(key)
                findings.append(Finding(
                    rule="suppression-hygiene", path=rel, line=sup.line,
                    message=("suppression without a reason — every waiver "
                             "must justify itself"),
                    hint="# raylint: disable=<rule> -- <why this is safe>"))

    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    suppressed.sort(key=lambda f: (f.path, f.line, f.rule))
    return LintResult(root=root, rules=[c.rule for c in checkers],
                      files_scanned=len(project.files),
                      findings=findings, suppressed=suppressed)


# -- shared AST helpers used by several checkers ----------------------------

def call_name(node: ast.Call) -> str:
    """Terminal name of a call: ``foo(...)`` -> foo, ``a.b.c(...)`` -> c."""
    if isinstance(node.func, ast.Name):
        return node.func.id
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    return ""


def dotted_name(node: ast.AST) -> str:
    """``a.b.c`` -> "a.b.c"; non-name chains collapse to ""."""
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return ".".join(reversed(parts))
    return ""


def keyword_arg(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def is_const(node: Optional[ast.AST], value) -> bool:
    return isinstance(node, ast.Constant) and node.value is value
