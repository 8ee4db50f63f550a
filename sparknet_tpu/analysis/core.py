"""graftlint core: rule registry, suppressions, file walking, reporting.

The repo's banking/obs/manifest contracts existed only as prose and
were violated in-tree anyway.  This package machine-checks them, the
same move the reference ecosystem made when dataflow invariants became
system-validated instead of reviewer-validated (Abadi et al.,
arXiv:1605.08695).

Deliberately stdlib-only: the linter must run on any box, so nothing in
``sparknet_tpu.analysis`` may import jax or numpy directly, and nothing
it triggers may initialize a jax backend (the parent package's lazy
``import jax`` is safe; a ``jax.devices()`` call is not).

Suppression syntax (per line, comma lists allowed; trailing prose after
the rule list is the required justification):

    open(p, "w")  # graftlint: disable=bank-guard -- offline re-attribution
    # graftlint: disable-next-line=bank-guard -- offline re-attribution
    # graftlint: disable-file=bank-guard -- fixture strings below
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
from typing import Callable, Iterable, Iterator

__all__ = [
    "Finding",
    "ModuleContext",
    "Scope",
    "RULES",
    "rule",
    "lint_source",
    "lint_file",
    "lint_paths",
    "iter_python_files",
    "render_text",
    "render_json",
]

# one directive grammar for all three forms; group(1) is the optional
# placement modifier, group(2) the comma-separated rule list (or "all"),
# anything after whitespace/``--``/``—`` is the human justification
_DIRECTIVE = re.compile(
    r"#\s*graftlint:\s*disable(-next-line|-file)?\s*=\s*"
    r"([A-Za-z0-9_\-]+(?:\s*,\s*[A-Za-z0-9_\-]+)*)"
)


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule hit.  ``suppressed`` hits are kept (not dropped) so
    ``--show-suppressed`` can audit what the directives are hiding."""

    rule: str
    path: str
    line: int
    message: str
    suppressed: bool = False

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class Scope:
    """One lexical analysis scope: the module or a (sync/async) function.

    ``body`` holds the scope's DIRECT statements — descendants are cut at
    nested function boundaries, so a helper defined inside a timing
    window is its own scope and does not inherit the window's markers.
    Class bodies do NOT open a scope (methods do): a timing window never
    spans two methods, but module-level code inside ``if`` / ``with`` /
    ``try`` blocks must stay in the module scope.
    """

    node: ast.AST  # ast.Module | ast.FunctionDef | ast.AsyncFunctionDef
    name: str

    def walk(self) -> Iterator[ast.AST]:
        """Descendants of this scope, stopping at nested functions."""
        stack = list(_direct_children(self.node))
        while stack:
            n = stack.pop()
            yield n
            if not isinstance(n, _FUNC_NODES):
                stack.extend(ast.iter_child_nodes(n))

    def calls(self) -> Iterator[ast.Call]:
        for n in self.walk():
            if isinstance(n, ast.Call):
                yield n

    def strings(self) -> Iterator[ast.Constant]:
        for n in self.walk():
            if isinstance(n, ast.Constant) and isinstance(n.value, str):
                yield n


_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def _direct_children(node: ast.AST) -> Iterator[ast.AST]:
    # a function scope's own decorators/defaults belong to the ENCLOSING
    # scope; start from the body + condition fields only
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        yield from node.body
    elif isinstance(node, ast.Lambda):
        yield node.body
    else:
        yield from ast.iter_child_nodes(node)


class ModuleContext:
    """Everything a rule may look at for one file: source, AST, scopes,
    suppression table, and a few shared predicates."""

    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.line_suppressions: dict[int, set[str]] = {}
        self.file_suppressions: set[str] = set()
        self._parse_directives()

    # -- directives --------------------------------------------------------

    def _parse_directives(self) -> None:
        for i, line in enumerate(self.lines, start=1):
            if "graftlint" not in line:
                continue
            m = _DIRECTIVE.search(line)
            if not m:
                continue
            kind = m.group(1) or ""
            rules = {r.strip() for r in m.group(2).split(",") if r.strip()}
            if kind == "-file":
                self.file_suppressions |= rules
            elif kind == "-next-line":
                self.line_suppressions.setdefault(i + 1, set()).update(rules)
            else:
                self.line_suppressions.setdefault(i, set()).update(rules)

    def is_suppressed(self, rule_id: str, line: int) -> bool:
        if {"all", rule_id} & self.file_suppressions:
            return True
        at = self.line_suppressions.get(line, set())
        return bool({"all", rule_id} & at)

    # -- shared predicates -------------------------------------------------

    def scopes(self) -> Iterator[Scope]:
        yield Scope(self.tree, "<module>")
        for n in ast.walk(self.tree):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield Scope(n, n.name)

    def imports_jax(self) -> bool:
        """True if any (possibly function-local) import touches jax."""
        for n in ast.walk(self.tree):
            if isinstance(n, ast.Import):
                if any(a.name.split(".")[0] == "jax" for a in n.names):
                    return True
            elif isinstance(n, ast.ImportFrom):
                if (n.module or "").split(".")[0] == "jax":
                    return True
        return False

    def module_strings(self) -> Iterator[str]:
        for n in ast.walk(self.tree):
            if isinstance(n, ast.Constant) and isinstance(n.value, str):
                yield n.value


# -- call-shape helpers shared by rules ------------------------------------


def call_name(call: ast.Call) -> str:
    """Trailing identifier of the called expression: ``perf_counter`` for
    both ``time.perf_counter()`` and a bare ``perf_counter()``."""
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


# -- registry --------------------------------------------------------------

RuleFn = Callable[[ModuleContext], Iterator[tuple[int, str]]]


@dataclasses.dataclass(frozen=True)
class RuleInfo:
    id: str
    summary: str
    fn: RuleFn


RULES: dict[str, RuleInfo] = {}


def rule(rule_id: str, summary: str) -> Callable[[RuleFn], RuleFn]:
    """Register a rule.  The wrapped function yields ``(lineno, message)``
    pairs; the harness attaches path/rule-id and applies suppressions."""

    def deco(fn: RuleFn) -> RuleFn:
        if rule_id in RULES:
            raise ValueError(f"duplicate rule id {rule_id!r}")
        RULES[rule_id] = RuleInfo(rule_id, summary, fn)
        return fn

    return deco


# -- running ---------------------------------------------------------------


def lint_source(source: str, path: str = "<string>",
                only: set[str] | None = None) -> list[Finding]:
    """Lint one source blob.  Returns ALL findings, suppressed ones
    flagged — callers filter on ``.suppressed`` for the pass/fail set."""
    # rules live in a sibling module; import here (not at module top) so
    # ``core`` itself has no import cycle with ``rules``
    from sparknet_tpu.analysis import rules as _rules  # noqa: F401

    try:
        ctx = ModuleContext(path, source)
    except SyntaxError as e:
        return [Finding("parse-error", path, e.lineno or 0,
                        f"could not parse: {e.msg}")]
    findings: list[Finding] = []
    for info in RULES.values():
        if only and info.id not in only:
            continue
        for lineno, message in info.fn(ctx):
            findings.append(Finding(
                info.id, path, lineno, message,
                suppressed=ctx.is_suppressed(info.id, lineno)))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def lint_file(path: str, only: set[str] | None = None) -> list[Finding]:
    with open(path, encoding="utf-8") as f:
        return lint_source(f.read(), path, only=only)


def iter_python_files(paths: Iterable[str]) -> Iterator[str]:
    """Expand files/directories into .py files, skipping hidden and cache
    directories.  Deterministic order so CI output diffs cleanly."""
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if not d.startswith(".")
                                 and d != "__pycache__")
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)
        elif p.endswith(".py"):
            yield p


def lint_paths(paths: Iterable[str],
               only: set[str] | None = None) -> list[Finding]:
    findings: list[Finding] = []
    for path in iter_python_files(paths):
        findings.extend(lint_file(path, only=only))
    return findings


# -- reporting -------------------------------------------------------------


def render_text(findings: list[Finding], show_suppressed: bool = False,
                label: str = "graftlint") -> str:
    lines = []
    active = [f for f in findings if not f.suppressed]
    for f in active:
        lines.append(f"{f.path}:{f.line}: [{f.rule}] {f.message}")
    if show_suppressed:
        for f in findings:
            if f.suppressed:
                lines.append(
                    f"{f.path}:{f.line}: [{f.rule}] (suppressed) {f.message}")
    n_sup = sum(1 for f in findings if f.suppressed)
    lines.append(
        f"{label}: {len(active)} finding(s), {n_sup} suppressed")
    return "\n".join(lines)


def render_json(findings: list[Finding]) -> str:
    active = [f for f in findings if not f.suppressed]
    return json.dumps({
        "findings": [f.to_dict() for f in findings],
        "unsuppressed": len(active),
        "suppressed": len(findings) - len(active),
    }, indent=1)
