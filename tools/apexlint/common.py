"""Shared plumbing for the apexlint checkers.

Everything here is stdlib-only on purpose: the lint gate must run in
any environment that can run the tests,
with no dependency on jax/numpy being importable — the checkers parse
source, they never import the code under analysis.

A "waiver" is a trailing comment that acknowledges a finding and
suppresses it with a justification:

    self._dropped += 1  # apexlint: unguarded(single-writer stat)
    t0 = time.time()    # apexlint: host-effect(outside trace, timing arg)
    # apexlint: unhandled(MSG_LEGACY)          (wire-protocol checker)
    obs.gauge("scratch", v)  # apexlint: unlisted(debug-only gauge)

Waivers are counted and reported so creep is visible in the summary
the CLI prints and tests/test_apexlint.py reads (`waivers`).
"""

from __future__ import annotations

import ast
import io
import re
import tokenize
from dataclasses import dataclass, field

WAIVER_RE = re.compile(
    r"apexlint:\s*(?P<kind>[a-z-]+)\((?P<arg>[^)]*)\)")


@dataclass
class Finding:
    checker: str
    path: str
    line: int
    message: str

    def as_dict(self) -> dict:
        return {"checker": self.checker, "path": self.path,
                "line": self.line, "message": self.message}

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.checker}] {self.message}"


@dataclass
class CheckResult:
    findings: list[Finding] = field(default_factory=list)
    waivers: int = 0

    def merge(self, other: "CheckResult") -> "CheckResult":
        self.findings.extend(other.findings)
        self.waivers += other.waivers
        return self


class ModuleSource:
    """One parsed module: AST plus a line -> comment-text map.

    `ast` drops comments, so annotations (`# guarded-by: _lock`) and
    waivers are recovered with `tokenize` and joined to AST nodes by
    line number.
    """

    def __init__(self, path: str, text: str | None = None):
        self.path = path
        if text is None:
            with open(path, encoding="utf-8") as fh:
                text = fh.read()
        self.text = text
        self.tree = ast.parse(text, filename=path)
        self.comments: dict[int, str] = {}
        try:
            for tok in tokenize.generate_tokens(
                    io.StringIO(text).readline):
                if tok.type == tokenize.COMMENT:
                    self.comments[tok.start[0]] = tok.string
        except tokenize.TokenError:
            pass  # torn file: AST parsed, comments best-effort

    def comment(self, line: int) -> str:
        return self.comments.get(line, "")

    def waiver(self, line: int, kind: str) -> str | None:
        """Return the waiver argument if `line` carries an
        `# apexlint: <kind>(...)` comment, else None."""
        m = WAIVER_RE.search(self.comment(line))
        if m and m.group("kind") == kind:
            return m.group("arg")
        return None

    def waivers_of_kind(self, kind: str) -> dict[int, str]:
        out = {}
        for line, text in self.comments.items():
            m = WAIVER_RE.search(text)
            if m and m.group("kind") == kind:
                out[line] = m.group("arg")
        return out


def attr_on_self(node: ast.expr) -> str | None:
    """'X' when node is `self.X`, else None."""
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def self_attr_write_targets(node: ast.stmt) -> list[tuple[str, int]]:
    """(attr, line) for every `self.X ... =`-shaped write in a
    statement: plain/aug/ann assigns, tuple unpacks, and subscript
    stores (`self.X[i] = v` mutates the object self.X guards)."""
    targets: list[ast.expr] = []
    if isinstance(node, ast.Assign):
        targets = list(node.targets)
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        targets = [node.target]
    out: list[tuple[str, int]] = []

    def visit_target(t: ast.expr) -> None:
        if isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                visit_target(e)
            return
        if isinstance(t, ast.Starred):
            visit_target(t.value)
            return
        base = t
        while isinstance(base, ast.Subscript):
            base = base.value
        attr = attr_on_self(base)
        if attr is not None:
            out.append((attr, t.lineno))

    for t in targets:
        visit_target(t)
    return out


def dotted_name(node: ast.expr) -> str | None:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None
