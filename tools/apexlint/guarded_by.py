"""Guarded-by checker: lock-discipline for annotated attributes.

An attribute whose assignment in `__init__` carries a trailing
`# guarded-by: <lock>` comment may only be written inside a
`with self.<lock>:` block (anywhere else in the class). Writes in
`__init__` itself are construction — no other thread can hold a
reference yet — and are exempt.

The check is lexical: a write inside a helper that is only ever
*called* with the lock held still flags, because nothing enforces that
calling convention. Either inline the write under the `with`, or waive
the line with `# apexlint: unguarded(<why it is safe>)`.

A method that hands the lock back wrapped (a timed or traced
acquisition) declares it on its `def` line with `# apexlint:
holds(<lock>)`; `with self.<method>(...):` then counts as `with
self.<lock>:`.

Nested functions (thread targets, closures) defined inside a `with`
block run later, after the lock is released, so the held-lock set is
reset to empty inside them.
"""

from __future__ import annotations

import ast
import re

from tools.apexlint.common import (
    CheckResult, Finding, ModuleSource, attr_on_self,
    self_attr_write_targets)

GUARDED_BY_RE = re.compile(r"guarded-by:\s*(\w+)")

CHECKER = "guarded-by"


def _declared_guards(cls: ast.ClassDef,
                     src: ModuleSource) -> dict[str, str]:
    """attr -> lock-attr from `# guarded-by:` comments in __init__."""
    guards: dict[str, str] = {}
    for stmt in cls.body:
        if (isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef))
                and stmt.name == "__init__"):
            for node in ast.walk(stmt):
                if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                    continue
                m = GUARDED_BY_RE.search(src.comment(node.lineno))
                if not m:
                    continue
                for attr, _ in self_attr_write_targets(node):
                    guards[attr] = m.group(1)
    return guards


def _declared_holders(cls: ast.ClassDef,
                      src: ModuleSource) -> dict[str, str]:
    """method name -> lock-attr from `# apexlint: holds(<lock>)` on a
    method's `def` line."""
    holders: dict[str, str] = {}
    for stmt in cls.body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            lock = src.waiver(stmt.lineno, "holds")
            if lock:
                holders[stmt.name] = lock.strip()
    return holders


class _WriteScanner:
    """Walk one method body tracking the lexically-held lock set."""

    def __init__(self, src: ModuleSource, guards: dict[str, str],
                 result: CheckResult, holders: dict[str, str]):
        self.src = src
        self.guards = guards
        self.result = result
        self.holders = holders

    def scan(self, fn: ast.FunctionDef | ast.AsyncFunctionDef) -> None:
        for stmt in fn.body:
            self._visit(stmt, frozenset())

    def _visit(self, node: ast.stmt, held: frozenset[str]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # closure/thread-target bodies execute later, after the
            # enclosing with-block has released its lock
            for stmt in node.body:
                self._visit(stmt, frozenset())
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            acquired = set(held)
            for item in node.items:
                expr = item.context_expr
                attr = attr_on_self(expr)
                if attr is None and isinstance(expr, ast.Call):
                    # `with self.<holder>(...)`: the declared lock
                    attr = self.holders.get(attr_on_self(expr.func))
                if attr is not None:
                    acquired.add(attr)
            for stmt in node.body:
                self._visit(stmt, frozenset(acquired))
            return
        self._check_stmt(node, held)
        # statements only nest inside statement lists: body/orelse/
        # finalbody of compound statements, except-handler bodies, and
        # match-case bodies (lambdas hold expressions only)
        for _, value in ast.iter_fields(node):
            if isinstance(value, list):
                for child in value:
                    if isinstance(child, ast.stmt):
                        self._visit(child, held)
                    elif isinstance(child, (ast.ExceptHandler,
                                            ast.match_case)):
                        for stmt in child.body:
                            self._visit(stmt, held)

    def _check_stmt(self, node: ast.stmt, held: frozenset[str]) -> None:
        for attr, line in self_attr_write_targets(node):
            lock = self.guards.get(attr)
            if lock is None or lock in held:
                continue
            if self.src.waiver(line, "unguarded") is not None:
                self.result.waivers += 1
                continue
            self.result.findings.append(Finding(
                CHECKER, self.src.path, line,
                f"write to self.{attr} (guarded-by {lock}) outside "
                f"`with self.{lock}:`"))


def check_module(src: ModuleSource) -> CheckResult:
    result = CheckResult()
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.ClassDef):
            continue
        guards = _declared_guards(node, src)
        if not guards:
            continue
        scanner = _WriteScanner(src, guards, result,
                                _declared_holders(node, src))
        for stmt in node.body:
            if (isinstance(stmt, (ast.FunctionDef,
                                  ast.AsyncFunctionDef))
                    and stmt.name != "__init__"):
                scanner.scan(stmt)
    return result


def check_paths(paths: list[str]) -> CheckResult:
    result = CheckResult()
    for path in paths:
        result.merge(check_module(ModuleSource(path)))
    return result
