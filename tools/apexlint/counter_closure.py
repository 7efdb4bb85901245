"""counter-closure: conservation laws between counters, enforced
statically at every increment site.

The runtime's accounting invariants (`evicted == stored + dropped`,
`dropped == sum(drop_reasons)`) today hold because tests assert them
after the fact; a new code path that bumps the left-hand side and
forgets the right-hand term leaks silently until a soak disagrees
with its ledger. Declaring the law at the counter-owning class makes
the leak a lint finding at the exact line:

    class Driver:
        # apexlint: closure(_cold_evicted == _cold_stored + _cold_dropped)

The check: every `self.<lhs> += ...` site in the class's methods must
be post-dominated — within its enclosing loop body if it sits in a
loop, else within its function — by EXACTLY ONE bump of a right-hand
term (`self.<term> += ...` or `self.<term>[...] += ...`; a dict or
per-shard-array term counts through its subscript). The analysis is a
small abstract interpreter over the statement suffix: if/else branches
union, loops contribute {0, 1, 2+} passes, try handlers enter from the
boundary before each body statement, and return/raise/break/continue
terminate a path. Any exit where the term count is not exactly 1 is a
finding.

A bump that is deliberately outside the law is waived at its line
with `# apexlint: closure(reason)` — an argument that does not parse
as an `lhs == a + b` equation is a waiver, one that does is a
declaration.

The same declarations feed a debug-mode runtime hook: `declarations()`
returns them machine-readable, and `check_object(obj, decl)` evaluates
the law on a live object (ints, per-shard numpy arrays, and
reason->count dict terms all compare), so a test can assert
dynamically what the static pass proved.
"""

from __future__ import annotations

import ast
import re

from tools.apexlint.callgraph import CallGraph, ClassInfo, ModuleInfo
from tools.apexlint.common import CheckResult, Finding, ModuleSource

CHECKER = "counter-closure"
WAIVER = "closure"

_EQ_RE = re.compile(
    r"^\s*(?P<lhs>\w+)\s*==\s*(?P<rhs>\w+(?:\s*\+\s*\w+)*)\s*$")

_CAP = 2  # count lattice: 0, 1, 2 ("two or more")


def parse_declaration(arg: str) -> tuple[str, tuple[str, ...]] | None:
    m = _EQ_RE.match(arg)
    if not m:
        return None
    terms = tuple(t.strip() for t in m.group("rhs").split("+"))
    return m.group("lhs"), terms


def _aug_attr(stmt: ast.stmt) -> str | None:
    """Attr name for `self.X += ...` / `self.X[...] += ...`."""
    if not isinstance(stmt, ast.AugAssign) or not isinstance(
            stmt.op, ast.Add):
        return None
    t = stmt.target
    while isinstance(t, ast.Subscript):
        t = t.value
    if isinstance(t, ast.Attribute) and isinstance(
            t.value, ast.Name) and t.value.id == "self":
        return t.attr
    return None


def _sim_block(stmts: list[ast.stmt], state: set[int],
               exits: list[int], terms: tuple[str, ...]) -> set[int]:
    """Abstract-interpret a statement list: `state` is the set of
    possible term-bump counts on entry; paths that leave the region
    (return/raise/break/continue) deposit their count into `exits`;
    the returned set is the fall-through counts (empty if none)."""
    for stmt in stmts:
        if not state:
            return state
        attr = _aug_attr(stmt)
        if attr in terms:
            state = {min(c + 1, _CAP) for c in state}
            continue
        if isinstance(stmt, (ast.Return, ast.Raise, ast.Break,
                             ast.Continue)):
            exits.extend(state)
            return set()
        if isinstance(stmt, ast.If):
            a = _sim_block(stmt.body, set(state), exits, terms)
            b = _sim_block(stmt.orelse, set(state), exits, terms)
            state = a | b
        elif isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
            once = _sim_block(stmt.body, set(state), exits, terms)
            twice = _sim_block(stmt.body, set(once), exits, terms)
            state = state | once | twice
            if stmt.orelse:
                state = _sim_block(stmt.orelse, state, exits, terms)
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            state = _sim_block(stmt.body, state, exits, terms)
        elif isinstance(stmt, ast.Try):
            # the handler can enter from the boundary BEFORE each body
            # statement (an exception interrupts the statement, not the
            # space after the last one) — so `try: op(); stored += 1
            # except: dropped += 1` counts exactly 1, not {1, 2}
            cur = set(state)
            entries: set[int] = set()
            for s in stmt.body:
                entries |= cur
                cur = _sim_block([s], cur, exits, terms)
                if not cur:
                    break
            body = cur
            after = set(body)
            for h in stmt.handlers:
                after |= _sim_block(h.body, set(entries), exits, terms)
            if stmt.orelse:
                after = (after - body) | _sim_block(
                    stmt.orelse, set(body), exits, terms)
            if stmt.finalbody:
                after = _sim_block(stmt.finalbody, after, exits, terms)
            state = after
        # plain statements (Expr/Assign/nested defs/...) don't bump
    return state


def _chain_to(func_node: ast.AST, bump: ast.stmt
              ) -> list[tuple[list[ast.stmt], int, ast.AST]] | None:
    """Path of (block, index, block_owner) from the function body down
    to the block directly holding `bump`."""
    def search(owner: ast.AST) -> list | None:
        for field in ("body", "orelse", "finalbody"):
            block = getattr(owner, field, None)
            if not isinstance(block, list):
                continue
            for i, s in enumerate(block):
                if s is bump:
                    return [(block, i, owner)]
                if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                    continue
                sub = search(s)
                if sub is not None:
                    return [(block, i, owner)] + sub
        for h in getattr(owner, "handlers", []) or []:
            sub = search(h)
            if sub is not None:
                return sub
        return None
    return search(func_node)


def _bump_exit_counts(func_node: ast.AST, bump: ast.stmt,
                      terms: tuple[str, ...]) -> list[int] | None:
    """Possible term-bump counts at every exit of the bump's
    post-dominance region (enclosing loop body, else the function)."""
    chain = _chain_to(func_node, bump)
    if chain is None:
        return None
    # region root: the innermost enclosing loop's body
    start = 0
    for i, (_, _, owner) in enumerate(chain):
        if isinstance(owner, (ast.For, ast.AsyncFor, ast.While)):
            start = i
    exits: list[int] = []
    state: set[int] = {0}
    for block, idx, _ in reversed(chain[start:]):
        state = _sim_block(block[idx + 1:], state, exits, terms)
        if not state:
            break
    exits.extend(state)  # fall off the region root
    return exits


def _class_span(cls: ast.ClassDef) -> tuple[int, int]:
    return cls.lineno, getattr(cls, "end_lineno", cls.lineno)


def _owning_class(mod: ModuleInfo, line: int) -> ClassInfo | None:
    best: ClassInfo | None = None
    for cls in mod.classes.values():
        lo, hi = _class_span(cls.node)
        if lo <= line <= hi:
            if best is None or _class_span(best.node)[0] < lo:
                best = cls
    return best


def check_paths(paths: list[str]) -> CheckResult:
    res = CheckResult()
    sources = []
    for p in paths:
        try:
            sources.append(ModuleSource(p))
        except (SyntaxError, OSError):
            continue
    graph = CallGraph(sources)
    for mod in graph.modules:
        _check_module(graph, mod, res)
    return res


def _declarations_in(mod: ModuleInfo) -> list[dict]:
    out = []
    for line, arg in sorted(mod.src.waivers_of_kind(WAIVER).items()):
        parsed = parse_declaration(arg)
        if parsed is None:
            continue  # a waiver, consumed at its bump site
        lhs, terms = parsed
        cls = _owning_class(mod, line)
        out.append({"path": mod.path, "module": mod.dotted, "line": line,
                    "class": cls.name if cls else None,
                    "lhs": lhs, "terms": list(terms),
                    "expr": f"{lhs} == {' + '.join(terms)}",
                    "_cls": cls})
    return out


def declarations(paths: list[str]) -> list[dict]:
    """Machine-readable closure declarations (the runtime-hook feed):
    [{path, module, line, class, lhs, terms, expr}, ...]. Entries may
    be .py files or package directories (expanded like the CLI scan) —
    a directory silently yielding [] was too easy a footgun."""
    import os

    from tools.apexlint import package_files  # lazy: avoids import cycle

    files: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            files.extend(package_files(p))
        else:
            files.append(p)
    out = []
    for p in files:
        try:
            mod = ModuleInfo(ModuleSource(p))
        except (SyntaxError, OSError):
            continue
        for d in _declarations_in(mod):
            d.pop("_cls")
            out.append(d)
    return out


def check_object(obj, decl: dict) -> None:
    """Debug-mode runtime assertion: evaluate a declaration on a live
    object. Terms/LHS may be ints, numpy arrays (compared
    elementwise), or reason->count mappings (summed). Raises
    AssertionError with both sides on violation."""
    def value(name):
        v = getattr(obj, name)
        if hasattr(v, "values") and callable(v.values):
            return sum(v.values())
        return v
    lhs = value(decl["lhs"])
    rhs = None
    for t in decl["terms"]:
        v = value(t)
        rhs = v if rhs is None else rhs + v
    ok = lhs == rhs
    if hasattr(ok, "all"):
        ok = bool(ok.all())
    if not ok:
        raise AssertionError(
            f"closure violated on {type(obj).__name__}: "
            f"{decl['expr']} (lhs={lhs!r}, rhs={rhs!r})")


def _check_module(graph: CallGraph, mod: ModuleInfo,
                  res: CheckResult) -> None:
    src = mod.src
    decls = _declarations_in(mod)
    for d in decls:
        cls = d.pop("_cls")
        if cls is None:
            res.findings.append(Finding(
                CHECKER, src.path, d["line"],
                f"closure declaration '{d['expr']}' sits outside any "
                "class body — it must live at the counter-owning "
                "class"))
            continue
        lhs, terms = d["lhs"], tuple(d["terms"])
        for meth in graph.method_table(cls).values():
            for stmt in ast.walk(meth.node):
                if not isinstance(stmt, ast.stmt) or \
                        _aug_attr(stmt) != lhs:
                    continue
                waiver = meth.module.src.waiver(stmt.lineno, WAIVER)
                if waiver is not None and \
                        parse_declaration(waiver) is None:
                    res.waivers += 1
                    continue
                counts = _bump_exit_counts(meth.node, stmt, terms)
                if counts is None:
                    continue
                bad = sorted(set(c for c in counts if c != 1))
                if bad:
                    shapes = ", ".join(
                        "a path leaks (0 term bumps)" if c == 0 else
                        "a path double-counts (2+ term bumps)"
                        for c in bad)
                    res.findings.append(Finding(
                        CHECKER, meth.module.src.path, stmt.lineno,
                        f"increment of self.{lhs} is not post-"
                        f"dominated by exactly one bump of "
                        f"{' / '.join(terms)}: {shapes} — breaks the "
                        f"declared closure '{d['expr']}'; waive with "
                        "# apexlint: closure(reason)"))
