"""Cross-module call graph for the whole-program checkers.

Stdlib-`ast` only, like everything else in apexlint: modules are
parsed, never imported. The graph indexes every scanned module's
top-level functions, classes (with methods), and import table, then
resolves three call shapes across module boundaries:

- `name(...)`        a module-level function, local or imported via
                     `from x import name [as alias]`
- `self.m(...)`      a method on the enclosing class, walking base
                     classes across modules (DistLearner inherits
                     SingleChipLearner from runtime/learner.py)
- `alias.fn(...)`    a function in another module bound by
                     `import x.y as alias` / `from x import y` where
                     y is itself a module

Module identity is the dotted path derived from the file path, and
imports resolve by dotted-suffix match so the graph works both on the
real package (`ape_x_dqn_tpu.runtime.learner`) and on flat fixture
directories (`from learner import X`). Unresolvable calls (third-party
modules, dynamic dispatch) resolve to None — checkers treat those as
opaque, exactly like the module-local v1 did.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field

from tools.apexlint.common import ModuleSource, dotted_name


@dataclass
class FuncInfo:
    """One function/method definition and where it lives."""
    node: ast.FunctionDef | ast.AsyncFunctionDef | ast.Lambda
    module: "ModuleInfo"
    cls: "ClassInfo | None" = None

    @property
    def name(self) -> str:
        return getattr(self.node, "name", "<lambda>")


@dataclass
class ClassInfo:
    node: ast.ClassDef
    module: "ModuleInfo"
    methods: dict[str, FuncInfo] = field(default_factory=dict)

    @property
    def name(self) -> str:
        return self.node.name


class ModuleInfo:
    """One module's symbol tables: functions, classes, imports."""

    def __init__(self, src: ModuleSource):
        self.src = src
        self.path = src.path
        self.dotted = _dotted_from_path(src.path)
        self.functions: dict[str, FuncInfo] = {}
        self.classes: dict[str, ClassInfo] = {}
        # local name -> (module dotted name, symbol-or-None); symbol
        # None means the local name is a module alias
        self.imports: dict[str, tuple[str, str | None]] = {}
        for node in src.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[node.name] = FuncInfo(node, self)
            elif isinstance(node, ast.ClassDef):
                info = ClassInfo(node, self)
                for item in node.body:
                    if isinstance(item, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        info.methods[item.name] = FuncInfo(item, self,
                                                           info)
                self.classes[node.name] = info
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    local = alias.asname or alias.name.split(".")[0]
                    # `import a.b` binds `a`; `import a.b as c` binds c
                    target = (alias.name if alias.asname
                              else alias.name.split(".")[0])
                    self.imports[local] = (target, None)
            elif isinstance(node, ast.ImportFrom) and node.module:
                for alias in node.names:
                    local = alias.asname or alias.name
                    self.imports[local] = (node.module, alias.name)


def _dotted_from_path(path: str) -> str:
    norm = os.path.normpath(path)
    if norm.endswith(".py"):
        norm = norm[:-3]
    parts = [p for p in norm.split(os.sep) if p not in ("", ".", "..")]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


class CallGraph:
    """Whole-program index over a list of ModuleSources."""

    def __init__(self, sources: list[ModuleSource]):
        self.modules: list[ModuleInfo] = [ModuleInfo(s) for s in sources]
        self._by_dotted: dict[str, ModuleInfo] = {}
        for mod in self.modules:
            self._by_dotted[mod.dotted] = mod

    # -- module / symbol resolution -----------------------------------

    def resolve_module(self, dotted: str) -> ModuleInfo | None:
        """Find a scanned module by dotted name, matching the longest
        dotted suffix (so `runtime.learner` and `learner` both hit
        `ape_x_dqn_tpu.runtime.learner` when unambiguous)."""
        if dotted in self._by_dotted:
            return self._by_dotted[dotted]
        tail = "." + dotted
        hits = [m for d, m in self._by_dotted.items() if d.endswith(tail)]
        return hits[0] if len(hits) == 1 else None

    def resolve_symbol(self, module: ModuleInfo, name: str, _depth: int = 0
                       ) -> FuncInfo | ClassInfo | ModuleInfo | None:
        """A name in `module`'s top-level namespace: local function or
        class, or an imported binding followed across modules."""
        if name in module.functions:
            return module.functions[name]
        if name in module.classes:
            return module.classes[name]
        if name in module.imports and _depth < 8:
            target_mod, symbol = module.imports[name]
            if symbol is None:
                return self.resolve_module(target_mod)
            # `from pkg import mod` where mod is a module, not a symbol
            target = self.resolve_module(target_mod)
            if target is None:
                return self.resolve_module(f"{target_mod}.{symbol}")
            resolved = self.resolve_symbol(target, symbol, _depth + 1)
            if resolved is None:
                return self.resolve_module(f"{target_mod}.{symbol}")
            return resolved
        return None

    # -- class hierarchy ----------------------------------------------

    def bases(self, cls: ClassInfo) -> list[ClassInfo]:
        out: list[ClassInfo] = []
        for base in cls.node.bases:
            resolved: FuncInfo | ClassInfo | ModuleInfo | None = None
            if isinstance(base, ast.Name):
                resolved = self.resolve_symbol(cls.module, base.id)
            elif isinstance(base, ast.Attribute):
                name = dotted_name(base)
                if name is not None:
                    head, _, attr = name.rpartition(".")
                    mod = self.resolve_symbol(cls.module, head) \
                        if "." not in head else self.resolve_module(head)
                    if isinstance(mod, ModuleInfo):
                        resolved = self.resolve_symbol(mod, attr)
            if isinstance(resolved, ClassInfo):
                out.append(resolved)
        return out

    def mro(self, cls: ClassInfo) -> list[ClassInfo]:
        """Linearized ancestry (DFS, left-to-right — close enough to C3
        for lint purposes; the package has no diamond method clashes)."""
        out: list[ClassInfo] = []
        seen: set[int] = set()

        def visit(c: ClassInfo) -> None:
            if id(c.node) in seen:
                return
            seen.add(id(c.node))
            out.append(c)
            for b in self.bases(c):
                visit(b)

        visit(cls)
        return out

    def lookup_method(self, cls: ClassInfo, name: str) -> FuncInfo | None:
        for c in self.mro(cls):
            if name in c.methods:
                return c.methods[name]
        return None

    def method_table(self, cls: ClassInfo) -> dict[str, FuncInfo]:
        """Full resolved method surface: own methods shadow inherited."""
        table: dict[str, FuncInfo] = {}
        for c in reversed(self.mro(cls)):
            table.update(c.methods)
        return table

    def is_base_of_any(self, cls: ClassInfo) -> bool:
        return any(cls.node is b.node
                   for m in self.modules for c in m.classes.values()
                   for b in self.bases(c))

    # -- call resolution -----------------------------------------------

    def resolve_call(self, call: ast.Call, module: ModuleInfo,
                     cls: ClassInfo | None) -> FuncInfo | None:
        func = call.func
        if isinstance(func, ast.Name):
            resolved = self.resolve_symbol(module, func.id)
            return resolved if isinstance(resolved, FuncInfo) else None
        if isinstance(func, ast.Attribute):
            if isinstance(func.value, ast.Name):
                if func.value.id == "self" and cls is not None:
                    return self.lookup_method(cls, func.attr)
                owner = self.resolve_symbol(module, func.value.id)
                if isinstance(owner, ModuleInfo):
                    fn = owner.functions.get(func.attr)
                    return fn
                if isinstance(owner, ClassInfo):
                    return self.lookup_method(owner, func.attr)
            else:
                # a.b.c(...): resolve the dotted receiver as a module
                recv = dotted_name(func.value)
                if recv is not None:
                    owner = self.resolve_module(recv)
                    if isinstance(owner, ModuleInfo):
                        return owner.functions.get(func.attr)
        return None
