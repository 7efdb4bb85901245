"""Learner-parity checker: the learner stacks stay in lockstep.

The sample→loss→optimize→write-back cycle is written twice: once in
`runtime/learner.py` (SingleChipLearner, whose jitted training
endpoints `parallel/dist_learner.py`'s DistLearner inherits, for every
Q-learning family) and once in `runtime/dpg_learner.py` (two nets, two
optimizers, soft targets). A driver is written against one endpoint
surface, so a cross-cutting change must land in both (PR 10 threaded
the in-graph diagnostics through every learner jit by hand). While
DPGLearner stands apart, this checker is the enforcement: it
statically compares the learners' jitted entry-point surfaces and
flags drift — DistLearner's own endpoints (add, add_at, ...) against
the ones it inherits included.

Discovery — a "learner" is any class whose resolved method table
(own + inherited, across modules via the call graph: DistLearner
inherits SingleChipLearner from another file) contains a jit-decorated
`train_step` with `donate_argnums`. Only LEAF classes compare (a base
like SingleChipLearner is represented by its subclasses).

Compared per learner:
- endpoint NAMES: every jitted endpoint present on any learner must be
  present on all (or waived);
- DONATION/STATIC pattern: a shared endpoint whose
  `donate_argnums`/`static_argnums` differ from the modal signature is
  drift — donation asymmetry is exactly how a driver written against
  one learner corrupts state under another;
- `metrics["diag"]` threading: if any learner threads the in-graph
  diagnostics (a `"diag"` key anywhere in its method bodies), all must.

Waivers are deliberate-asymmetry declarations on the CLASS def line:
`# apexlint: parity(<text>)` — a finding is waived only when the
waiver text NAMES the endpoint (or `diag`) it excuses, so a blanket
waiver cannot silently absorb future drift on other endpoints.
"""

from __future__ import annotations

import ast
from collections import Counter

from tools.apexlint.callgraph import CallGraph, ClassInfo
from tools.apexlint.common import CheckResult, Finding, ModuleSource
from tools.apexlint.jit_purity import jit_decorator

CHECKER = "learner-parity"


def _int_tuple(node: ast.expr | None) -> tuple[int, ...]:
    if node is None:
        return ()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        return tuple(e.value for e in node.elts
                     if isinstance(e, ast.Constant)
                     and isinstance(e.value, int))
    return ()


def _jit_signature(fn: ast.FunctionDef | ast.AsyncFunctionDef
                   ) -> tuple[tuple[int, ...], tuple[int, ...]] | None:
    """(donate_argnums, static_argnums) for a jit-decorated method."""
    dec = jit_decorator(fn)
    if dec is None:
        return None
    if not isinstance(dec, ast.Call):
        return ((), ())
    kwargs = {kw.arg: kw.value for kw in dec.keywords if kw.arg}
    return (tuple(sorted(_int_tuple(kwargs.get("donate_argnums")))),
            tuple(sorted(_int_tuple(kwargs.get("static_argnums")))))


def _surface(graph: CallGraph, cls: ClassInfo
             ) -> dict[str, tuple[tuple[int, ...], tuple[int, ...]]]:
    out = {}
    for name, fn in graph.method_table(cls).items():
        sig = _jit_signature(fn.node)
        if sig is not None:
            out[name] = sig
    return out


def _threads_diag(graph: CallGraph, cls: ClassInfo) -> bool:
    for fn in graph.method_table(cls).values():
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Constant) and node.value == "diag":
                return True
    return False


def _class_waiver(cls: ClassInfo) -> str | None:
    return cls.module.src.waiver(cls.node.lineno, "parity")


def _fmt_sig(sig: tuple[tuple[int, ...], tuple[int, ...]]) -> str:
    return f"donate={list(sig[0])}, static={list(sig[1])}"


def check_graph(graph: CallGraph) -> CheckResult:
    result = CheckResult()
    learners: list[ClassInfo] = []
    for mod in graph.modules:
        for cls in mod.classes.values():
            fn = graph.lookup_method(cls, "train_step")
            if fn is None:
                continue
            sig = _jit_signature(fn.node)
            if sig is not None and sig[0]:
                learners.append(cls)
    leaves = [c for c in learners if not graph.is_base_of_any(c)]
    if len(leaves) < 2:
        return result

    surfaces = {c.name: _surface(graph, c) for c in leaves}
    all_endpoints = sorted(set().union(*surfaces.values()))
    any_diag = any(_threads_diag(graph, c) for c in leaves)

    def emit(cls: ClassInfo, token: str, message: str) -> None:
        waiver = _class_waiver(cls)
        if waiver is not None and token in waiver:
            result.waivers += 1
            return
        result.findings.append(Finding(
            CHECKER, cls.module.src.path, cls.node.lineno, message))

    for cls in leaves:
        surface = surfaces[cls.name]
        others = [c.name for c in leaves if c.name != cls.name]
        for ep in all_endpoints:
            if ep not in surface:
                have = [n for n in others if ep in surfaces[n]]
                emit(cls, ep,
                     f"learner {cls.name} is missing jitted endpoint "
                     f"{ep}() (present on {', '.join(have)}) — the "
                     f"variants must stay in lockstep (ROADMAP item 5) "
                     f"or declare the asymmetry in a parity waiver")
                continue
            sigs = Counter(surfaces[n][ep] for n in surfaces
                           if ep in surfaces[n])
            modal, count = sigs.most_common(1)[0]
            if surface[ep] != modal and count > 1:
                emit(cls, ep,
                     f"learner {cls.name}.{ep}() has jit signature "
                     f"{_fmt_sig(surface[ep])} but the other learners "
                     f"use {_fmt_sig(modal)} — donation-pattern drift "
                     f"corrupts state for callers written against the "
                     f"majority contract")
        if any_diag and not _threads_diag(graph, cls):
            emit(cls, "diag",
                 f"learner {cls.name} does not thread "
                 f"metrics[\"diag\"] while the other learners do — "
                 f"the learning-health plane (PR 10) goes blind for "
                 f"this variant")
    result.findings.sort(key=lambda f: (f.path, f.line))
    return result


def check_paths(paths: list[str]) -> CheckResult:
    return check_graph(CallGraph([ModuleSource(p) for p in paths]))
