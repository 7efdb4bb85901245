"""Remediation-accounting: every actuator call site must be counted.

The remediation plane's trust story (ISSUE 14) is that NO automated
fleet action is invisible: wherever code invokes one of the bounded
actuators — the callables behind `runtime/remediation.Actuators` plus
the serving tier's `force_backpressure` — the enclosing function must
also bump a `remediation_*` obs counter, or the call must carry an
explicit waiver naming where the accounting lives:

    self.serving.force_backpressure(on)  # apexlint: unaccounted(counted centrally in RemediationEngine._apply)

The counter does not have to be on the same line (an actuator that
raises is counted on the failure path), but it must be in the same
function scope — accounting a restart from a different module is how
actions go missing from the run JSONL when the call site is
refactored. Waivers are counted so accounting-by-reference creep
stays visible in the summary (the CLI and tests/test_apexlint.py
read it).

Scope: modules under `/runtime/` — the engine itself, the driver's
actuator wrappers, and the actor host's watchdogs.
"""

from __future__ import annotations

import ast

from tools.apexlint.common import CheckResult, Finding, ModuleSource

CHECKER = "remediation-accounting"

SCOPE_SEGMENTS = ("/runtime/",)

# the attribute names an actuator invocation goes through: the six
# Actuators fields plus the serving tier's direct latch override
ACTUATOR_NAMES = {
    "restart_actor", "quarantine_peer", "pause_actor", "resume_actor",
    "set_backpressure", "set_priority", "force_backpressure",
}


def _scopes(tree: ast.Module):
    """(scope node, nodes owned by that scope) for the module and
    every function — nested function bodies belong to the nested
    function, not the enclosing one (a callback defined inline does
    its own accounting)."""
    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for scope in [tree, *funcs]:
        owned: list[ast.AST] = []
        stack = list(scope.body)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef, ast.Lambda)):
                continue
            owned.append(node)
            stack.extend(ast.iter_child_nodes(node))
        yield scope, owned


def _counts_remediation(nodes: list[ast.AST]) -> bool:
    """True when the scope bumps a remediation_* counter: a call to a
    method named `count` whose first argument is a string literal
    starting with "remediation_"."""
    for node in nodes:
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "count" and node.args):
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str) \
                and arg.value.startswith("remediation_"):
            return True
    return False


def check_module(src: ModuleSource) -> CheckResult:
    result = CheckResult()
    norm = src.path.replace("\\", "/")
    if not any(seg in norm for seg in SCOPE_SEGMENTS):
        return result
    for _scope, owned in _scopes(src.tree):
        calls = [n for n in owned
                 if isinstance(n, ast.Call)
                 and isinstance(n.func, ast.Attribute)
                 and n.func.attr in ACTUATOR_NAMES]
        if not calls:
            continue
        if _counts_remediation(owned):
            continue
        for call in calls:
            if src.waiver(call.lineno, "unaccounted") is not None:
                result.waivers += 1
                continue
            result.findings.append(Finding(
                CHECKER, src.path, call.lineno,
                f"{call.func.attr}() actuator call without a "
                f"remediation_* counter bump in the enclosing "
                f"function — count the action or waive with "
                f"`# apexlint: unaccounted(where it is counted)`"))
    return result


def check_paths(paths: list[str]) -> CheckResult:
    result = CheckResult()
    for path in paths:
        result.merge(check_module(ModuleSource(path)))
    return result
