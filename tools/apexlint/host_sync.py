"""Hidden-host-sync checker: no silent device round-trips on the hot path.

The throughput story (PERF.md's roofline; Horgan 2018's whole claim)
dies by a thousand `.item()`s: every host materialization of a jit
output (`float(m["loss"])`, `np.asarray(device_array)`,
`jax.block_until_ready`, `jax.device_get`) blocks the dispatch queue
and serializes the learner against the device. The measured complement
is the PR 8 device-time plane; this checker is the static half — it
flags sync-shaped calls inside the hot-path modules unless they sit in
an observability window or carry a justification.

Scope — a module is hot when its basename is one of the learner/ingest
files (HOT_BASENAMES) or it carries an `# apexlint-scope: hot-path`
comment (how fixtures opt in). Inside `runtime/driver.py` only the
train-loop functions are hot (DRIVER_HOT_FUNCS): checkpointing,
staging-buffer numpy work, and teardown are host-side by design.

Flagged calls: `.item()`, `np.asarray`/`np.array` on a value,
`float(<name/attr/subscript>)` (a direct device-value fetch —
`float(np.mean(host_list))` stays quiet), `jax.block_until_ready`,
`jax.device_get`.

Allowed windows (lexical containment):
- `with obs.span(...)` / `with obs.stage_window(...)` bodies — the
  measured-sync points the perf plane rides;
- `if <...>.enabled:` / `if windowed:` bodies — obs-gated branches
  that only pay the sync when observability asked for it.

Sanitized values: after `x = jax.device_get(...)` / `x =
jax.block_until_ready(...)` / `x = np.asarray(...)` / `x = float(...)`
the name `x` is host-side, so later `float(x[...])`/`x.item()` reads
are free and stay quiet. A sanitizer inside an allowed window only
covers reads inside that same window (the un-observed branch never ran
it); an unwindowed sanitizer (itself flagged or waived — one explicit
sync covering the batch) sanitizes the rest of the function.

Waive with `# apexlint: host-sync(<why>)` on the call line, or on the
`def` line to waive a whole documented-off-hot-loop function (each
suppressed site still counts toward the waiver total, so creep stays
visible in the summary's `waivers`).
"""

from __future__ import annotations

import ast
import os

from tools.apexlint.common import (
    CheckResult, Finding, ModuleSource, dotted_name)

CHECKER = "host-sync"

HOT_BASENAMES = {"learner.py", "dist_learner.py", "dpg_learner.py",
                 "ingest.py"}
DRIVER_HOT_FUNCS = {"_learner_loop", "_learner_loop_inner",
                    "_publish_params", "_ship_staged",
                    "_ship_staged_cold"}
SCOPE_MARK = "apexlint-scope: hot-path"

WINDOW_WITH_ATTRS = {"span", "stage_window"}
WINDOW_IF_NAMES = {"windowed"}
WINDOW_IF_ATTRS = {"enabled"}
SYNC_FULL = {"jax.block_until_ready", "jax.device_get"}


def _base_name(expr: ast.expr) -> str | None:
    """The root Name of a Name/Attribute/Subscript chain."""
    while isinstance(expr, (ast.Attribute, ast.Subscript)):
        expr = expr.value
    return expr.id if isinstance(expr, ast.Name) else None


def _sync_kind(call: ast.Call) -> tuple[str, ast.expr | None] | None:
    """(description, synced-value-expr) when `call` is sync-shaped."""
    func = call.func
    name = dotted_name(func)
    if name in SYNC_FULL:
        return (f"{name}() blocks on device completion",
                call.args[0] if call.args else None)
    if name is not None:
        head, _, attr = name.rpartition(".")
        if head in ("np", "numpy") and attr in ("asarray", "array"):
            return (f"{head}.{attr}() pulls a device value to host",
                    call.args[0] if call.args else None)
    if (isinstance(func, ast.Attribute) and func.attr == "item"
            and not call.args and not call.keywords):
        return (".item() blocks on a device->host transfer", func.value)
    if (isinstance(func, ast.Name) and func.id == "float"
            and len(call.args) == 1
            and isinstance(call.args[0],
                           (ast.Name, ast.Attribute, ast.Subscript))):
        return ("float() on a device value blocks on a device->host "
                "transfer", call.args[0])
    return None


def _is_window(node: ast.AST) -> bool:
    if isinstance(node, (ast.With, ast.AsyncWith)):
        for item in node.items:
            call = item.context_expr
            if (isinstance(call, ast.Call)
                    and isinstance(call.func, ast.Attribute)
                    and call.func.attr in WINDOW_WITH_ATTRS):
                return True
        return False
    if isinstance(node, ast.If):
        for sub in ast.walk(node.test):
            if isinstance(sub, ast.Name) and sub.id in WINDOW_IF_NAMES:
                return True
            if isinstance(sub, ast.Attribute) \
                    and sub.attr in WINDOW_IF_ATTRS:
                return True
    return False


def _window_spans(fn: ast.AST) -> list[tuple[int, int]]:
    spans = []
    for node in ast.walk(fn):
        if _is_window(node):
            spans.append((node.lineno, node.end_lineno or node.lineno))
    return spans


def _in_window(line: int, spans: list[tuple[int, int]]) -> bool:
    return any(lo <= line <= hi for lo, hi in spans)


def _hot_functions(src: ModuleSource) -> list[ast.AST]:
    base = os.path.basename(src.path)
    marked = any(SCOPE_MARK in c for c in src.comments.values())
    driver = base == "driver.py"
    if not (marked or base in HOT_BASENAMES or driver):
        return []
    out: list[ast.AST] = []
    for node in ast.walk(src.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if driver and not marked \
                    and node.name not in DRIVER_HOT_FUNCS:
                continue
            out.append(node)
    if driver and not marked:
        return out
    # non-driver hot modules: every function is in scope; drop nested
    # duplicates (ast.walk yields inner defs too — the outer walk of
    # each function already covers them)
    roots, covered = [], set()
    for node in out:
        if id(node) in covered:
            continue
        roots.append(node)
        for sub in ast.walk(node):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and sub is not node:
                covered.add(id(sub))
    return roots


def _def_waived(src: ModuleSource,
                fn: ast.AST) -> bool:
    line = getattr(fn, "lineno", 0)
    for dec in getattr(fn, "decorator_list", []):
        if src.waiver(dec.lineno, CHECKER) is not None:
            return True
    return src.waiver(line, CHECKER) is not None


def _sanitizers(fn: ast.AST) -> list[tuple[int, str, bool]]:
    """(line, name, unwindowed) for `x = <sync-call>(...)` rebinds."""
    spans = _window_spans(fn)
    out = []
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)):
            continue
        kind = _sync_kind(node.value)
        if kind is None:
            continue
        for tgt in node.targets:
            if isinstance(tgt, ast.Name):
                out.append((node.lineno, tgt.id,
                            not _in_window(node.lineno, spans)))
    return out


def check_module(src: ModuleSource) -> CheckResult:
    result = CheckResult()
    for fn in _hot_functions(src):
        fn_waived = _def_waived(src, fn)
        spans = _window_spans(fn)
        sanitizers = _sanitizers(fn)
        seen_lines: set[int] = set()
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            kind = _sync_kind(node)
            if kind is None or node.lineno in seen_lines:
                continue
            desc, value = kind
            line = node.lineno
            if _in_window(line, spans):
                continue
            root = _base_name(value) if value is not None else None
            # reads inside windows were skipped above, so only the
            # unwindowed (explicitly flagged-or-waived) sanitizers can
            # cover what remains
            if root is not None and any(
                    s_line < line and s_name == root and unwin
                    for s_line, s_name, unwin in sanitizers):
                continue
            seen_lines.add(line)
            if fn_waived or src.waiver(line, CHECKER) is not None:
                result.waivers += 1
                continue
            result.findings.append(Finding(
                CHECKER, src.path, line,
                f"{desc} on the hot path "
                f"({getattr(fn, 'name', '<fn>')}()) — move it inside an "
                f"obs window, batch it through one explicit waived "
                f"fetch, or keep the value on-device"))
    return result


def check_paths(paths: list[str]) -> CheckResult:
    result = CheckResult()
    for path in paths:
        result.merge(check_module(ModuleSource(path)))
    result.findings.sort(key=lambda f: (f.path, f.line))
    return result
