"""Retry-annotation: swallowed socket errors must be observable.

The elastic-fleet contract (PR 7) is that EVERY dropped send, failed
pull, or torn connection surfaces somewhere an operator can see —
never a bare `except OSError: pass`. PR 16 extends the same contract
to `replay/`: the disk spill rung does real file IO off the ingest
thread, and a swallowed OSError there is a silently lost replay
segment — exactly the loss class this checker exists to surface.
PR 18's `comm/shm_transport.py` sits in the same scope: attaching or
unlinking a /dev/shm segment is file IO, and a swallowed failure
there silently downgrades a granted shm connection to TCP — that
downgrade must be counted (shm_fallbacks) or carry a lossy waiver
naming why the loss is benign. In
`comm/`, `runtime/`, and `replay/` modules, any except handler typed
on a socket-ish/IO error class
(OSError, ConnectionError and its subclasses, socket.error,
socket.timeout, TimeoutError, BrokenPipeError, InterruptedError) that
*swallows* the exception (no `raise` anywhere in the handler body)
must do at least one of:

- emit an obs signal: call a method named `count` / `inc` / `log` /
  `warning` / `error` / `exception` inside the handler, or
- bump an accounting attribute: `+=` onto a name containing `drop`,
  `error`, `disconnect`, or `fail`, or
- carry an explicit lossy waiver on the `except` line or on its
  first statement:

      except OSError:  # apexlint: lossy(close-path best effort)
          pass

The waiver text is the justification; waivers are counted so silent-
loss creep stays visible in the summary (the CLI and
tests/test_apexlint.py read it). Handlers that
re-raise (even conditionally) are exempt — they don't swallow.
"""

from __future__ import annotations

import ast

from tools.apexlint.common import CheckResult, Finding, ModuleSource

CHECKER = "retry-annotation"

# paths under these package segments are in scope: the transport and
# the runtime are where a swallowed socket error means silent data
# loss, and the replay tier (disk spill rung, PR 16) is where a
# swallowed file-IO error means a silently lost segment
SCOPE_SEGMENTS = ("/comm/", "/runtime/", "/replay/")

SOCKET_ERROR_NAMES = {
    "OSError", "IOError", "ConnectionError", "ConnectionResetError",
    "ConnectionRefusedError", "ConnectionAbortedError",
    "BrokenPipeError", "TimeoutError", "InterruptedError",
    "socket.error", "socket.timeout", "socket_mod.error",
    "socket_mod.timeout",
}

OBS_CALL_NAMES = {"count", "inc", "log", "warning", "error",
                  "exception"}

ACCOUNTING_SUBSTRINGS = ("drop", "error", "disconnect", "fail")


def _exc_names(node: ast.expr | None) -> list[str]:
    """Dotted names of the exception types an `except` clause catches
    (a Tuple catches several)."""
    if node is None:
        return []
    if isinstance(node, ast.Tuple):
        out: list[str] = []
        for e in node.elts:
            out.extend(_exc_names(e))
        return out
    parts: list[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return [".".join(reversed(parts))]
    return []


def _swallows(handler: ast.ExceptHandler) -> bool:
    """True when no `raise` is reachable anywhere in the handler body
    (nested function bodies don't count: a callback defined inside the
    handler doesn't re-raise on this path)."""
    for stmt in handler.body:
        for node in ast.walk(stmt):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue
            if isinstance(node, ast.Raise):
                return False
    return True


def _accounts(handler: ast.ExceptHandler) -> bool:
    """True when the handler emits an obs signal or bumps an
    accounting attribute."""
    for stmt in handler.body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                fn = node.func
                name = (fn.attr if isinstance(fn, ast.Attribute)
                        else fn.id if isinstance(fn, ast.Name)
                        else None)
                if name in OBS_CALL_NAMES:
                    return True
            if isinstance(node, ast.AugAssign) \
                    and isinstance(node.op, ast.Add):
                target = node.target
                attr = (target.attr if isinstance(target, ast.Attribute)
                        else target.id if isinstance(target, ast.Name)
                        else "")
                if any(s in attr.lower()
                       for s in ACCOUNTING_SUBSTRINGS):
                    return True
            # handler delegates to a self._note_*/self._on_* helper:
            # the accounting lives one call down (the transport's
            # _note_send_failure pattern) — accept the delegation
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr.startswith(("_note_", "_on_")):
                return True
    return False


def check_module(src: ModuleSource) -> CheckResult:
    result = CheckResult()
    norm = src.path.replace("\\", "/")
    if not any(seg in norm for seg in SCOPE_SEGMENTS):
        return result
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        caught = _exc_names(node.type)
        if not any(n in SOCKET_ERROR_NAMES for n in caught):
            continue
        if not _swallows(node):
            continue
        if _accounts(node):
            continue
        # the waiver may sit on the `except` line or on the handler's
        # first statement (`pass  # apexlint: lossy(...)`)
        waiver_lines = [node.lineno]
        if node.body:
            waiver_lines.append(node.body[0].lineno)
        if any(src.waiver(ln, "lossy") is not None
               for ln in waiver_lines):
            result.waivers += 1
            continue
        result.findings.append(Finding(
            CHECKER, src.path, node.lineno,
            f"except {'/'.join(caught)} swallows a socket error "
            f"without emitting an obs counter or accounting bump — "
            f"count the loss or waive with "
            f"`# apexlint: lossy(reason)`"))
    return result


def check_paths(paths: list[str]) -> CheckResult:
    result = CheckResult()
    for path in paths:
        result.merge(check_module(ModuleSource(path)))
    return result
