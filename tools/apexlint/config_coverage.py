"""Config-knob coverage checker: no dead knobs, no phantom docs.

Both directions of config/doc drift:

1. Every field declared in a `configs.py` dataclass must be READ
   somewhere in the package (an `x.<field>` attribute load or a
   `getattr(x, "<field>", ...)` outside configs.py itself). A field
   nobody reads is a knob the operator turns that does nothing — the
   worst kind of config bug, because the run silently ignores the
   intent (this checker's first catch: `actors.param_pull_every`,
   documented as the pull cadence and wired to nothing). Waive a
   deliberately-dormant field with `# apexlint: unread(<why>)` on its
   declaration line.

2. Every `replay.` / `comm.` / `obs.` / `actors.` / `serving.` /
   `remediation.` knob mentioned in README must exist as a field on
   the matching dataclass (`PREFIX_TO_CLASS`), and so must every
   `<block>.` knob (as in `network.<block>.shard_count`) of a block of
   NetworkConfig: a field there whose annotation names a dataclass of
   configs.py is a block, read off the file, so a new one needs no
   edit here.
   Mentions
   that name a package MODULE instead of a knob (`obs.health`,
   `obs.report` — `ape_x_dqn_tpu/obs/health.py` exists) are skipped.

Reads are detected purely syntactically (any attribute load with the
field's name counts, whatever the receiver) — the checker errs quiet:
a false "read" hides a dead knob, a false "unread" would block CI on
working code. Dynamic access through the `--set dotted.key=value`
override machinery deliberately does NOT count as a read: being
settable is not being honored.
"""

from __future__ import annotations

import ast
import os
import re

from tools.apexlint.common import CheckResult, Finding, ModuleSource

CHECKER = "config-coverage"

PREFIX_TO_CLASS = {"replay": "ReplayConfig", "comm": "CommConfig",
                   "obs": "ObsConfig", "actors": "ActorConfig",
                   "serving": "ServingConfig",
                   "remediation": "RemediationConfig"}


def network_blocks(src: ModuleSource, classes: dict) -> dict[str, str]:
    """{field: class} of NetworkConfig's blocks: its fields whose
    annotation names a dataclass of the same file."""
    for node in src.tree.body:
        if isinstance(node, ast.ClassDef) and node.name == "NetworkConfig":
            return {item.target.id: item.annotation.id
                    for item in node.body
                    if isinstance(item, ast.AnnAssign)
                    and isinstance(item.annotation, ast.Name)
                    and item.annotation.id in classes}
    return {}


def _is_dataclass(cls: ast.ClassDef) -> bool:
    for dec in cls.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = target.attr if isinstance(target, ast.Attribute) else (
            target.id if isinstance(target, ast.Name) else "")
        if name == "dataclass":
            return True
    return False


def dataclass_fields(src: ModuleSource) -> dict[str, dict[str, int]]:
    """{class name: {field name: declaration line}}."""
    out: dict[str, dict[str, int]] = {}
    for node in src.tree.body:
        if not (isinstance(node, ast.ClassDef) and _is_dataclass(node)):
            continue
        fields: dict[str, int] = {}
        for item in node.body:
            if isinstance(item, ast.AnnAssign) \
                    and isinstance(item.target, ast.Name):
                fields[item.target.id] = item.lineno
        out[node.name] = fields
    return out


def _attribute_reads(paths: list[str], skip: str) -> set[str]:
    reads: set[str] = set()
    for path in paths:
        if os.path.abspath(path) == skip:
            continue
        src = ModuleSource(path)
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Attribute) \
                    and isinstance(node.ctx, ast.Load):
                reads.add(node.attr)
            elif (isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Name)
                  and node.func.id in ("getattr", "hasattr")
                  and len(node.args) >= 2
                  and isinstance(node.args[1], ast.Constant)
                  and isinstance(node.args[1].value, str)):
                reads.add(node.args[1].value)
    return reads


def _module_exists(paths: list[str], prefix: str, attr: str) -> bool:
    tail = os.path.join(prefix, f"{attr}.py")
    return any(os.path.normpath(p).endswith(tail) for p in paths)


def check(paths: list[str], configs_path: str | None = None,
          readme_path: str | None = None) -> CheckResult:
    result = CheckResult()
    if configs_path is None:
        configs_path = next(
            (p for p in paths
             if os.path.basename(p) == "configs.py"), None)
    if configs_path is None:
        return result
    configs_src = ModuleSource(configs_path)
    classes = dataclass_fields(configs_src)

    # direction 1: declared but never read (a block of NetworkConfig is
    # fetched by the name models.DECODERS holds, `getattr(net_cfg, name)`:
    # its own fields are the knobs)
    blocks = network_blocks(configs_src, classes)
    reads = _attribute_reads(paths, os.path.abspath(configs_path))
    for cls_name, fields in classes.items():
        for field, line in fields.items():
            if field in reads or (cls_name == "NetworkConfig"
                                  and field in blocks):
                continue
            if configs_src.waiver(line, "unread") is not None:
                result.waivers += 1
                continue
            result.findings.append(Finding(
                CHECKER, configs_src.path, line,
                f"{cls_name}.{field} is declared (and settable via "
                f"--set) but read nowhere in the package — a knob "
                f"that does nothing; wire it or drop it"))

    # direction 2: README knobs that don't exist
    if readme_path and os.path.exists(readme_path):
        prefixes = {**PREFIX_TO_CLASS, **blocks}
        knob_re = re.compile(
            r"\b(" + "|".join(prefixes) + r")\.([a-z_][a-z0-9_]*)")
        with open(readme_path, encoding="utf-8") as fh:
            for lineno, text in enumerate(fh, start=1):
                for m in knob_re.finditer(text):
                    prefix, attr = m.group(1), m.group(2)
                    if attr == "py":
                        continue  # `remediation.py` is a filename
                    cls_name = prefixes[prefix]
                    fields = classes.get(cls_name)
                    if fields is None or attr in fields:
                        continue
                    if _module_exists(paths, prefix, attr):
                        continue  # `obs.health` names a module, not a knob
                    result.findings.append(Finding(
                        CHECKER, readme_path, lineno,
                        f"README names knob {prefix}.{attr} but "
                        f"{cls_name} has no field `{attr}` — stale "
                        f"doc or missing config"))
    result.findings.sort(key=lambda f: (f.path, f.line))
    return result
