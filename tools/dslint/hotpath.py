"""Pass 1 — hot-path d2h/sync lint (rules ``hot-path-sync``,
``hot-path-d2h-shape``, ``hot-path-missing``).

The PR 2 serving contract: one scheduler step = ONE compiled device
program + ONE token-sized device→host transfer.  This pass verifies it
instead of asserting it:

- Functions marked ``# dslint: hot-path`` (scheduler dispatch/drain,
  ``model._*_step_impl``, engine commit) may not contain host-sync
  constructs: ``np.asarray``/``np.array`` on non-literal arguments,
  ``.item()``/``.tolist()``/``.block_until_ready()``,
  ``jax.device_get``, or ``float()``/``int()``/``bool()`` forcing a
  ``jnp``/``jax`` computation or a ``*_dev`` value to the host.
- The ONLY exceptions are lines carrying a structured
  ``# dslint: d2h <shape>`` annotation (the promoted form of the old
  ``# the ONLY d2h`` comments) whose shape appears verbatim in
  docs/DESIGN.md's transfer contract — so the allowlist itself is
  cross-checked against the documented contract, and an undocumented
  shape cannot be waved through.
- Coverage is closed both ways: every function matching the
  REQUIRED_HOT_PATHS table must carry the annotation (a new
  ``_*_step_impl`` cannot silently opt out), and a table entry that no
  longer matches any function fails too (a rename must update the
  table, keeping it honest).
"""

from __future__ import annotations

import ast
import re
from typing import List, Optional, Tuple

from .core import (Finding, Project, SourceFile, register_rules,
                   root_name as _root_name)

register_rules("hot-path-sync", "hot-path-d2h-shape", "hot-path-missing")

#: (file, function-name regex): every match must be hot-path annotated
REQUIRED_HOT_PATHS: Tuple[Tuple[str, str], ...] = (
    ("deepspeed_tpu/inference/v2/scheduler.py",
     r"^(_drain_impl|_step_impl|_plan_step|_dispatch_spec"
     r"|_dispatch_draft_spec)$"),
    ("deepspeed_tpu/inference/v2/model.py",
     r"^(_\w*step_impl|_assemble_logits)$"),
    ("deepspeed_tpu/inference/v2/engine.py",
     r"^(_commit_batch|commit_spec)$"),
)

DESIGN_PATH = "docs/DESIGN.md"
#: shapes validate against THIS section when present (a shape string
#: appearing in unrelated prose must not legitimize a transfer);
#: docs without the section (fixtures) fall back to the whole text
CONTRACT_HEADING = "### The transfer contract"

#: builtin casts that force a device value to the host when applied to
#: a fresh jax computation
_CASTS = {"float", "int", "bool"}
#: host-func roots whose results are never device values (keeps
#: ``int(getattr(...))``-style code out of the cast check)
_DEVICE_ROOTS = {"jnp", "jax"}


def _is_dev_expr(node: ast.AST) -> bool:
    """Names/attributes following the ``*_dev`` device-value naming
    convention (``tokens_dev``, ``out_dev``)."""
    if isinstance(node, ast.Name):
        return node.id.endswith("_dev")
    if isinstance(node, ast.Attribute):
        return node.attr.endswith("_dev")
    return False


def _sync_reason(call: ast.Call) -> Optional[str]:
    """Why this call is a host sync, or None."""
    func = call.func
    if isinstance(func, ast.Attribute):
        root = _root_name(func.value)
        if func.attr in ("asarray", "array") and root in ("np", "numpy"):
            arg = call.args[0] if call.args else None
            if arg is None or isinstance(
                    arg, (ast.List, ast.Tuple, ast.Constant)):
                return None     # host-literal construction, not a sync
            return f"np.{func.attr}() on a potentially device value"
        if func.attr in ("item", "tolist") and not call.args:
            return f".{func.attr}() host sync"
        if func.attr == "block_until_ready":
            return ".block_until_ready() host sync"
        if func.attr == "device_get" and root == "jax":
            return "jax.device_get() host sync"
        return None
    if isinstance(func, ast.Name) and func.id in _CASTS \
            and len(call.args) == 1:
        arg = call.args[0]
        if isinstance(arg, ast.Call) and _root_name(arg) in _DEVICE_ROOTS:
            return (f"{func.id}() forces a {_root_name(arg)} "
                    "computation to the host")
        if _is_dev_expr(arg):
            return f"{func.id}() on a device value"
    return None


def contract_text(design: str) -> str:
    """The transfer-contract section of the design doc (up to the next
    heading), or the whole text when the heading is absent."""
    start = design.find(CONTRACT_HEADING)
    if start < 0:
        return design
    m = re.search(r"\n#{2,3} ", design[start + len(CONTRACT_HEADING):])
    end = start + len(CONTRACT_HEADING) + (m.start() if m
                                           else len(design))
    return design[start:end]


def _lint_function(sf: SourceFile, func: ast.AST, design: str
                   ) -> List[Finding]:
    out: List[Finding] = []
    qual = func.name
    for node in ast.walk(func):
        if not isinstance(node, ast.Call):
            continue
        reason = _sync_reason(node)
        if reason is None:
            continue
        line = node.lineno
        shape = sf.d2h_annotation(line)
        snippet = (sf.lines[line - 1].split("#")[0].strip()
                   if line - 1 < len(sf.lines) else "")
        if shape is not None:
            # declared transfer: allowed iff the shape is part of the
            # documented contract
            if shape and shape in design:
                continue
            out.append(Finding(
                "hot-path-d2h-shape", sf.rel, line,
                f"declared d2h shape {shape!r} in {qual}() is not in "
                f"the {DESIGN_PATH} transfer contract — token-sized "
                "transfers must be documented before they ship",
                detail=f"{qual}:{shape}"))
            continue
        if sf.suppressed("hot-path-sync", line):
            continue
        out.append(Finding(
            "hot-path-sync", sf.rel, line,
            f"host sync in hot path {qual}(): {reason} "
            f"[`{snippet}`] — annotate an intentional token-sized "
            "transfer with '# dslint: d2h <shape>' or suppress with "
            "a reason",
            detail=f"{qual}:{snippet}"))
    return out


def run(project: Project,
        required=REQUIRED_HOT_PATHS,
        design_path: str = DESIGN_PATH) -> List[Finding]:
    findings: List[Finding] = []
    design = contract_text(project.doc(design_path))

    # coverage: the contract functions must be annotated
    for rel, pattern in required:
        sf = project.file(rel)
        if sf is None:
            findings.append(Finding(
                "hot-path-missing", rel, 0,
                f"hot-path contract file missing from the scan "
                f"(expected functions matching {pattern!r})",
                detail=f"file:{pattern}"))
            continue
        rx = re.compile(pattern)
        matched = False
        for func in sf.functions():
            if not rx.match(func.name):
                continue
            matched = True
            if not sf.func_annotated(func, "hot-path") \
                    and not sf.suppressed("hot-path-missing",
                                          func.lineno):
                findings.append(Finding(
                    "hot-path-missing", sf.rel, func.lineno,
                    f"{func.name}() matches the serving hot-path "
                    f"contract ({pattern!r}) but is not annotated "
                    "'# dslint: hot-path' — the d2h lint cannot see "
                    "it",
                    detail=func.name))
        if not matched:
            findings.append(Finding(
                "hot-path-missing", sf.rel, 0,
                f"no function matches hot-path contract {pattern!r} — "
                "renamed hot paths must update "
                "tools/dslint/hotpath.py:REQUIRED_HOT_PATHS",
                detail=f"none:{pattern}"))

    # the lint itself: every annotated function, required or not
    for sf in project.files():
        for func in sf.functions():
            if sf.func_annotated(func, "hot-path"):
                findings.extend(_lint_function(sf, func, design))
    return findings
