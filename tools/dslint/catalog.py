"""Pass 5 — catalog closure (rules ``metric-catalog``, ``chaos-site``,
``flight-event``, ``env-doc``).

Every name-shaped registry in the system must be CLOSED: a name used
anywhere in the production tree must be registered, and a registered
name must be used — otherwise the catalogs rot in both directions
(phantom names that silently no-op; dead entries that document nothing).

- ``metric-catalog``: the absorbed ``tools/check_metrics.py`` lint
  (naming convention, DESIGN.md documentation, help text, dead-metric
  scan) — see :mod:`tools.dslint.metrics_catalog`.
- ``chaos-site``: every site name passed to the fault-injection
  registry (``fire`` / ``has_site`` / ``maybe_raise`` /
  ``site_value``) must exist in ``fault_injection.SITES``, and every
  registered site must be exercised somewhere outside the registry —
  a ``DS_CHAOS`` spec naming an unknown site already raises at arm
  time; this closes the static side so the name can't drift in code.
- ``flight-event``: every literal event kind recorded into the flight
  recorder (``.record("...")`` / ``._record("...")`` /
  ``._record_event("...")``) must be registered in
  ``flight_recorder.EVENT_KINDS``, and every registered kind must be
  recorded somewhere — postmortem consumers grep by kind.
- ``env-doc``: every ``DS_*`` environment variable the production
  tree reads must appear in docs/DESIGN.md or README.md — an
  undocumented env knob is an unsupported one.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, Project, register_rules

register_rules("metric-catalog", "chaos-site", "flight-event",
               "env-doc")

FAULT_INJECTION = "deepspeed_tpu/runtime/fault_injection.py"
FLIGHT_RECORDER = "deepspeed_tpu/telemetry/flight_recorder.py"
DOC_PATHS = ("docs/DESIGN.md", "README.md")

_SITE_METHODS = {"fire", "has_site", "maybe_raise", "site_value"}
_EVENT_METHODS = {"record", "_record", "_record_event"}


def _literal_str_arg(call: ast.Call) -> Optional[str]:
    if call.args and isinstance(call.args[0], ast.Constant) and \
            isinstance(call.args[0].value, str):
        return call.args[0].value
    return None


def _dict_literal_keys(tree: ast.AST, name: str) -> Optional[Set[str]]:
    """String keys of a module-level ``NAME: ... = {...}`` dict."""
    for node in ast.walk(tree):
        target = None
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name):
            target, value = node.targets[0].id, node.value
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name):
            target, value = node.target.id, node.value
        if target == name and isinstance(value, ast.Dict):
            return {k.value for k in value.keys
                    if isinstance(k, ast.Constant) and
                    isinstance(k.value, str)}
    return None


def _set_literal(tree: ast.AST, name: str) -> Optional[Set[str]]:
    """String members of a module-level ``NAME = frozenset({...})`` /
    ``NAME = {...}`` set literal."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == name):
            continue
        value = node.value
        if isinstance(value, ast.Call) and \
                getattr(value.func, "id", "") == "frozenset" and \
                value.args:
            value = value.args[0]
        if isinstance(value, (ast.Set,)):
            return {e.value for e in value.elts
                    if isinstance(e, ast.Constant) and
                    isinstance(e.value, str)}
    return None


# -- chaos sites -------------------------------------------------------------
def check_chaos_sites(project: Project,
                      registry_path: str = FAULT_INJECTION
                      ) -> List[Finding]:
    out: List[Finding] = []
    reg = project.file(registry_path)
    if reg is None:
        return [Finding("chaos-site", registry_path, 0,
                        "fault-injection registry missing from scan",
                        detail="missing-module")]
    sites = _dict_literal_keys(reg.tree, "SITES")
    if sites is None:
        return [Finding("chaos-site", registry_path, 0,
                        "SITES dict literal not found — the site "
                        "catalog must stay statically readable",
                        detail="no-SITES")]
    used: Set[str] = set()
    for sf in project.files():
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Attribute) and
                    node.func.attr in _SITE_METHODS):
                continue
            site = _literal_str_arg(node)
            if site is None:
                continue    # dynamic dispatch: runtime validation owns it
            if sf.rel != registry_path:
                used.add(site)
            if site not in sites and not sf.suppressed(
                    "chaos-site", node.lineno):
                out.append(Finding(
                    "chaos-site", sf.rel, node.lineno,
                    f"unknown fault-injection site {site!r} — register "
                    f"it in {registry_path}:SITES (known: "
                    f"{sorted(sites)})",
                    detail=f"unknown:{site}"))
    for site in sorted(sites - used):
        out.append(Finding(
            "chaos-site", registry_path, 0,
            f"site {site!r} is registered in SITES but never "
            "exercised (fire/has_site/maybe_raise/site_value) in the "
            "production tree — dead chaos coverage",
            detail=f"dead:{site}"))
    return out


# -- flight events -----------------------------------------------------------
def check_flight_events(project: Project,
                        recorder_path: str = FLIGHT_RECORDER
                        ) -> List[Finding]:
    out: List[Finding] = []
    rec = project.file(recorder_path)
    if rec is None:
        return [Finding("flight-event", recorder_path, 0,
                        "flight recorder missing from scan",
                        detail="missing-module")]
    kinds = _set_literal(rec.tree, "EVENT_KINDS")
    if kinds is None:
        return [Finding("flight-event", recorder_path, 0,
                        "EVENT_KINDS set literal not found — the "
                        "event-kind catalog must stay statically "
                        "readable", detail="no-EVENT_KINDS")]
    used: Set[str] = set()
    for sf in project.files():
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Attribute) and
                    node.func.attr in _EVENT_METHODS):
                continue
            kind = _literal_str_arg(node)
            if kind is None:
                continue    # wrappers forward a variable; their
                # literal callers are collected instead
            used.add(kind)
            if kind not in kinds and not sf.suppressed(
                    "flight-event", node.lineno):
                out.append(Finding(
                    "flight-event", sf.rel, node.lineno,
                    f"flight event kind {kind!r} is not registered in "
                    f"{recorder_path}:EVENT_KINDS — postmortem "
                    "consumers grep by kind; register it (with the "
                    "DESIGN.md event catalog) before recording it",
                    detail=f"unknown:{kind}"))
    for kind in sorted(kinds - used):
        out.append(Finding(
            "flight-event", recorder_path, 0,
            f"event kind {kind!r} is registered in EVENT_KINDS but "
            "never recorded in the production tree — dead catalog "
            "entry", detail=f"dead:{kind}"))
    return out


# -- env vars ----------------------------------------------------------------
def _env_reads(tree: ast.AST) -> List[Tuple[str, int]]:
    """(name, line) for every DS_* environment read: os.getenv /
    os.environ.get / os.environ[...] / `"DS_X" in os.environ`."""
    reads: List[Tuple[str, int]] = []

    def _is_environ(node: ast.AST) -> bool:
        return (isinstance(node, ast.Attribute) and
                node.attr == "environ") or (
            isinstance(node, ast.Name) and node.id == "environ")

    for node in ast.walk(tree):
        name = None
        if isinstance(node, ast.Call):
            f = node.func
            if (isinstance(f, ast.Attribute) and f.attr == "getenv") \
                    or (isinstance(f, ast.Name) and f.id == "getenv"):
                name = _const_str(node.args[0]) if node.args else None
            elif isinstance(f, ast.Attribute) and f.attr == "get" and \
                    _is_environ(f.value):
                name = _const_str(node.args[0]) if node.args else None
        elif isinstance(node, ast.Subscript) and _is_environ(node.value):
            name = _const_str(node.slice)
        elif isinstance(node, ast.Compare) and len(node.ops) == 1 and \
                isinstance(node.ops[0], (ast.In, ast.NotIn)) and \
                _is_environ(node.comparators[0]):
            name = _const_str(node.left)
        if name and name.startswith("DS_"):
            reads.append((name, node.lineno))
    return reads


def _const_str(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def check_env_docs(project: Project,
                   doc_paths: Tuple[str, ...] = DOC_PATHS
                   ) -> List[Finding]:
    docs = "\n".join(project.doc(p) for p in doc_paths)
    #: word-boundary set of documented names: a raw substring test
    #: would let DS_WORKLOAD ride on DS_WORKLOAD_TRACE's documentation
    documented = set(re.findall(r"\bDS_[A-Z0-9_]+\b", docs))
    out: List[Finding] = []
    seen: Set[str] = set()
    for sf in project.files():
        for name, line in _env_reads(sf.tree):
            if name in seen:
                continue
            if name in documented:
                seen.add(name)
                continue
            if sf.suppressed("env-doc", line):
                seen.add(name)
                continue
            seen.add(name)
            out.append(Finding(
                "env-doc", sf.rel, line,
                f"environment variable {name} is read here but "
                f"documented in neither of {doc_paths} — an "
                "undocumented env knob is an unsupported one",
                detail=name))
    return out


# -- the absorbed metric lint ------------------------------------------------
def check_metric_catalog(project: Project) -> List[Finding]:
    from . import metrics_catalog
    try:
        errors = metrics_catalog.check(repo_root=project.root)
    except Exception as e:     # import failure IS a catalog failure
        return [Finding("metric-catalog",
                        "deepspeed_tpu/telemetry/metrics.py", 0,
                        f"metric catalog check failed to run: "
                        f"{type(e).__name__}: {e}",
                        detail=f"error:{type(e).__name__}")]
    return [Finding("metric-catalog",
                    "deepspeed_tpu/telemetry/metrics.py", 0, err,
                    detail=err.split(":")[0])
            for err in errors]


def run(project: Project) -> List[Finding]:
    out: List[Finding] = []
    out.extend(check_chaos_sites(project))
    out.extend(check_flight_events(project))
    out.extend(check_env_docs(project))
    out.extend(check_metric_catalog(project))
    return out
