"""dslint core: the repo-native static contract checker's framework
(ISSUE 15).

The serving stack's load-bearing invariants — one compiled program +
token-sized d2h, mirrored config blocks, RLock-only telemetry, <5µs
disabled paths, closed metric/chaos/event/env catalogs — were enforced
by prose and review until now.  dslint turns each written contract into
an AST pass over the production tree so a contract break fails CI
instead of shipping.

Vocabulary (parsed from ``# dslint:`` comments, found via
:mod:`tokenize` so string literals can't false-trigger):

- ``# dslint: disable=<rule>[,<rule>...] -- <reason>`` — suppress the
  named rules on this line; placed on a compound statement's header
  line (``with``/``for``/``if``/``def``) it covers the whole block.
  The reason string is REQUIRED: a bare disable is itself a finding
  (rule ``bare-suppression``), as is disabling an unknown rule.
- ``# dslint: hot-path`` — marks a serving hot-path function (on the
  ``def`` line or the line above): the hot-path pass lints its body
  for host syncs.
- ``# dslint: disabled-path`` — marks a function documented "<5µs
  disabled": the disabled-path pass checks its guard shape.
- ``# dslint: d2h <shape>`` — declares an intentional device→host
  transfer on this line (e.g. ``[S] int32``); the hot-path pass allows
  it only when ``<shape>`` appears in docs/DESIGN.md's transfer
  contract.

Baseline file (``tools/dslint/baseline.json``): grandfathered findings
carried as ``{"rule", "path", "detail", "reason"}`` records (matched on
the first three; ``reason`` is required — the baseline is a debt
ledger, not a mute button).  ``--strict`` also fails on stale entries
so the ledger can only shrink.  Empty at merge.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import json
import os
import re
import tokenize
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the production tree dslint walks (tests are deliberately excluded —
#: contracts bind shipped code; tools/dslint itself is excluded so the
#: linter's own pattern tables stay out of its jurisdiction)
SCAN_ROOTS = ("deepspeed_tpu", "tools")
EXCLUDE_DIRS = ("__pycache__", os.path.join("tools", "dslint"))

#: every rule id a ``disable=`` may name (passes register theirs at
#: import; the two framework rules are always present)
RULE_IDS: Set[str] = {"bare-suppression", "parse-error"}

DEFAULT_BASELINE = os.path.join("tools", "dslint", "baseline.json")

_TAG_RE = re.compile(r"dslint:\s*(?P<body>.+?)\s*$")
_DISABLE_RE = re.compile(
    r"^disable=(?P<rules>[a-z0-9-]+(?:\s*,\s*[a-z0-9-]+)*)"
    r"(?:\s+--\s+(?P<reason>.+?))?$")


def register_rules(*ids: str) -> None:
    """Pass modules declare their rule ids so suppressions validate."""
    RULE_IDS.update(ids)


def root_name(node: ast.AST) -> Optional[str]:
    """The base Name of a dotted call/attr/subscript chain
    (``jnp.sum(x)[0]`` -> ``jnp``), or None — shared by the hot-path
    and lock passes so their idea of a call's root can't drift."""
    while isinstance(node, (ast.Attribute, ast.Subscript, ast.Call)):
        node = node.func if isinstance(node, ast.Call) else node.value
    return node.id if isinstance(node, ast.Name) else None


@dataclasses.dataclass(frozen=True)
class Finding:
    """One contract violation.  ``detail`` is the line-number-free
    component of the baseline key, so a finding keeps matching its
    baseline entry across unrelated edits to the same file."""
    rule: str
    path: str           # repo-relative, forward slashes
    line: int           # 1-based; 0 = file- or project-scope
    message: str
    detail: str = ""

    @property
    def key(self) -> Tuple[str, str, str]:
        return (self.rule, self.path, self.detail or self.message)

    def format(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        return f"{loc}: [{self.rule}] {self.message}"


@dataclasses.dataclass
class _Suppression:
    line: int           # the comment's line
    end: int            # last line it covers (inclusive)
    rules: Set[str]
    reason: Optional[str]


@dataclasses.dataclass
class _Annotation:
    line: int
    kind: str           # "hot-path" | "disabled-path" | "d2h"
    arg: str            # d2h shape text, "" otherwise
    end: int = 0        # statement coverage for d2h (inclusive)


class SourceFile:
    """One parsed production file: AST + raw lines + dslint comments."""

    def __init__(self, rel: str, text: str):
        self.rel = rel.replace(os.sep, "/")
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text)     # caller handles SyntaxError
        self.suppressions: List[_Suppression] = []
        self.annotations: List[_Annotation] = []
        self.comment_findings: List[Finding] = []
        self._stmt_span: Dict[int, int] = {}   # lineno -> end_lineno
        for node in ast.walk(self.tree):
            if isinstance(node, ast.stmt) and hasattr(node, "end_lineno"):
                # widest statement starting on this line wins
                prev = self._stmt_span.get(node.lineno, 0)
                self._stmt_span[node.lineno] = max(prev, node.end_lineno)
        self._parse_comments()

    # -- comment vocabulary --------------------------------------------------
    def _parse_comments(self) -> None:
        try:
            toks = tokenize.generate_tokens(io.StringIO(self.text).readline)
            comments = [(t.start[0], t.string) for t in toks
                        if t.type == tokenize.COMMENT]
        except tokenize.TokenError:
            comments = []
        for line, comment in comments:
            m = _TAG_RE.search(comment)
            if not m:
                continue
            body = m.group("body")
            if body.startswith("disable="):
                dm = _DISABLE_RE.match(body)
                if not dm:
                    self.comment_findings.append(Finding(
                        "bare-suppression", self.rel, line,
                        f"malformed dslint disable comment: {body!r} "
                        "(want: disable=<rule>[,<rule>] -- <reason>)",
                        detail=body))
                    continue
                rules = {r.strip() for r in dm.group("rules").split(",")}
                reason = dm.group("reason")
                unknown = rules - RULE_IDS
                if unknown:
                    self.comment_findings.append(Finding(
                        "bare-suppression", self.rel, line,
                        f"dslint disable names unknown rule(s) "
                        f"{sorted(unknown)} (known: {sorted(RULE_IDS)})",
                        detail=f"unknown:{','.join(sorted(unknown))}"))
                if not reason or not reason.strip():
                    self.comment_findings.append(Finding(
                        "bare-suppression", self.rel, line,
                        "dslint disable without a reason — suppressions "
                        "must say why ('disable=<rule> -- <reason>')",
                        detail=f"bare:{','.join(sorted(rules))}"))
                    continue    # a bare disable does not suppress
                self.suppressions.append(_Suppression(
                    line, self._coverage_end(line), rules & RULE_IDS,
                    reason.strip()))
            elif body == "hot-path" or body == "disabled-path":
                self.annotations.append(_Annotation(line, body, ""))
            elif body.startswith("d2h"):
                shape = body[len("d2h"):].strip()
                self.annotations.append(_Annotation(
                    line, "d2h", shape, end=self._coverage_end(line)))
            # unknown tags are ignored: forward compatibility with
            # newer vocab in older checkouts

    def _coverage_end(self, line: int) -> int:
        """A tag on a statement's first line covers the statement's
        whole span (so one disable on a ``with``/``for`` header covers
        the block); on a comment-only line it skips any further
        comment lines and covers the NEXT statement's span."""
        end = self._stmt_span.get(line)
        if end:
            return end
        stripped = (self.lines[line - 1].lstrip()
                    if line - 1 < len(self.lines) else "")
        if not stripped.startswith("#"):
            return line
        nxt = line + 1
        while nxt - 1 < len(self.lines) and (
                not self.lines[nxt - 1].strip()
                or self.lines[nxt - 1].lstrip().startswith("#")):
            nxt += 1
        return self._stmt_span.get(nxt, nxt)

    # -- queries -------------------------------------------------------------
    def suppressed(self, rule: str, line: int) -> bool:
        return any(rule in s.rules and s.line <= line <= s.end
                   for s in self.suppressions)

    def func_annotated(self, func: ast.AST, kind: str) -> bool:
        """Whether a FunctionDef carries ``# dslint: <kind>`` on its
        ``def`` line, the line above it, or the line above its first
        decorator."""
        candidates = {func.lineno, func.lineno - 1}
        if getattr(func, "decorator_list", None):
            candidates.add(func.decorator_list[0].lineno - 1)
        return any(a.kind == kind and a.line in candidates
                   for a in self.annotations)

    def d2h_annotation(self, line: int) -> Optional[str]:
        """The declared d2h shape covering ``line``, or None."""
        for a in self.annotations:
            if a.kind == "d2h" and a.line <= line <= (a.end or a.line):
                return a.arg
        return None

    def functions(self) -> Iterable[ast.AST]:
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield node


class Project:
    """The scanned production tree plus doc files, shared by all
    passes.  ``root`` defaults to the repo; tests point it at fixture
    trees (every pass must work on an arbitrary root)."""

    def __init__(self, root: str = REPO_ROOT,
                 scan_roots: Sequence[str] = SCAN_ROOTS):
        self.root = root
        self.scan_roots = tuple(scan_roots)
        self._files: Dict[str, SourceFile] = {}
        self._docs: Dict[str, str] = {}
        self.parse_findings: List[Finding] = []
        self._load()

    def _load(self) -> None:
        paths: List[str] = []
        for sr in self.scan_roots:
            full = os.path.join(self.root, sr)
            if os.path.isfile(full):
                paths.append(sr)
                continue
            for dirpath, dirs, files in os.walk(full):
                rel_dir = os.path.relpath(dirpath, self.root)
                if any(x in rel_dir for x in EXCLUDE_DIRS):
                    continue
                for name in sorted(files):
                    if name.endswith(".py"):
                        paths.append(os.path.normpath(
                            os.path.join(rel_dir, name)))
        for rel in sorted(set(paths)):
            try:
                with open(os.path.join(self.root, rel),
                          encoding="utf-8") as f:
                    text = f.read()
            except OSError:
                continue
            try:
                self._files[rel.replace(os.sep, "/")] = SourceFile(rel,
                                                                   text)
            except SyntaxError as e:
                self.parse_findings.append(Finding(
                    "parse-error", rel.replace(os.sep, "/"),
                    getattr(e, "lineno", 0) or 0,
                    f"cannot parse: {e.msg}", detail=str(e.msg)))

    def files(self) -> List[SourceFile]:
        return list(self._files.values())

    def file(self, rel: str) -> Optional[SourceFile]:
        return self._files.get(rel)

    def doc(self, rel: str) -> str:
        """A doc file's text ("" when absent), cached."""
        if rel not in self._docs:
            try:
                with open(os.path.join(self.root, rel),
                          encoding="utf-8") as f:
                    self._docs[rel] = f.read()
            except OSError:
                self._docs[rel] = ""
        return self._docs[rel]


# -- baseline ----------------------------------------------------------------
def load_baseline(path: str) -> Tuple[List[dict], List[str]]:
    """Parse the baseline file -> (entries, format errors)."""
    if not os.path.exists(path):
        return [], []
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [], [f"{path}: unreadable baseline: {e}"]
    errors = []
    entries = doc.get("findings", []) if isinstance(doc, dict) else []
    if not isinstance(entries, list):
        return [], [f"{path}: 'findings' must be a list"]
    for i, e in enumerate(entries):
        if not isinstance(e, dict) or not all(
                isinstance(e.get(k), str) and e.get(k)
                for k in ("rule", "path", "detail", "reason")):
            errors.append(
                f"{path}: findings[{i}] must carry non-empty string "
                "rule/path/detail/reason fields")
    return entries, errors


def apply_baseline(findings: List[Finding], entries: List[dict]
                   ) -> Tuple[List[Finding], List[Finding], List[dict]]:
    """-> (new findings, baselined findings, stale entries)."""
    index = {(e.get("rule"), e.get("path"), e.get("detail")): e
             for e in entries}
    new, old, hit = [], [], set()
    for f in findings:
        e = index.get(f.key)
        if e is None:
            new.append(f)
        else:
            old.append(f)
            hit.add(f.key)
    stale = [e for k, e in index.items() if k not in hit]
    return new, old, stale


@dataclasses.dataclass
class Report:
    findings: List[Finding]             # unsuppressed, not baselined
    baselined: List[Finding]
    stale_baseline: List[dict]
    baseline_errors: List[str]

    @property
    def clean(self) -> bool:
        return not self.findings and not self.baseline_errors
