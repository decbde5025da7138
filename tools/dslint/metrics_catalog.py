"""Metric-namespace catalog (absorbed ``tools/check_metrics.py``,
ISSUE 4 naming/docs lint + ISSUE 9 dead-metric pass; ISSUE 15 moved
the implementation here so it is one dslint rule among many —
``tools/check_metrics.py`` remains as a thin CLI shim over this
module).

Asserts that every metric registered in the telemetry registry

- matches the ``ds_<area>_<name>`` naming convention with a known area
  (counters additionally end in ``_total``),
- is documented in docs/DESIGN.md's "Telemetry" metric table, and
- is actually RECORDED somewhere in the production tree (a
  ``.inc(`` / ``.observe(`` / ``.set(`` / ``.bind(`` on the minted
  object outside ``telemetry/metrics.py``) — a metric minted but never
  fed is a dead series that scrapes as a forever-zero and rots the
  dashboard.

Unlike the pure-AST passes this one imports the live registry (the
catalog is the process's metric namespace, not a source artifact), so
it carries the telemetry import cost — CI pays it once.
"""

from __future__ import annotations

import os
import re
import sys
from typing import Dict, List

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

AREAS = ("serving", "comm", "kv", "train", "fastgen", "chaos",
         "fleet", "slo", "telemetry", "pool", "disagg", "journey",
         "mem", "host")
NAME_RE = re.compile(
    r"^ds_(%s)_[a-z][a-z0-9_]*$" % "|".join(AREAS))

#: where metric objects are minted — excluded from the recording scan
CATALOG = os.path.join("deepspeed_tpu", "telemetry", "metrics.py")
#: the production tree the recording scan walks (tests are deliberately
#: excluded: a metric recorded only by its test is still dead)
SCAN_ROOTS = ("deepspeed_tpu", "tools")
#: a minted identifier counts as recorded when one of these is called
#: on it anywhere in the scanned tree
RECORD_METHODS = ("inc", "observe", "set", "bind")


def _minted_identifiers(repo_root: str,
                        catalog: str = None) -> Dict[str, str]:
    """{metric name: python identifier} parsed from the catalog."""
    path = os.path.join(repo_root, catalog or CATALOG)
    with open(path) as f:
        src = f.read()
    out: Dict[str, str] = {}
    for m in re.finditer(
            r"^(?P<ident>[A-Z][A-Z0-9_]*) = registry\.\w+\(\s*\n?\s*"
            r"\"(?P<name>ds_[a-z0-9_]+)\"", src, re.MULTILINE):
        out[m.group("name")] = m.group("ident")
    return out


def _scan_recordings(repo_root: str, catalog: str = None) -> str:
    """Concatenated source of every production .py file outside the
    catalog (one pass; the per-metric check is a regex over it)."""
    chunks: List[str] = []
    for root in SCAN_ROOTS:
        full = os.path.join(repo_root, root)
        if os.path.isfile(full):
            with open(full) as f:
                chunks.append(f.read())
            continue
        for dirpath, _dirs, files in os.walk(full):
            if "__pycache__" in dirpath:
                continue
            for name in files:
                if not name.endswith(".py"):
                    continue
                path = os.path.join(dirpath, name)
                if path.endswith(catalog or CATALOG):
                    continue
                with open(path) as f:
                    chunks.append(f.read())
    return "\n".join(chunks)


def check(design_path: str = None,
          repo_root: str = REPO_ROOT,
          catalog: str = None) -> List[str]:
    """Return a list of lint errors (empty = clean).  The string
    messages are the stable interface ``tools/check_metrics.py`` and
    tests/test_telemetry.py consume."""
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from deepspeed_tpu.telemetry import Counter, get_registry
    from deepspeed_tpu.telemetry import metrics  # noqa: F401 — mint catalog

    if design_path is None:
        design_path = os.path.join(repo_root, "docs", "DESIGN.md")
    with open(design_path) as f:
        design = f.read()

    errors = []
    registered = get_registry().all_metrics()
    if not registered:
        errors.append("no metrics registered — catalog import broken?")
    idents = _minted_identifiers(repo_root, catalog)
    source = _scan_recordings(repo_root, catalog)
    for name, metric in sorted(registered.items()):
        if not NAME_RE.match(name):
            errors.append(
                f"{name}: does not match ds_<area>_<name> "
                f"(area in {AREAS}, lowercase [a-z0-9_])")
        if isinstance(metric, Counter) and not name.endswith("_total"):
            errors.append(f"{name}: counters must end in _total")
        if f"`{name}`" not in design:
            errors.append(
                f"{name}: not documented in docs/DESIGN.md "
                "(add a row to the Telemetry metric table)")
        if not metric.help:
            errors.append(f"{name}: registered without help text")
        # dead-metric pass (ISSUE 9): minted in the catalog but never
        # fed anywhere in the production tree.  Metrics registered
        # OUTSIDE the catalog (tests minting throwaways) are skipped —
        # the naming/docs lints above already police them.
        ident = idents.get(name)
        if ident is not None and not re.search(
                r"\b%s\s*\.\s*(%s)\s*\(" % (ident,
                                            "|".join(RECORD_METHODS)),
                source):
            errors.append(
                f"{name}: dead metric — minted as {ident} in "
                f"{catalog or CATALOG} but never recorded "
                f"(.{'/.'.join(RECORD_METHODS)}) anywhere in "
                f"{SCAN_ROOTS}")
    return errors
