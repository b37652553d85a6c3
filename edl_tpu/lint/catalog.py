"""Catalog drift: env knobs and metric names vs. the doc catalogs.

The operator docs (doc/usage.md, doc/observability.md,
doc/robustness.md, ...) carry knob and metric catalogs that earlier
PRs kept in sync *by review* — and review missed entries both ways.
These two checks make the sync mechanical:

**knob-drift** — every ``EDL_TPU_*`` name that appears in code (string
constants, excluding docstrings: the set of names the process can
actually read) must appear in at least one doc file, and every name a
doc file teaches must still exist somewhere in the repo's code (tests/
examples/scripts/k8s count — a knob may be exercised only there).
Docs may use a trailing ``*`` wildcard (``EDL_TPU_DEMO_*``) to cover
a family.

**metric-drift** — every metric name registered through
``obs_metrics.counter/gauge/histogram`` must appear in
doc/observability.md, and every ``edl_*`` token that page uses must
resolve to a registered metric (modulo the Prometheus-derived
``_bucket``/``_count``/``_sum`` suffixes of histograms).
"""

from __future__ import annotations

import ast
import re

from edl_tpu.lint.engine import Finding, Project, check, dotted

_KNOB_RE = re.compile(r"EDL_TPU_[A-Z0-9][A-Z0-9_]*")
_KNOB_WILD_RE = re.compile(r"EDL_TPU_[A-Z0-9_]+\*")
_METRIC_RE = re.compile(r"\bedl_[a-z0-9_]+")
_METRIC_DOC = "doc/observability.md"
_DERIVED_SUFFIXES = ("_bucket", "_count", "_sum")

# repo-wide existence scan for the stale-doc direction (a knob may be
# exercised only by tests, smokes, or deployment manifests)
_EXISTENCE_GLOBS = ("edl_tpu/**/*.py", "tests/**/*.py", "scripts/**/*.py",
                    "examples/**/*.py", "k8s/*.yaml", "docker/*")


def _docstring_nodes(tree: ast.AST) -> set[int]:
    """ids of Constant nodes that are docstrings (skipped: a docstring
    explaining a knob is commentary, not a read site)."""
    out: set[int] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = node.body
            if body and isinstance(body[0], ast.Expr) and \
                    isinstance(body[0].value, ast.Constant) and \
                    isinstance(body[0].value.value, str):
                out.add(id(body[0].value))
    return out


def _code_knobs(project: Project) -> dict[str, tuple[str, int]]:
    """knob -> (path, line) of first non-docstring string-constant use."""
    knobs: dict[str, tuple[str, int]] = {}
    for src in project.sources:
        skip = _docstring_nodes(src.tree)
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)):
                continue
            if id(node) in skip:
                continue
            for m in _KNOB_RE.finditer(node.value):
                if node.value[m.end():m.end() + 1] == "*":
                    continue  # a `EDL_TPU_FOO_*` family reference
                knob = m.group(0).rstrip("_")
                knobs.setdefault(knob, (src.rel, node.lineno))
    return knobs


def _doc_knobs(project: Project) -> tuple[dict[str, tuple[str, int]],
                                          set[str]]:
    """(knob -> (docfile, line) first mention, wildcard prefixes)."""
    knobs: dict[str, tuple[str, int]] = {}
    wild: set[str] = set()
    for rel, text in project.doc_texts().items():
        for i, line in enumerate(text.splitlines(), 1):
            for m in _KNOB_WILD_RE.finditer(line):
                wild.add(m.group(0)[:-1])  # keep the trailing _ — precision
            for m in _KNOB_RE.finditer(line):
                if line[m.end():m.end() + 1] == "*":
                    continue  # wildcard family entry, collected above
                knobs.setdefault(m.group(0).rstrip("_"), (rel, i))
    return knobs, wild


def _repo_code_text(project: Project) -> str:
    parts: list[str] = []
    for pattern in _EXISTENCE_GLOBS:
        for p in sorted(project.root.glob(pattern)):
            if p.is_file():
                try:
                    parts.append(p.read_text(encoding="utf-8"))
                except (UnicodeDecodeError, OSError):
                    continue
    return "\n".join(parts)


@check("knob-drift",
       "EDL_TPU_* env knobs read in code but undocumented, or "
       "documented but gone from code")
def knob_drift(project: Project) -> list[Finding]:
    code = _code_knobs(project)
    documented, wild = _doc_knobs(project)
    findings: list[Finding] = []
    for knob, (path, line) in sorted(code.items()):
        covered = knob in documented or \
            any(knob.startswith(prefix) for prefix in wild)
        if not covered:
            findings.append(Finding(
                check="knob-drift", path=path, line=line,
                message=f"`{knob}` read in code but absent from every doc "
                        "catalog (doc/usage.md / doc/observability.md / "
                        "doc/robustness.md / ...)"))
    existing = set(_KNOB_RE.findall(_repo_code_text(project)))
    existing = {k.rstrip("_") for k in existing}
    for knob, (docfile, line) in sorted(documented.items()):
        if knob not in existing:
            findings.append(Finding(
                check="knob-drift", path=docfile, line=line,
                message=f"`{knob}` documented but no longer exists "
                        "anywhere in code — delete or update the entry"))
    return findings


# -- metric-drift ------------------------------------------------------------
_REGISTRARS = {"counter", "gauge", "histogram"}


def _registered_metrics(project: Project) -> dict[str, tuple[str, int]]:
    out: dict[str, tuple[str, int]] = {}
    for src in project.sources:
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted(node.func) or ""
            if name.rsplit(".", 1)[-1] not in _REGISTRARS:
                continue
            if not node.args:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str) \
                    and _METRIC_RE.fullmatch(arg.value):
                out.setdefault(arg.value, (src.rel, node.lineno))
    return out


@check("metric-drift",
       "registered edl_* metrics missing from doc/observability.md, or "
       "doc'd metric names no longer registered")
def metric_drift(project: Project) -> list[Finding]:
    registered = _registered_metrics(project)
    doc_path = project.root / _METRIC_DOC
    if not doc_path.is_file():
        return [Finding(check="metric-drift", path=_METRIC_DOC, line=1,
                        message="doc/observability.md missing — the metric "
                                "catalog has nowhere to live")]
    text = doc_path.read_text(encoding="utf-8")
    doc_tokens: dict[str, int] = {}
    for i, line in enumerate(text.splitlines(), 1):
        for m in _METRIC_RE.finditer(line):
            if m.group(0) == "edl_tpu":
                continue  # the package name, not a metric
            doc_tokens.setdefault(m.group(0), i)
    findings: list[Finding] = []
    for name, (path, line) in sorted(registered.items()):
        documented = name in doc_tokens or any(
            name + sfx in doc_tokens for sfx in _DERIVED_SUFFIXES)
        if not documented:
            findings.append(Finding(
                check="metric-drift", path=path, line=line,
                message=f"metric `{name}` registered in code but absent "
                        f"from {_METRIC_DOC}'s catalog"))
    for tok, line in sorted(doc_tokens.items()):
        if tok in registered:
            continue
        base = next((tok[:-len(sfx)] for sfx in _DERIVED_SUFFIXES
                     if tok.endswith(sfx) and tok[:-len(sfx)] in registered),
                    None)
        if base is not None:
            continue
        # a *prefix family* mention (``edl_gateway_``-style prose) is
        # fine when at least one registered metric carries the prefix
        if any(r.startswith(tok) for r in registered):
            continue
        findings.append(Finding(
            check="metric-drift", path=_METRIC_DOC, line=line,
            message=f"metric `{tok}` documented but not registered "
                    "anywhere in code — delete or update the entry"))
    return findings
