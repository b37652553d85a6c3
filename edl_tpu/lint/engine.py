"""Analysis engine: source loading, check registry, orchestration.

A :class:`Project` parses every Python file in scope once (AST + parent
links + raw lines) and hands the set to each registered check.  Checks
are plain functions ``fn(project) -> list[Finding]`` registered with the
:func:`check` decorator; they live in sibling modules (``locks``,
``wire``, ``clock``, ``catalog``) and are imported lazily so the CLI
can list/select them without import-order games.

Findings are **line-free keyed**: the baseline identity of a finding is
``(check, path, context, message)`` plus an occurrence index (see
``baseline.py``), so unrelated edits that shift line numbers do not
invalidate waivers.  Messages must therefore never embed line numbers.

Inline waivers: a finding whose source line (or enclosing statement
line) carries ``# edl-lint: disable=<check-id>[,<check-id>...]`` is
dropped before baseline comparison — for findings that are *forever
intentional* and carry their justification as a comment right there.
Everything else goes through the committed baseline ratchet.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Callable, Iterable

# directories scanned for code-level checks, relative to the repo root
PACKAGE_DIRS = ("edl_tpu",)
# documentation set for the catalog cross-checks
DOC_FILES = ("README.md", "doc/usage.md", "doc/observability.md",
             "doc/robustness.md", "doc/memstate.md", "doc/serving.md",
             "doc/design.md", "doc/lint.md", "doc/distill.md")

_DISABLE_RE = re.compile(r"edl-lint:\s*disable=([a-z0-9_,\-]+|all)")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One defect occurrence.  ``context`` is the enclosing
    ``Class.method`` (or ``<module>``) — part of the stable identity."""

    check: str
    path: str          # repo-relative posix path
    line: int          # 1-based; display only, NOT identity
    message: str       # must not contain line numbers
    context: str = "<module>"

    def render(self) -> str:
        return f"{self.path}:{self.line} · {self.check} · {self.message}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


class Source:
    """One parsed Python file: tree + parent links + raw lines."""

    def __init__(self, abspath: Path, root: Path):
        self.abspath = abspath
        self.rel = abspath.relative_to(root).as_posix()
        self.text = abspath.read_text(encoding="utf-8")
        self.lines = self.text.splitlines()
        self.tree = ast.parse(self.text, filename=str(abspath))
        self.parents: dict[ast.AST, ast.AST] = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node

    def context_of(self, node: ast.AST) -> str:
        """``Class.method`` / ``func`` / ``<module>`` for a node."""
        names: list[str] = []
        cur: ast.AST | None = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                names.append(cur.name)
            cur = self.parents.get(cur)
        return ".".join(reversed(names)) or "<module>"

    def enclosing(self, node: ast.AST, kinds) -> ast.AST | None:
        cur = self.parents.get(node)
        while cur is not None and not isinstance(cur, kinds):
            cur = self.parents.get(cur)
        return cur

    def disabled(self, line: int, check: str) -> bool:
        """True when the 1-based ``line`` carries an inline waiver for
        ``check`` (or ``all``) — trailing on the line itself, or on an
        immediately-preceding pure-comment line (for waivers whose
        justification doesn't fit in trailing position)."""
        candidates = [line]
        # walk up through a contiguous pure-comment block above
        i = line - 1
        while i >= 1 and self.lines[i - 1].lstrip().startswith("#"):
            candidates.append(i)
            i -= 1
        for ln in candidates:
            if not 1 <= ln <= len(self.lines):
                continue
            m = _DISABLE_RE.search(self.lines[ln - 1])
            if m is not None:
                ids = m.group(1)
                if ids == "all" or check in ids.split(","):
                    return True
        return False


class Project:
    """Everything the checks need, parsed once."""

    def __init__(self, root: str | Path,
                 package_dirs: Iterable[str] = PACKAGE_DIRS):
        self.root = Path(root).resolve()
        self.sources: list[Source] = []
        self.parse_failures: list[Finding] = []
        paths: list[Path] = []
        for d in package_dirs:
            base = self.root / d
            if base.is_dir():
                paths.extend(sorted(base.rglob("*.py")))
        for p in paths:
            try:
                self.sources.append(Source(p, self.root))
            except (SyntaxError, UnicodeDecodeError) as e:
                self.parse_failures.append(Finding(
                    check="parse", path=p.relative_to(self.root).as_posix(),
                    line=getattr(e, "lineno", None) or 1,
                    message=f"unparseable: {type(e).__name__}"))
        self._by_rel = {s.rel: s for s in self.sources}

    def source(self, rel: str) -> Source | None:
        return self._by_rel.get(rel)

    def doc_texts(self) -> dict[str, str]:
        out: dict[str, str] = {}
        for rel in DOC_FILES:
            p = self.root / rel
            if p.is_file():
                out[rel] = p.read_text(encoding="utf-8")
        return out


# -- registry ----------------------------------------------------------------
CHECKS: dict[str, Callable[[Project], list[Finding]]] = {}
CHECK_DOC: dict[str, str] = {}


def check(check_id: str, doc: str = ""):
    """Register ``fn(project) -> list[Finding]`` under ``check_id``."""

    def deco(fn):
        CHECKS[check_id] = fn
        doc_lines = (doc or (fn.__doc__ or "")).strip().splitlines()
        CHECK_DOC[check_id] = doc_lines[0] if doc_lines else check_id
        return fn

    return deco


# canonical ordering (doc/lint.md's catalog order); registration adds
# any novel check after these
_CANONICAL = ["blocking-under-lock", "lock-order", "wire-error", "clock",
              "thread-hygiene", "knob-drift", "metric-drift"]


def _load_checks() -> None:
    # imported for their registration side effect
    from edl_tpu.lint import catalog, clock, locks, wire  # noqa: F401


def check_ids() -> list[str]:
    _load_checks()
    known = [c for c in _CANONICAL if c in CHECKS]
    return known + sorted(set(CHECKS) - set(known))


def run(root: str | Path, checks: Iterable[str] | None = None,
        project: Project | None = None) -> list[Finding]:
    """Run the selected checks (default: all) and return findings with
    inline-disabled ones filtered, sorted by (path, line, check)."""
    _load_checks()
    project = project or Project(root)
    selected = list(checks) if checks else check_ids()
    unknown = [c for c in selected if c not in CHECKS]
    if unknown:
        raise ValueError(f"unknown checks: {unknown} (have {list(CHECKS)})")
    findings: list[Finding] = list(project.parse_failures)
    for cid in selected:
        findings.extend(CHECKS[cid](project))
    kept = []
    for f in findings:
        src = project.source(f.path)
        if src is not None and src.disabled(f.line, f.check):
            continue
        kept.append(f)
    kept.sort(key=lambda f: (f.path, f.line, f.check, f.message))
    return kept


# -- shared AST helpers ------------------------------------------------------
def dotted(node: ast.AST) -> str | None:
    """``a.b.c`` for Name/Attribute chains (``self.x.y`` included);
    None for anything dynamic (subscripts, call results)."""
    parts: list[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
    elif isinstance(cur, ast.Call) or parts == []:
        return None
    else:
        return None
    return ".".join(reversed(parts))


def terminal(name: str) -> str:
    """Last segment of a dotted name."""
    return name.rsplit(".", 1)[-1]


def name_segments(name: str) -> set[str]:
    """Lowercased underscore-split segments of an identifier's last
    dotted part: ``self._adm_lock`` -> {"adm", "lock"}."""
    return {seg for seg in terminal(name).lower().split("_") if seg}
