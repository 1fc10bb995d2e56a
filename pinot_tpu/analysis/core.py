"""Analysis framework core: indexer, findings, suppressions, baseline.

Design notes:

  * One :class:`ModuleIndex` is built per run and shared by every
    checker — each source file is read and ``ast.parse``d exactly once
    (the whole tree is ~170 files; a full six-checker run stays well
    under a second, cheap enough for tier-1).
  * A :class:`Finding` carries BOTH a line number (for humans/editors)
    and a line-number-independent ``key`` (for the baseline): keys are
    built from stable names — class, attribute, function, site, knob —
    so an unrelated edit above a finding does not churn the baseline.
  * Suppression is two-layer: inline ``# lint: <code>(<reason>)``
    comments for violations that are correct-by-argument at the site,
    and the committed baseline for pre-existing accepted findings.
    Both REQUIRE a reason; a bare code suppresses nothing.
"""
from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Tuple


def repo_root() -> str:
    """The checkout root: parent of the installed ``pinot_tpu`` package."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def default_baseline_path() -> str:
    return os.path.join(repo_root(), "ANALYSIS_BASELINE.json")


#: ``# lint: code(reason)`` — reason is REQUIRED (an unexplained
#: suppression is just a hidden bug); multiple suppressions may share a
#: line: ``# lint: unlocked(ctor only) hang(bounded by caller)``
_SUPPRESS_RE = re.compile(r"#\s*lint:\s*(.+)$")
_SUPPRESS_ITEM_RE = re.compile(r"([a-z]+)\(([^)]+)\)")


@dataclass
class SourceFile:
    """One parsed module: source text, AST, and its suppression map."""

    path: str              # absolute
    relpath: str           # relative to the repo root, '/'-separated
    source: str
    tree: ast.AST
    #: line number -> {code: reason} (codes suppressed on that line)
    suppressions: Dict[int, Dict[str, str]] = field(default_factory=dict)

    @property
    def lines(self) -> List[str]:
        return self.source.splitlines()

    def suppressed(self, line: int, code: str) -> Optional[str]:
        """Reason if ``code`` is suppressed at ``line`` (the flagged
        line itself, or a standalone suppression comment directly
        above), else None."""
        for ln in (line, line - 1):
            reason = self.suppressions.get(ln, {}).get(code)
            if reason:
                return reason
        return None


def _parse_suppressions(source: str) -> Dict[int, Dict[str, str]]:
    out: Dict[int, Dict[str, str]] = {}
    for i, line in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(line)
        if not m:
            continue
        items = {code: reason.strip()
                 for code, reason in _SUPPRESS_ITEM_RE.findall(m.group(1))
                 if reason.strip()}
        if items:
            out[i] = items
    return out


class ModuleIndex:
    """Parsed view of the tree under ``root`` (the repo checkout).

    Indexes ``pinot_tpu/`` (production), ``tests/`` (the failpoint
    checker proves every site is armed by a test), and the top-level
    ``bench.py`` driver (it reads config knobs too). Files that fail
    to parse surface as findings from :meth:`parse_errors` rather than
    crashing the run — a syntax error must fail the gate, not the tool.
    """

    SUBDIRS = ("pinot_tpu", "tests")
    TOP_GLOBS = ("bench.py",)

    def __init__(self, root: Optional[str] = None,
                 files: Optional[Iterable[str]] = None):
        self.root = os.path.abspath(root or repo_root())
        self._files: Dict[str, SourceFile] = {}
        self._errors: List[Tuple[str, str]] = []
        paths: List[str] = []
        if files is not None:
            paths = [os.path.join(self.root, f) if not os.path.isabs(f)
                     else f for f in files]
        else:
            for sub in self.SUBDIRS:
                base = os.path.join(self.root, sub)
                for dirpath, dirs, names in os.walk(base):
                    dirs[:] = [d for d in dirs if d != "__pycache__"]
                    paths.extend(os.path.join(dirpath, n)
                                 for n in sorted(names)
                                 if n.endswith(".py"))
            for g in self.TOP_GLOBS:
                p = os.path.join(self.root, g)
                if os.path.exists(p):
                    paths.append(p)
        for p in paths:
            rel = os.path.relpath(p, self.root).replace(os.sep, "/")
            try:
                with open(p, encoding="utf-8") as f:
                    src = f.read()
                tree = ast.parse(src, filename=p)
            except (OSError, SyntaxError, ValueError) as e:
                self._errors.append((rel, f"{type(e).__name__}: {e}"))
                continue
            self._files[rel] = SourceFile(
                path=p, relpath=rel, source=src, tree=tree,
                suppressions=_parse_suppressions(src))

    def files(self, prefix: str = "") -> List[SourceFile]:
        return [sf for rel, sf in sorted(self._files.items())
                if rel.startswith(prefix)]

    def get(self, relpath: str) -> Optional[SourceFile]:
        return self._files.get(relpath)

    def parse_errors(self) -> List["Finding"]:
        return [Finding(checker="parse", code="parse", file=rel, line=0,
                        key=rel, message=msg)
                for rel, msg in self._errors]


@dataclass
class Finding:
    checker: str    # registry name, e.g. 'locks'
    code: str       # suppression code, e.g. 'unlocked'
    file: str       # repo-relative path
    line: int
    key: str        # stable, line-independent baseline fingerprint
    message: str
    #: set by run_analysis when the finding is accepted somewhere
    suppressed_by: Optional[str] = None   # 'inline' | 'baseline'
    reason: Optional[str] = None

    def ident(self) -> Tuple[str, str, str]:
        return (self.checker, self.file, self.key)

    def render(self) -> str:
        return (f"{self.file}:{self.line}: [{self.checker}/{self.code}] "
                f"{self.message}  (key: {self.key})")


class Checker:
    """Base class; subclasses register via :func:`register`."""

    name = "base"
    code = "base"

    def run(self, index: ModuleIndex) -> List[Finding]:  # pragma: no cover
        raise NotImplementedError

    # -- helpers shared by checkers -----------------------------------
    def finding(self, sf: SourceFile, node_or_line, key: str,
                message: str) -> Finding:
        line = (node_or_line if isinstance(node_or_line, int)
                else getattr(node_or_line, "lineno", 0))
        return Finding(checker=self.name, code=self.code, file=sf.relpath,
                       line=line, key=key, message=message)


#: name -> checker instance, populated by @register at import time
CHECKERS: Dict[str, Checker] = {}


def register(cls):
    CHECKERS[cls.name] = cls()
    return cls


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def load_baseline(path: str) -> Dict[Tuple[str, str, str], str]:
    """{(checker, file, key): reason}. Entries without a non-empty
    reason are IGNORED (and therefore fail the gate) — the baseline is
    the written-justification ledger, not a mute button."""
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    out: Dict[Tuple[str, str, str], str] = {}
    for e in data.get("findings", []):
        reason = str(e.get("reason", "")).strip()
        if not reason:
            continue
        out[(e["checker"], e["file"], e["key"])] = reason
    return out


def write_baseline(path: str, findings: List[Finding],
                   reason: str = "TODO: justify or fix") -> None:
    """Emit a baseline skeleton for the given findings. Meant for
    bootstrapping — every TODO reason must be replaced by hand before
    the entry counts (load_baseline drops empty reasons only, but code
    review owns the TODOs)."""
    entries = [{"checker": f.checker, "file": f.file, "key": f.key,
                "line": f.line, "message": f.message, "reason": reason}
               for f in sorted(findings,
                               key=lambda f: (f.checker, f.file, f.key))]
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": 1, "findings": entries}, f, indent=1)
        f.write("\n")


@dataclass
class AnalysisReport:
    findings: List[Finding]                 # every raw finding
    unsuppressed: List[Finding]
    inline_suppressed: List[Finding]
    baselined: List[Finding]
    #: baseline entries that matched no current finding — stale entries
    #: are surfaced (fix landed? key drifted?) but do not fail the gate
    stale_baseline: List[Tuple[str, str, str]]

    def to_json(self) -> dict:
        def fd(f: Finding) -> dict:
            d = {"checker": f.checker, "code": f.code, "file": f.file,
                 "line": f.line, "key": f.key, "message": f.message}
            if f.suppressed_by:
                d["suppressed_by"] = f.suppressed_by
                d["reason"] = f.reason
            return d
        return {
            "unsuppressed": [fd(f) for f in self.unsuppressed],
            "inline_suppressed": [fd(f) for f in self.inline_suppressed],
            "baselined": [fd(f) for f in self.baselined],
            "stale_baseline": [list(k) for k in self.stale_baseline],
            "counts": {
                "unsuppressed": len(self.unsuppressed),
                "inline_suppressed": len(self.inline_suppressed),
                "baselined": len(self.baselined),
                "stale_baseline": len(self.stale_baseline),
            },
        }


def run_analysis(index: Optional[ModuleIndex] = None,
                 checkers: Optional[Iterable[str]] = None,
                 baseline: Optional[Dict[Tuple[str, str, str], str]] = None,
                 ) -> AnalysisReport:
    """Run the selected checkers and classify every finding."""
    index = index or ModuleIndex()
    baseline = baseline or {}
    names = list(checkers) if checkers else sorted(CHECKERS)
    findings: List[Finding] = list(index.parse_errors())
    for name in names:
        findings.extend(CHECKERS[name].run(index))

    unsuppressed: List[Finding] = []
    inline_sup: List[Finding] = []
    baselined: List[Finding] = []
    matched_keys = set()
    for f in findings:
        sf = index.get(f.file)
        reason = sf.suppressed(f.line, f.code) if sf is not None else None
        if reason is not None:
            f.suppressed_by, f.reason = "inline", reason
            inline_sup.append(f)
            continue
        breason = baseline.get(f.ident())
        if breason is not None:
            f.suppressed_by, f.reason = "baseline", breason
            matched_keys.add(f.ident())
            baselined.append(f)
            continue
        unsuppressed.append(f)
    stale = sorted(set(baseline) - matched_keys)
    return AnalysisReport(findings=findings, unsuppressed=unsuppressed,
                          inline_suppressed=inline_sup,
                          baselined=baselined, stale_baseline=stale)


# ---------------------------------------------------------------------------
# small AST helpers shared by checkers
# ---------------------------------------------------------------------------

def call_name(node: ast.Call) -> str:
    """Dotted name of a call target: ``jax.jit`` for jax.jit(...),
    ``fire`` for fire(...); '' when the target is not a name chain."""
    return dotted(node.func)


def dotted(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def kwarg_names(node: ast.Call) -> List[str]:
    return [k.arg for k in node.keywords if k.arg is not None]


def walk_functions(tree: ast.AST):
    """Yield every FunctionDef/AsyncFunctionDef in the tree."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node
