"""sitpu-lint core: file loading, suppressions, the baseline gate.

The suite is pure stdlib ``ast`` — no jax import, no execution of the
code under analysis — so it runs in a bare CI container in well under a
second. Checkers receive parsed :class:`SourceFile` objects and return
:class:`Diagnostic` records; this module owns everything around them:

- **inline suppressions**: a ``# sitpu-lint: disable=CODE[,CODE...]``
  comment on the diagnostic's reported line (or ``disable=all``)
  silences it at the source — use for true positives the code cannot
  express otherwise, with a justification in the surrounding comment.
- **the baseline** (``tools/lint/baseline.json``): the committed ledger
  of accepted findings, each with a mandatory human ``reason`` string.
  The gate fails only on findings NOT in the baseline, so the suite can
  hold invariants that have principled exceptions (e.g. the plain-image
  builders genuinely have no ``ring_slots`` working set to cap) without
  those exceptions rotting into "the linter is red, ignore it".
  Baseline entries match on ``(code, path, message)`` — never on line
  numbers, which churn — and entries that no longer match anything are
  reported as stale so the baseline shrinks as debts are paid.
"""

from __future__ import annotations

import ast
import io
import json
import os
import re
import tokenize
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

_SUPPRESS_RE = re.compile(r"sitpu-lint:\s*disable=([A-Za-z0-9_,\- ]+)")


@dataclass(frozen=True)
class Diagnostic:
    """One finding: ``path:line  CODE  message`` (path repo-relative)."""

    path: str
    line: int
    code: str
    message: str
    symbol: str = ""          # enclosing function, for humans + baseline

    def render(self) -> str:
        sym = f" [{self.symbol}]" if self.symbol else ""
        return f"{self.path}:{self.line}  {self.code}  {self.message}{sym}"

    def key(self) -> Tuple[str, str, str]:
        """Baseline identity: line numbers churn, messages are stable."""
        return (self.code, self.path, self.message)


class SourceFile:
    """One parsed file: AST + per-line suppression sets."""

    def __init__(self, abspath: str, relpath: str, text: str):
        self.abspath = abspath
        self.path = relpath            # repo-relative, '/' separators
        self.text = text
        self.tree = ast.parse(text, filename=relpath)
        self.suppressions = _parse_suppressions(text)

    def suppressed(self, line: int, code: str) -> bool:
        codes = self.suppressions.get(line)
        return bool(codes) and (code in codes or "all" in codes)


def _parse_suppressions(text: str) -> Dict[int, Set[str]]:
    out: Dict[int, Set[str]] = {}
    try:
        toks = tokenize.generate_tokens(io.StringIO(text).readline)
        for tok in toks:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if m:
                codes = {c.strip() for c in m.group(1).split(",") if c.strip()}
                out.setdefault(tok.start[0], set()).update(codes)
    except tokenize.TokenError:
        pass
    return out


def load_sources(root: str, paths: Iterable[str]) -> List[SourceFile]:
    """Parse ``paths`` (absolute) into SourceFiles. Raises on a syntax
    error; gate-facing callers use :func:`load_sources_with_diags` so a
    half-edited file fails as its own SITPU-PARSE finding (with the
    report artifact still written) instead of a raw traceback."""
    out = []
    for p in sorted(set(paths)):
        rel = os.path.relpath(p, root).replace(os.sep, "/")
        with open(p, "r", encoding="utf-8") as f:
            text = f.read()
        out.append(SourceFile(p, rel, text))
    return out


def load_sources_with_diags(root: str, paths: Iterable[str]
                            ) -> Tuple[List[SourceFile], List[Diagnostic]]:
    """Like :func:`load_sources`, but unparseable files become
    ``SITPU-PARSE`` diagnostics (per file) instead of crashing the run —
    the gate must fail loudly AND still produce its report."""
    out, diags = [], []
    for p in sorted(set(paths)):
        rel = os.path.relpath(p, root).replace(os.sep, "/")
        with open(p, "r", encoding="utf-8") as f:
            text = f.read()
        try:
            out.append(SourceFile(p, rel, text))
        except SyntaxError as e:
            diags.append(Diagnostic(rel, e.lineno or 1, "SITPU-PARSE",
                                    f"file does not parse: {e.msg}"))
    return out, diags


def default_scan_paths(repo_root: str) -> List[str]:
    """The repo surface the invariants cover: the package (minus the
    linter itself — host tooling has no degrade/trace semantics), the
    bench driver and the benchmark harnesses."""
    pkg = os.path.join(repo_root, "scenery_insitu_tpu")
    skip = os.path.join(pkg, "tools") + os.sep
    paths = []
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        if (dirpath + os.sep).startswith(skip):
            continue
        for name in filenames:
            if name.endswith(".py"):
                paths.append(os.path.join(dirpath, name))
    bench = os.path.join(repo_root, "bench.py")
    if os.path.exists(bench):
        paths.append(bench)
    bdir = os.path.join(repo_root, "benchmarks")
    if os.path.isdir(bdir):
        for name in os.listdir(bdir):
            if name.endswith(".py"):
                paths.append(os.path.join(bdir, name))
    return paths


def find_repo_root() -> str:
    here = os.path.dirname(os.path.abspath(__file__))
    # tools/lint -> tools -> scenery_insitu_tpu -> repo
    return os.path.abspath(os.path.join(here, "..", "..", ".."))


# ------------------------------------------------------------------ AST util

def iter_calls(node: ast.AST) -> Iterable[ast.Call]:
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            yield n


def call_name(call: ast.Call) -> str:
    """Rightmost name of the called expression: ``obs.degrade`` ->
    ``degrade``, ``degrade`` -> ``degrade``, ``a.b.c()`` -> ``c``."""
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def dotted_name(expr: ast.AST) -> str:
    """``jax.lax.scan`` -> "jax.lax.scan"; "" when not a pure name chain."""
    parts = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if isinstance(expr, ast.Name):
        parts.append(expr.id)
        return ".".join(reversed(parts))
    return ""


# functions that mint a ledger entry on behalf of their caller, with the
# positional index of the literal component argument (used by both the
# LEDGER checker and the registry round-trip discovery)
DEGRADE_WRAPPERS = {"degrade": 0}


def calls_degrade(node: ast.AST) -> bool:
    """Does ``node`` contain a ledger mint — ``obs.degrade(...)`` /
    ``degrade(...)`` or a degrade-minting wrapper (the fallback-ledger
    contract, obs/recorder.py)?"""
    return any(call_name(c) in DEGRADE_WRAPPERS for c in iter_calls(node))


def enclosing_functions(tree: ast.Module):
    """Yield (outermost_top_level_def, def_node) for every function."""
    for top in tree.body:
        if isinstance(top, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for n in ast.walk(top):
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield top, n


def func_params(fn) -> List[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


# ----------------------------------------------------------------- baseline

class Baseline:
    """Committed suppression ledger. Every entry carries a mandatory
    ``reason`` — a baseline without stated reasons is just a muted
    linter."""

    def __init__(self, entries: Optional[List[dict]] = None):
        self.entries = entries or []
        bad = [e for e in self.entries
               if not str(e.get("reason", "")).strip()]
        if bad:
            raise ValueError(
                f"baseline entries without a reason string: "
                f"{[(e.get('code'), e.get('path')) for e in bad]}")
        self._index = {(e["code"], e["path"], e["message"]): e
                       for e in self.entries}

    @classmethod
    def load(cls, path: str) -> "Baseline":
        if not os.path.exists(path):
            return cls([])
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
        return cls(doc.get("entries", []))

    def save(self, path: str) -> None:
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"version": 1, "entries": self.entries}, f, indent=2,
                      sort_keys=False)
            f.write("\n")

    def split(self, diags: Sequence[Diagnostic]):
        """(new, accepted, stale_entries)."""
        new, accepted = [], []
        hit: Set[Tuple[str, str, str]] = set()
        for d in diags:
            if d.key() in self._index:
                accepted.append(d)
                hit.add(d.key())
            else:
                new.append(d)
        stale = [e for k, e in self._index.items() if k not in hit]
        return new, accepted, stale

    @staticmethod
    def entry_for(d: Diagnostic, reason: str) -> dict:
        return {"code": d.code, "path": d.path, "message": d.message,
                "symbol": d.symbol, "reason": reason}
