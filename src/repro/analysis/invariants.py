"""Repo-invariant checks: lint the framework against its own conventions.

Two invariant families, each enforcing a contract the code cannot express
in types (docs/static-analysis.md §3):

  - ``INV101``/``INV102`` — **journal-kind exhaustiveness.** Four
    independent readers switch over record kinds: replay
    (``core/durable.py`` :class:`ReplayCache`), compaction
    (``journal/compact.py`` ``_fold``), lineage (``journal/lineage.py``
    ``apply``), and the run timeline (``obs/timeline.py``
    ``from_records``). Each site must account for EVERY kind in
    ``KNOWN_KINDS`` — either by handling it (a literal comparison /
    membership test against the ``kind``) or by naming it in the site's
    declared ignore-set constant. Without this check, a newly added kind
    compiles clean while silently dropping history at whichever sites
    forgot it.
  - ``INV201`` — **wall-vs-monotonic clock policy.** Inside ``src/repro``,
    ``time.time()`` is legal only for *record timestamps* (journal records,
    span logs, mtime comparisons); duration and liveness math must use
    ``time.monotonic()`` (PRs 5 and 9 swept those call sites by hand).
    Every remaining ``time.time()`` must carry a justification comment —
    ``# record timestamp`` or ``# wall-clock: <reason>`` — on its own or
    the preceding line.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .findings import Finding
from .replay import StaticResolver, _canonical

__all__ = [
    "KIND_SITES",
    "check_clock_policy",
    "check_kind_exhaustiveness",
    "collect_kind_coverage",
    "known_kinds",
]

#: The four journal-kind switch sites: (site name, repo-relative path,
#: scope name within the file, declared ignore-set constant in that module).
KIND_SITES: Tuple[Tuple[str, str, str, str], ...] = (
    ("replay", "src/repro/core/durable.py", "ReplayCache", "REPLAY_IGNORED_KINDS"),
    ("compact", "src/repro/journal/compact.py", "_fold", "DROPPABLE_KINDS"),
    ("lineage", "src/repro/journal/lineage.py", "apply", "LINEAGE_IGNORED_KINDS"),
    ("timeline", "src/repro/obs/timeline.py", "from_records", "TIMELINE_IGNORED_KINDS"),
)

#: Justification marker for a wall-clock call site (INV201). Matches the
#: established ``# record timestamp`` convention plus an explicit
#: ``# wall-clock: <reason>`` escape hatch.
CLOCK_JUSTIFICATION = re.compile(r"#\s*(record timestamp|wall[- ]clock)", re.IGNORECASE)

_WALL_CALLS = frozenset({"time.time", "time.time_ns"})

def known_kinds() -> Set[str]:
    """The journal-kind vocabulary, read from the runtime source of truth.

    Resolved at call time (not import time) so tests can inject a fake kind
    into ``repro.core.durable.KNOWN_KINDS`` and watch every switch site
    light up.
    """
    from repro.core import durable

    return set(durable.KNOWN_KINDS)


# -- kind exhaustiveness ----------------------------------------------------


def _module_set_constants(tree: ast.Module) -> Dict[str, Set[str]]:
    """Module-level ``NAME = frozenset({...})`` / set / tuple / list of str."""
    consts: Dict[str, Set[str]] = {}
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        if not isinstance(target, ast.Name):
            continue
        values = _literal_strings(node.value)
        if values is not None:
            consts[target.id] = values
    return consts


def _literal_strings(node: ast.AST) -> Optional[Set[str]]:
    """The string elements of a literal set/tuple/list/frozenset(...), else None."""
    if isinstance(node, ast.Call):
        func = node.func
        name = func.id if isinstance(func, ast.Name) else getattr(func, "attr", "")
        if name in ("frozenset", "set") and len(node.args) == 1:
            return _literal_strings(node.args[0])
        return None
    if isinstance(node, (ast.Set, ast.Tuple, ast.List)):
        out: Set[str] = set()
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                out.add(elt.value)
            else:
                return None  # non-literal element: not a kind vocabulary
        return out
    return None


def _find_scope(tree: ast.Module, scope_name: str) -> Optional[ast.AST]:
    """The first ClassDef/FunctionDef named ``scope_name`` anywhere in the file."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name == scope_name:
                return node
    return None


def _mentions_kind(node: ast.AST) -> bool:
    """True if an expression reads a ``kind`` (``rec.kind`` or a kind var)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr == "kind":
            return True
        if isinstance(sub, ast.Name) and sub.id.endswith("kind"):
            return True
    return False


def _handled_kinds(scope: ast.AST, consts: Dict[str, Set[str]]) -> Set[str]:
    """String literals a scope compares (or membership-tests) a kind against."""
    handled: Set[str] = set()
    for node in ast.walk(scope):
        if not isinstance(node, ast.Compare):
            continue
        operands = [node.left, *node.comparators]
        if not any(_mentions_kind(op) for op in operands):
            continue
        for op, comparator in zip(node.ops, node.comparators, strict=True):
            if isinstance(op, (ast.Eq, ast.NotEq)):
                for cand in (node.left, comparator):
                    if isinstance(cand, ast.Constant) and isinstance(cand.value, str):
                        handled.add(cand.value)
            elif isinstance(op, (ast.In, ast.NotIn)):
                literal = _literal_strings(comparator)
                if literal is not None:
                    handled.update(literal)
                elif isinstance(comparator, ast.Name) and comparator.id in consts:
                    handled.update(consts[comparator.id])
    return handled


def collect_kind_coverage(
    text: str, scope_name: str, ignore_const: str
) -> Tuple[Set[str], Set[str]]:
    """``(handled, declared_ignored)`` kind sets for one switch site's file."""
    tree = ast.parse(text)
    consts = _module_set_constants(tree)
    scope = _find_scope(tree, scope_name)
    handled = _handled_kinds(scope, consts) if scope is not None else set()
    return handled, consts.get(ignore_const, set())


def check_kind_exhaustiveness(
    repo_root: str, sites: Sequence[Tuple[str, str, str, str]] = KIND_SITES
) -> List[Finding]:
    """INV101/INV102 findings across the journal-kind switch sites."""
    import os

    vocabulary = known_kinds()
    findings: List[Finding] = []
    for site, rel_path, scope_name, ignore_const in sites:
        path = os.path.join(repo_root, rel_path)
        if not os.path.exists(path):
            findings.append(
                Finding(
                    code="INV101",
                    message=f"switch site file missing: {rel_path}",
                    path=rel_path,
                    symbol=site,
                )
            )
            continue
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
        handled, ignored = collect_kind_coverage(text, scope_name, ignore_const)
        covered = handled | ignored
        for kind in sorted(vocabulary - covered):
            findings.append(
                Finding(
                    code="INV101",
                    message=(
                        f"journal kind {kind!r} is neither handled in "
                        f"{scope_name} nor declared in {ignore_const} — a "
                        f"record of this kind would be silently dropped by "
                        f"the {site} reader"
                    ),
                    path=rel_path,
                    symbol=f"{site}:{kind}",
                    snippet=kind,
                )
            )
        for kind in sorted(covered - vocabulary):
            findings.append(
                Finding(
                    code="INV102",
                    message=(
                        f"kind {kind!r} at the {site} site is not in "
                        "KNOWN_KINDS — stale vocabulary or a typo"
                    ),
                    path=rel_path,
                    symbol=f"{site}:{kind}",
                    snippet=kind,
                )
            )
    return findings


# -- clock policy -----------------------------------------------------------


def check_clock_policy(
    text: str, path: str = "", package: Sequence[str] = ()
) -> List[Finding]:
    """INV201 findings: unjustified ``time.time()`` call sites in one file."""
    try:
        tree = ast.parse(text)
    except SyntaxError:
        return []
    resolver = StaticResolver(tree, package=package)
    lines = text.splitlines()
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        canon = _canonical(resolver, node.func, set())
        if canon not in _WALL_CALLS:
            continue
        lineno = node.lineno
        window = lines[max(0, lineno - 2) : lineno]  # the line + the one above
        if any(CLOCK_JUSTIFICATION.search(ln) for ln in window):
            continue
        findings.append(
            Finding(
                code="INV201",
                message=(
                    f"{canon}() without a policy justification — annotate "
                    "'# record timestamp' (or '# wall-clock: <reason>') if "
                    "this feeds a record, or switch to time.monotonic() if "
                    "it feeds duration/liveness math"
                ),
                path=path,
                line=lineno,
                symbol=canon,
                snippet=lines[lineno - 1].strip() if lineno <= len(lines) else "",
            )
        )
    return findings

