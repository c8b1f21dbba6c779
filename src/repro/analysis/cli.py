"""``python -m repro lint`` — run the static-analysis suite over a tree.

Walks the given paths (default: ``src``) for Python files and runs:

  - the replay-safety detectors (RS1xx) over every *task-decorated*
    function found statically (``@atomic_task``, ``@graph.task(...)``, and
    callables passed to ``Graph.add``/``add_stream``);
  - the clock-policy check (INV201) over files inside ``src/repro``;
  - the journal-kind exhaustiveness check (INV101/INV102) once per
    invocation, against the repo's four switch sites.

Findings already recorded in the committed baseline
(``.repro-lint-baseline.json`` at the repo root) are reported as
suppressed and do not fail the run; anything new exits 1. See
docs/static-analysis.md §5 for the ratchet workflow.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import sys
from typing import Iterable, List, Optional, Sequence, Tuple

from .findings import CODES, Finding, load_baseline, split_baselined, write_baseline
from .invariants import check_clock_policy, check_kind_exhaustiveness
from .replay import check_source_tasks

__all__ = ["add_lint_parser", "cmd_lint", "find_repo_root", "lint_paths", "main"]

BASELINE_NAME = ".repro-lint-baseline.json"

_SKIP_DIRS = frozenset({"__pycache__", ".git", ".ruff_cache", ".pytest_cache", "build"})


def find_repo_root(start: Optional[str] = None) -> str:
    """Nearest ancestor of ``start`` (default: cwd) holding a pyproject.toml."""
    cur = os.path.abspath(start or os.getcwd())
    while True:
        if os.path.exists(os.path.join(cur, "pyproject.toml")):
            return cur
        parent = os.path.dirname(cur)
        if parent == cur:
            return os.path.abspath(start or os.getcwd())
        cur = parent


def _iter_py_files(paths: Sequence[str]) -> Iterable[str]:
    """Every ``.py`` file under ``paths`` (files pass through, dirs walk)."""
    for path in paths:
        if os.path.isfile(path):
            if path.endswith(".py"):
                yield path
            continue
        for dirpath, dirnames, filenames in os.walk(path):
            dirnames[:] = sorted(d for d in dirnames if d not in _SKIP_DIRS)
            for fname in sorted(filenames):
                if fname.endswith(".py"):
                    yield os.path.join(dirpath, fname)


def _package_of(rel_path: str) -> Tuple[str, ...]:
    """Dotted package tuple for a file path like ``src/repro/core/graph.py``."""
    parts = rel_path.replace(os.sep, "/").split("/")
    if "src" in parts:
        parts = parts[parts.index("src") + 1 :]
    if parts and parts[-1].endswith(".py"):
        parts = parts[:-1] if parts[-1] != "__init__.py" else parts[:-1]
    return tuple(p for p in parts if p)


def _rel(path: str, root: str) -> str:
    """Repo-relative, forward-slash form of ``path`` (stable fingerprints)."""
    rel = os.path.relpath(os.path.abspath(path), root)
    return rel.replace(os.sep, "/")


def lint_paths(
    paths: Sequence[str],
    repo_root: Optional[str] = None,
    select: Optional[Sequence[str]] = None,
    kind_checks: bool = True,
) -> List[Finding]:
    """Run every applicable detector over ``paths``; returns raw findings.

    ``select`` filters by code prefix (``["RS"]``, ``["INV201"]``, ...).
    ``kind_checks=False`` skips the repo-level INV101/INV102 pass (used by
    tests that lint synthetic trees with no switch sites).
    """
    root = repo_root or find_repo_root(paths[0] if paths else None)
    findings: List[Finding] = []
    for path in _iter_py_files(paths):
        rel = _rel(path, root)
        try:
            with open(path, encoding="utf-8") as fh:
                text = fh.read()
        except OSError as exc:
            findings.append(
                Finding(code="E999", message=f"unreadable: {exc}", path=rel)
            )
            continue
        try:
            ast.parse(text)
        except SyntaxError as exc:
            findings.append(
                Finding(
                    code="E999",
                    message=f"syntax error: {exc.msg}",
                    path=rel,
                    line=exc.lineno or 0,
                )
            )
            continue
        package = _package_of(rel)
        findings.extend(check_source_tasks(text, path=rel, package=package))
        if rel.startswith("src/repro/"):
            findings.extend(check_clock_policy(text, path=rel, package=package))
    if kind_checks and os.path.isdir(os.path.join(root, "src", "repro")):
        # repo-level pass: only meaningful when the framework tree itself
        # is under this root (out-of-tree user code has no switch sites)
        findings.extend(check_kind_exhaustiveness(root))
    if select:
        prefixes = tuple(s.strip() for s in select if s.strip())
        findings = [f for f in findings if f.code.startswith(prefixes)]
    findings.sort(key=lambda f: (f.path, f.line, f.code, f.symbol))
    return findings


def add_lint_parser(subparsers: "argparse._SubParsersAction") -> None:
    """Register the ``lint`` subcommand on ``python -m repro``'s parser."""
    p = subparsers.add_parser(
        "lint",
        help="run the replay-safety and repo-invariant static analysis",
        description=(
            "Static analysis for durable graphs: replay-safety of task "
            "functions (RS1xx) and framework invariants (INVxxx). "
            "Exit 0 = clean modulo baseline, 1 = new findings, 2 = error."
        ),
    )
    p.add_argument(
        "paths",
        nargs="*",
        default=["src"],
        help="files or directories to lint (default: src)",
    )
    p.add_argument(
        "--select",
        action="append",
        default=None,
        metavar="PREFIX",
        help="only report codes matching these prefixes (repeatable, "
        "comma-separated; e.g. --select RS --select INV201)",
    )
    p.add_argument("--json", action="store_true", help="machine-readable output")
    p.add_argument(
        "--baseline",
        default=None,
        metavar="PATH",
        help=f"baseline file (default: <repo-root>/{BASELINE_NAME})",
    )
    p.add_argument(
        "--no-baseline",
        action="store_true",
        help="ignore the baseline: report and fail on every finding",
    )
    p.add_argument(
        "--write-baseline",
        action="store_true",
        help="write current findings to the baseline file and exit 0",
    )
    p.add_argument(
        "--explain",
        metavar="CODE",
        default=None,
        help="print the catalog entry for one code and exit",
    )
    p.set_defaults(fn=cmd_lint)


def cmd_lint(args: "argparse.Namespace") -> int:
    """Entry point for the ``lint`` subcommand; returns the exit code."""
    if args.explain:
        entry = CODES.get(args.explain)
        if entry is None:
            print(f"unknown code {args.explain!r}", file=sys.stderr)
            return 2
        print(f"{args.explain} [{entry[0]}] {entry[1]}")
        return 0

    select: Optional[List[str]] = None
    if args.select:
        select = [s for chunk in args.select for s in chunk.split(",") if s]

    repo_root = find_repo_root()
    missing = [p for p in args.paths if not os.path.exists(p)]
    if missing:
        print(f"lint: no such path(s): {', '.join(missing)}", file=sys.stderr)
        return 2
    findings = lint_paths(args.paths, repo_root=repo_root, select=select)

    baseline_path = args.baseline or os.path.join(repo_root, BASELINE_NAME)
    if args.write_baseline:
        n = write_baseline(baseline_path, findings)
        print(f"wrote {n} baseline entries to {baseline_path}")
        return 0

    baseline = None if args.no_baseline else load_baseline(baseline_path)
    new, suppressed = split_baselined(findings, baseline)

    if args.json:
        print(
            json.dumps(
                {
                    "findings": [f.to_obj() for f in new],
                    "suppressed": [f.to_obj() for f in suppressed],
                    "counts": {"new": len(new), "suppressed": len(suppressed)},
                },
                sort_keys=True,
            )
        )
    else:
        for f in new:
            print(f.render())
        tail = f"{len(new)} finding(s)"
        if suppressed:
            tail += f", {len(suppressed)} suppressed by baseline"
        print(tail)
    return 1 if new else 0


def main(argv: Optional[Sequence[str]] = None) -> int:
    """Standalone entry (``python -m repro.analysis.cli``)."""
    parser = argparse.ArgumentParser(prog="repro-lint")
    sub = parser.add_subparsers(dest="cmd", required=True)
    add_lint_parser(sub)
    args = parser.parse_args(["lint", *(argv if argv is not None else sys.argv[1:])])
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
