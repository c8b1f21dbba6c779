"""Finding model, detector catalog, and baseline handling for ``repro.analysis``.

A :class:`Finding` is one detector hit: a stable ``code`` (``RS1xx`` replay
safety, ``INVxxx`` repo invariants), a human message, and enough location
context to render and to *fingerprint*. Fingerprints deliberately exclude
the line number — a baseline entry survives unrelated edits that shift the
file, and dies exactly when the flagged code itself changes.

The baseline file (``.repro-lint-baseline.json``, committed at the repo
root) grandfathers pre-existing findings so the lint gate can be adopted on
a tree that is not yet clean, then ratchet: new findings fail, baselined
ones are reported as suppressed. See docs/static-analysis.md §5.
"""

from __future__ import annotations

import hashlib
import json
import os
from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = [
    "CODES",
    "Finding",
    "ReplayUnsafeError",
    "ReplayUnsafeWarning",
    "load_baseline",
    "split_baselined",
    "write_baseline",
]


class ReplayUnsafeWarning(UserWarning):
    """A task function registered with ``check="warn"`` has determinism hazards."""


class ReplayUnsafeError(ValueError):
    """A task function registered with ``check="error"`` has determinism hazards.

    Carries the offending :class:`Finding` list as ``findings``.
    """

    def __init__(self, message: str, findings: Sequence["Finding"] = ()):
        super().__init__(message)
        self.findings: Tuple["Finding", ...] = tuple(findings)


#: Detector catalog: code -> (category, one-line description). The RS1xx
#: family applies to *task functions* (replay-safety contract,
#: docs/durable-workflows.md §1); the INVxxx family lints the framework
#: tree itself (docs/static-analysis.md §3).
CODES: Dict[str, Tuple[str, str]] = {
    "RS101": ("replay-safety", "wall-clock or monotonic-clock read in a task function"),
    "RS102": ("replay-safety", "unseeded random number generation in a task function"),
    "RS103": ("replay-safety", "ambient I/O (file, env, network, process) in a task function"),
    "RS104": ("replay-safety", "mutation of captured closure/global state in a task function"),
    "RS105": ("replay-safety", "iteration over an unordered set feeding a task result"),
    "RS900": ("replay-safety", "possible determinism hazard (bytecode heuristic, no source)"),
    "INV101": ("journal-kinds", "journal kind not handled or declared-ignored at a switch site"),
    "INV102": ("journal-kinds", "stale kind at a switch site (absent from KNOWN_KINDS)"),
    "INV201": ("clock-policy", "time.time() call site without a policy justification comment"),
    "E999": ("parse", "file could not be parsed (syntax error or unreadable)"),
}


@dataclass(frozen=True)
class Finding:
    """One detector hit — immutable, hashable, JSON-serializable."""

    code: str
    message: str
    path: str = ""  # repo-relative when produced by the CLI walker
    line: int = 0
    symbol: str = ""  # function qualname / invariant site name
    snippet: str = ""  # offending source line, whitespace-stripped

    def fingerprint(self) -> str:
        """Stable identity for baseline matching.

        Hashes ``(code, path, symbol, snippet)`` — NOT the line number, so
        a baseline entry survives unrelated edits above the flagged line
        and expires exactly when the flagged code itself changes.
        """
        basis = "\x00".join((self.code, self.path, self.symbol, self.snippet))
        return hashlib.sha256(basis.encode()).hexdigest()[:16]

    def category(self) -> str:
        """Catalog category for this finding's code."""
        return CODES.get(self.code, ("unknown", ""))[0]

    def to_obj(self) -> Dict[str, Any]:
        """Plain-dict form (CLI ``--json`` output and baseline entries)."""
        return {
            "code": self.code,
            "message": self.message,
            "path": self.path,
            "line": self.line,
            "symbol": self.symbol,
            "snippet": self.snippet,
            "fingerprint": self.fingerprint(),
        }

    def render(self) -> str:
        """One human-readable line (``path:line: CODE [symbol] message``)."""
        where = f"{self.path}:{self.line}" if self.path else (self.symbol or "<callable>")
        sym = f" [{self.symbol}]" if self.symbol and self.path else ""
        tail = f" :: {self.snippet}" if self.snippet else ""
        return f"{where}: {self.code}{sym} {self.message}{tail}"


# --------------------------------------------------------------------------
# baseline
# --------------------------------------------------------------------------

BASELINE_VERSION = 1


def load_baseline(path: str) -> Set[str]:
    """Load the fingerprint set from a baseline file (empty set if absent)."""
    if not os.path.exists(path):
        return set()
    with open(path, encoding="utf-8") as fh:
        obj = json.load(fh)
    return {str(e["fingerprint"]) for e in obj.get("findings", ())}


def write_baseline(path: str, findings: Iterable[Finding]) -> int:
    """Write ``findings`` as the new baseline; returns the entry count.

    Entries keep the human-readable context next to each fingerprint so a
    reviewer can audit what exactly is being grandfathered.
    """
    entries = sorted(
        (
            {
                "fingerprint": f.fingerprint(),
                "code": f.code,
                "path": f.path,
                "symbol": f.symbol,
                "message": f.message,
            }
            for f in findings
        ),
        key=lambda e: (e["path"], e["code"], e["fingerprint"]),
    )
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"version": BASELINE_VERSION, "findings": entries}, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return len(entries)


def split_baselined(
    findings: Sequence[Finding], baseline: Optional[Set[str]]
) -> Tuple[List[Finding], List[Finding]]:
    """Partition ``findings`` into ``(new, suppressed-by-baseline)``."""
    if not baseline:
        return list(findings), []
    new: List[Finding] = []
    suppressed: List[Finding] = []
    for f in findings:
        (suppressed if f.fingerprint() in baseline else new).append(f)
    return new, suppressed
