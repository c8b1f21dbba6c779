"""Static analysis for durable graphs: replay safety + repo invariants.

Two layers (docs/static-analysis.md):

  - **Replay-safety checking of task functions** (``RS1xx``) — AST-walk a
    callable (or every node of a :class:`~repro.core.graph.Graph`) for
    determinism hazards that would break bit-identical replay: wall-clock
    reads, unseeded RNG, ambient I/O, mutation of captured state, and
    iteration over unordered sets. Wired into graph registration via
    ``Graph.add(..., check="warn"|"error"|"off")`` (default from the
    ``REPRO_LINT`` env var), so the contract travels with user code.
  - **Repo-invariant checks** (``INVxxx``) — lint the framework tree
    itself: journal-kind exhaustiveness across the four switch sites,
    and the wall-vs-monotonic clock policy. Run via ``python -m repro lint``.

Pure stdlib (``ast``, ``inspect``, ``dis``); importing this package pulls
in none of the runtime.
"""

from .findings import (
    CODES,
    Finding,
    ReplayUnsafeError,
    ReplayUnsafeWarning,
    load_baseline,
    split_baselined,
    write_baseline,
)
from .invariants import (
    KIND_SITES,
    check_clock_policy,
    check_kind_exhaustiveness,
    known_kinds,
)
from .replay import check_callable, check_graph, check_source_tasks

__all__ = [
    "CODES",
    "Finding",
    "KIND_SITES",
    "ReplayUnsafeError",
    "ReplayUnsafeWarning",
    "check_callable",
    "check_clock_policy",
    "check_graph",
    "check_kind_exhaustiveness",
    "check_source_tasks",
    "known_kinds",
    "lint_paths",
    "load_baseline",
    "split_baselined",
    "write_baseline",
]


def lint_paths(*args, **kwargs):
    """Proxy to :func:`repro.analysis.cli.lint_paths` (lazy import)."""
    from .cli import lint_paths as _lint_paths

    return _lint_paths(*args, **kwargs)
