"""repro.analysis — replay-safety detectors, repo invariants, lint CLI.

Covers docs/static-analysis.md:
  - per-detector positive + negative cases (RS101–RS105), including
    decorated, nested, and lambda task functions, and the RS900 bytecode
    fallback for sourceless callables;
  - registration-time enforcement: ``Graph.add(..., check=...)`` warn and
    error modes on both executors, the ``REPRO_LINT`` env default, and
    rejection of invalid modes;
  - the kind-exhaustiveness regression: a kind injected into
    ``KNOWN_KINDS`` must be reported at ALL FOUR switch sites
    (replay/compact/lineage/timeline) — a new journal kind cannot ship
    without every reader handling it;
  - clock-policy (INV201) detection;
  - the ``python -m repro lint`` CLI: ``--json``, baseline write +
    suppression, exit codes, and the self-test that the committed tree is
    clean modulo the committed baseline.
"""

import functools
import json
import os
import random
import subprocess
import sys
import time

import pytest

from repro.analysis import (
    Finding,
    ReplayUnsafeError,
    ReplayUnsafeWarning,
    check_callable,
    check_clock_policy,
    check_graph,
    check_kind_exhaustiveness,
    check_source_tasks,
    load_baseline,
    split_baselined,
    write_baseline,
)
from repro.core import ContextGraph, Gateway, InProcWorker, LocalExecutor, TaskRegistry
from repro.core import ClusterExecutor
from repro.core import durable as durable_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")


def codes(findings):
    return sorted(f.code for f in findings)


# --------------------------------------------------------------------------
# RS detectors — live callables
# --------------------------------------------------------------------------


def _clock_task(ctx):
    return time.time()


def _rng_task(ctx):
    return random.random()


def _io_task(ctx, p):
    with open(p) as fh:
        return fh.read()


_SINK = []


def _mutating_task(ctx, x):
    _SINK.append(x)
    return x


def _global_write_task(ctx, x):
    global _TOTAL
    _TOTAL = x
    return x


def _set_iter_task(ctx, xs):
    return [i for i in set(xs)]


def _clean_task(ctx, a, b):
    out = []
    for i in sorted(set(a)):  # sorted() iteration is fine
        out.append(i * b)  # local mutation is fine
    return out


_leaky_lambda = lambda ctx: time.time()  # noqa: E731 — lambda task on purpose


def _passthrough(fn):
    @functools.wraps(fn)
    def inner(*a, **kw):
        return fn(*a, **kw)

    return inner


@_passthrough
def _decorated_task(ctx):
    return random.randint(0, 9)


def _make_nested_task():
    def inner(ctx):
        return time.monotonic()

    return inner


@pytest.mark.parametrize(
    "fn, want",
    [
        (_clock_task, ["RS101"]),
        (_rng_task, ["RS102"]),
        (_io_task, ["RS103"]),
        (_mutating_task, ["RS104"]),
        (_global_write_task, ["RS104"]),
        (_set_iter_task, ["RS105"]),
        (_clean_task, []),
        (_leaky_lambda, ["RS101"]),
        (_decorated_task, ["RS102"]),  # seen through functools.wraps
        (_make_nested_task(), ["RS101"]),  # closure-defined task
    ],
    ids=[
        "clock",
        "rng",
        "io",
        "mutation",
        "global-write",
        "set-iter",
        "clean",
        "lambda",
        "decorated",
        "nested",
    ],
)
def test_detector_matrix(fn, want):
    assert codes(check_callable(fn)) == want


def test_seeded_rng_factory_is_clean_unseeded_is_not():
    import numpy as np

    def seeded(ctx, seed):
        return np.random.default_rng(seed).normal()

    def unseeded(ctx):
        return np.random.default_rng().normal()

    assert check_callable(seeded) == []
    assert codes(check_callable(unseeded)) == ["RS102"]


def test_findings_carry_location_and_snippet():
    (f,) = check_callable(_clock_task)
    assert f.symbol.endswith("_clock_task")
    assert f.path.endswith("test_analysis.py")
    assert "time.time()" in f.snippet
    assert f.line > 0


def test_bytecode_fallback_for_sourceless_function():
    ns = {"time": time}
    exec("def ghost(ctx):\n    return time.time()", ns)
    assert codes(check_callable(ns["ghost"])) == ["RS900"]


def test_non_function_callables_are_skipped():
    assert check_callable(len) == []
    assert check_callable(map) == []


def test_check_graph_walks_callable_nodes_and_skips_registry_names():
    g = ContextGraph(name="lintme")
    g.add("a", _clock_task)
    g.add("b", "registry-task-name", deps=["a"])
    found = check_graph(g)
    assert codes(found) == ["RS101"]
    assert found[0].symbol.startswith("a:")


# --------------------------------------------------------------------------
# RS detectors — static (file) mode
# --------------------------------------------------------------------------

_STATIC_SRC = """
import time
import numpy as np
from repro.core.durable import atomic_task

@atomic_task
def leaky(ctx):
    return time.time()

def helper_not_a_task():
    return time.time()  # not registered: RS does not apply

def named(ctx):
    return np.random.rand(3)

g.add("n1", named)
g.add_stream("n2", fn=lambda ctx, start=0: iter([time.time()]))
"""


def test_static_mode_checks_only_task_functions():
    found = check_source_tasks(_STATIC_SRC, path="x.py")
    assert codes(found) == ["RS101", "RS101", "RS102"]
    assert {f.symbol for f in found} == {"leaky", "named", "<lambda>"}


def test_static_mode_tolerates_syntax_errors():
    assert check_source_tasks("def broken(:", path="x.py") == []


# --------------------------------------------------------------------------
# registration-time enforcement
# --------------------------------------------------------------------------


def test_add_check_off_is_silent_default():
    g = ContextGraph()
    g.add("t", _clock_task)  # no warning machinery triggered
    assert "t" in g.nodes


def test_add_check_warn_warns_and_registers():
    g = ContextGraph()
    with pytest.warns(ReplayUnsafeWarning, match="RS101"):
        g.add("t", _clock_task, check="warn")
    assert "t" in g.nodes


def test_add_check_error_raises_and_does_not_register():
    g = ContextGraph()
    with pytest.raises(ReplayUnsafeError) as exc:
        g.add("t", _clock_task, check="error")
    assert "t" not in g.nodes
    assert [f.code for f in exc.value.findings] == ["RS101"]


def test_add_check_error_passes_clean_function():
    g = ContextGraph()
    g.add("t", _clean_task, check="error")
    assert "t" in g.nodes


def test_add_check_rejects_unknown_mode():
    g = ContextGraph()
    with pytest.raises(ValueError, match="check must be"):
        g.add("t", _clean_task, check="loud")


def test_repro_lint_env_sets_the_default(monkeypatch):
    monkeypatch.setenv("REPRO_LINT", "error")
    g = ContextGraph()
    with pytest.raises(ReplayUnsafeError):
        g.add("t", _clock_task)
    g.add("ok", _clock_task, check="off")  # explicit arg beats the env
    assert "ok" in g.nodes


def test_warn_mode_graph_runs_on_local_executor():
    g = ContextGraph(name="warn-local")
    with pytest.warns(ReplayUnsafeWarning):
        g.add("t", _clock_task, check="warn")
    rep = LocalExecutor().run(g)
    assert "t" in rep.outputs and "t" in rep.executed


def test_warn_mode_graph_runs_on_cluster_executor():
    reg = TaskRegistry()

    @reg.task("leaky")
    def leaky(ctx):
        return time.time()

    g = ContextGraph(name="warn-cluster")
    with pytest.warns(ReplayUnsafeWarning):
        g.add("t", leaky, check="warn")
    g.nodes["t"].fn = "leaky"  # dispatch via the registry name
    with Gateway([InProcWorker("w0", reg)]) as gw:
        rep = ClusterExecutor(gw).run(g)
    assert "t" in rep.outputs and "t" in rep.executed


# --------------------------------------------------------------------------
# kind exhaustiveness — the regression the tentpole exists for
# --------------------------------------------------------------------------


def test_kind_exhaustiveness_clean_on_current_tree():
    assert check_kind_exhaustiveness(REPO) == []


def test_new_kind_is_reported_at_all_four_switch_sites(monkeypatch):
    """A kind added to KNOWN_KINDS without reader support cannot ship."""
    monkeypatch.setattr(
        durable_mod,
        "KNOWN_KINDS",
        frozenset(durable_mod.KNOWN_KINDS | {"FAKE_KIND"}),
    )
    found = check_kind_exhaustiveness(REPO)
    assert codes(found) == ["INV101"] * 4
    sites = {f.symbol.split(":")[0] for f in found}
    assert sites == {"replay", "compact", "lineage", "timeline"}
    assert all(f.symbol.endswith(":FAKE_KIND") for f in found)


def test_stale_kind_at_a_site_is_reported(tmp_path, monkeypatch):
    """A kind handled by a reader but absent from KNOWN_KINDS is INV102."""
    site_dir = tmp_path / "src" / "repro" / "core"
    site_dir.mkdir(parents=True)
    (site_dir / "durable.py").write_text(
        "REPLAY_IGNORED_KINDS = frozenset({'GHOST_KIND'})\n"
        "class ReplayCache:\n"
        "    def scan(self, rec):\n"
        "        if rec.kind == 'NODE_COMMIT':\n"
        "            pass\n"
    )
    sites = (("replay", "src/repro/core/durable.py", "ReplayCache", "REPLAY_IGNORED_KINDS"),)
    found = check_kind_exhaustiveness(str(tmp_path), sites=sites)
    assert "INV102" in codes(found)
    assert any("GHOST_KIND" in f.message for f in found)


# --------------------------------------------------------------------------
# clock policy
# --------------------------------------------------------------------------


def test_clock_policy_flags_unjustified_and_accepts_justified():
    bad = "import time\n\ndef f():\n    return time.time()\n"
    good = "import time\n\ndef f():\n    return time.time()  # record timestamp\n"
    good2 = (
        "import time\n\ndef f():\n"
        "    # wall-clock: compared against journaled absolute deadline\n"
        "    return time.time()\n"
    )
    assert codes(check_clock_policy(bad, path="x.py")) == ["INV201"]
    assert check_clock_policy(good, path="x.py") == []
    assert check_clock_policy(good2, path="x.py") == []
    # monotonic is always policy-clean
    mono = "import time\n\ndef f():\n    return time.monotonic()\n"
    assert check_clock_policy(mono, path="x.py") == []


# --------------------------------------------------------------------------
# baseline mechanics
# --------------------------------------------------------------------------


def test_fingerprint_survives_line_drift_but_not_code_change():
    a = Finding(code="RS101", message="m", path="p.py", line=10, symbol="f", snippet="s")
    b = Finding(code="RS101", message="m", path="p.py", line=99, symbol="f", snippet="s")
    c = Finding(code="RS101", message="m", path="p.py", line=10, symbol="f", snippet="t")
    assert a.fingerprint() == b.fingerprint()
    assert a.fingerprint() != c.fingerprint()


def test_baseline_roundtrip_and_split(tmp_path):
    path = str(tmp_path / "base.json")
    f1 = Finding(code="RS101", message="m1", path="a.py", symbol="f")
    f2 = Finding(code="RS104", message="m2", path="b.py", symbol="g")
    assert write_baseline(path, [f1]) == 1
    base = load_baseline(path)
    new, suppressed = split_baselined([f1, f2], base)
    assert new == [f2] and suppressed == [f1]
    assert load_baseline(str(tmp_path / "missing.json")) == set()


# --------------------------------------------------------------------------
# CLI (subprocess)
# --------------------------------------------------------------------------

_DIRTY_TREE_SRC = """
import time
from repro.core.durable import atomic_task

@atomic_task
def leaky(ctx):
    return time.time()
"""


@pytest.fixture
def dirty_tree(tmp_path):
    (tmp_path / "pyproject.toml").write_text("[project]\nname = 'x'\n")
    pkg = tmp_path / "lib"
    pkg.mkdir()
    (pkg / "tasks.py").write_text(_DIRTY_TREE_SRC)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("REPRO_LINT", None)
    return tmp_path, env


def _lint(args, cwd, env):
    return subprocess.run(
        [sys.executable, "-m", "repro", "lint", *args],
        capture_output=True,
        text=True,
        cwd=cwd,
        env=env,
        timeout=120,
    )


def test_cli_reports_findings_and_exits_1(dirty_tree):
    root, env = dirty_tree
    proc = _lint(["lib"], cwd=root, env=env)
    assert proc.returncode == 1, proc.stderr
    assert "RS101" in proc.stdout and "lib/tasks.py" in proc.stdout


def test_cli_json_output(dirty_tree):
    root, env = dirty_tree
    proc = _lint(["lib", "--json"], cwd=root, env=env)
    assert proc.returncode == 1, proc.stderr
    obj = json.loads(proc.stdout)
    assert obj["counts"] == {"new": 1, "suppressed": 0}
    (f,) = obj["findings"]
    assert f["code"] == "RS101" and f["path"] == "lib/tasks.py" and f["fingerprint"]


def test_cli_baseline_write_then_suppress(dirty_tree):
    root, env = dirty_tree
    proc = _lint(["lib", "--write-baseline"], cwd=root, env=env)
    assert proc.returncode == 0, proc.stderr
    assert (root / ".repro-lint-baseline.json").exists()
    proc = _lint(["lib"], cwd=root, env=env)
    assert proc.returncode == 0, proc.stderr
    assert "suppressed by baseline" in proc.stdout
    # --no-baseline un-suppresses
    proc = _lint(["lib", "--no-baseline"], cwd=root, env=env)
    assert proc.returncode == 1


def test_cli_select_filters_codes(dirty_tree):
    root, env = dirty_tree
    proc = _lint(["lib", "--select", "RS104"], cwd=root, env=env)
    assert proc.returncode == 0, proc.stderr  # the RS101 is filtered out
    proc = _lint(["lib", "--select", "RS101,RS104"], cwd=root, env=env)
    assert proc.returncode == 1


def test_cli_bad_path_exits_2(dirty_tree):
    root, env = dirty_tree
    proc = _lint(["definitely/not/here"], cwd=root, env=env)
    assert proc.returncode == 2
    assert "no such path" in proc.stderr


def test_cli_explain(dirty_tree):
    root, env = dirty_tree
    proc = _lint(["--explain", "RS101"], cwd=root, env=env)
    assert proc.returncode == 0 and "replay-safety" in proc.stdout
    proc = _lint(["--explain", "RS999"], cwd=root, env=env)
    assert proc.returncode == 2


def test_repo_tree_is_clean_modulo_committed_baseline():
    """The acceptance gate: lint src/ tests/ benchmarks/ exits 0."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("REPRO_LINT", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro", "lint", "src", "tests", "benchmarks"],
        capture_output=True,
        text=True,
        cwd=REPO,
        env=env,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
