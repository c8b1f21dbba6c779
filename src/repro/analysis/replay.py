"""Replay-safety checker: AST determinism-hazard detectors for task functions.

Durable replay (docs/durable-workflows.md §1) assumes a task function is a
*pure function of its injected inputs and context*: re-executing it with the
same ``(ctx, **inputs)`` must reproduce the journaled output digest. This
module walks a task function's AST and flags the ways user code commonly
breaks that contract:

  - ``RS101`` — clock reads (``time.time``, ``datetime.now``, monotonic /
    perf counters): any clock read is a nondeterministic *value*. Sleeping
    is fine (no value); reading the time is not.
  - ``RS102`` — unseeded randomness (``random.*`` module-level, legacy
    ``np.random.*`` global state, ``default_rng()`` / ``Random()`` called
    without a seed, ``uuid4``, ``os.urandom``). The sanctioned idiom is the
    seeded generator ``np.random.default_rng(seed)`` that
    ``data/pipeline.py`` uses.
  - ``RS103`` — ambient I/O: ``open``, env reads, network, subprocesses,
    ``input``. Ambient state is invisible to the ``(ξ, inputs)`` digests,
    so a replay can silently read different data.
  - ``RS104`` — mutation of captured closure/global state (``global`` /
    top-level ``nonlocal`` writes, ``.append``/``.update``/item assignment
    on names the function does not bind): cross-call state leaks make the
    second execution see different inputs than the digest recorded.
  - ``RS105`` — iterating an unordered ``set`` expression: iteration order
    is salted per process, so results fed from it replay differently.
  - ``RS900`` — bytecode-heuristic fallback when source is unavailable,
    the same degradation path ``fn_digest`` in ``core/graph.py`` takes.

Two resolvers feed the same detectors: a *dynamic* one for live callables
(registration-time checks resolve names through ``fn.__globals__`` and the
closure), and a *static* one for linted files (an import-alias table built
from the module AST). Both reduce a call like ``np.random.rand(3)`` to the
canonical dotted name ``numpy.random.rand`` before the hazard tables apply.
"""

from __future__ import annotations

import ast
import builtins
import inspect
import textwrap
from types import CodeType, FunctionType, ModuleType
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

from .findings import Finding

__all__ = [
    "check_callable",
    "check_graph",
    "check_source_tasks",
]

# -- hazard tables (canonical dotted names) ---------------------------------

_WALL_CLOCK = frozenset(
    {
        "time.time",
        "time.time_ns",
        "time.monotonic",
        "time.monotonic_ns",
        "time.perf_counter",
        "time.perf_counter_ns",
        "time.process_time",
        "time.localtime",
        "time.gmtime",
        "time.ctime",
        "datetime.datetime.now",
        "datetime.datetime.utcnow",
        "datetime.datetime.today",
        "datetime.date.today",
    }
)

_UNSEEDED_RNG = frozenset(
    {
        "random.random",
        "random.randint",
        "random.randrange",
        "random.choice",
        "random.choices",
        "random.shuffle",
        "random.sample",
        "random.uniform",
        "random.gauss",
        "random.normalvariate",
        "random.getrandbits",
        "random.randbytes",
        "random.betavariate",
        "random.expovariate",
        "random.seed",
        "numpy.random.rand",
        "numpy.random.randn",
        "numpy.random.randint",
        "numpy.random.random",
        "numpy.random.random_sample",
        "numpy.random.ranf",
        "numpy.random.sample",
        "numpy.random.choice",
        "numpy.random.shuffle",
        "numpy.random.permutation",
        "numpy.random.normal",
        "numpy.random.uniform",
        "numpy.random.standard_normal",
        "numpy.random.seed",
        "os.urandom",
        "uuid.uuid1",
        "uuid.uuid4",
        "secrets.token_bytes",
        "secrets.token_hex",
        "secrets.token_urlsafe",
        "secrets.randbelow",
        "secrets.choice",
    }
)

#: RNG factories that are replay-safe *only when seeded*: a zero-argument
#: call falls back to OS entropy and is flagged; ``default_rng(seed)`` is
#: the sanctioned idiom.
_SEEDED_RNG_FACTORIES = frozenset({"numpy.random.default_rng", "random.Random"})

_AMBIENT_IO = frozenset(
    {
        "open",
        "io.open",
        "input",
        "os.getenv",
        "os.putenv",
        "os.listdir",
        "os.scandir",
        "os.walk",
        "os.system",
        "os.popen",
        "os.uname",
        "socket.gethostname",
        "socket.getfqdn",
        "platform.node",
        "getpass.getuser",
    }
)

#: Call prefixes that are ambient I/O wholesale (network + process spawn).
_AMBIENT_IO_PREFIXES = (
    "socket.",
    "subprocess.",
    "requests.",
    "urllib.",
    "http.client.",
)

#: Reads of the process environment (attribute/subscript access, not calls).
_AMBIENT_ATTRS = frozenset({"os.environ", "sys.stdin"})

_MUTATING_METHODS = frozenset(
    {
        "append",
        "extend",
        "insert",
        "add",
        "update",
        "pop",
        "popitem",
        "remove",
        "discard",
        "clear",
        "setdefault",
        "sort",
        "reverse",
        "appendleft",
        "extendleft",
        "popleft",
    }
)

#: Root names whose presence in a sourceless function's co_names is
#: suspicious enough to surface under the RS900 bytecode heuristic.
_BYTECODE_SUSPECTS = frozenset(
    {
        "time",
        "random",
        "secrets",
        "uuid",
        "socket",
        "subprocess",
        "requests",
        "urlopen",
        "urandom",
        "environ",
        "getenv",
        "open",
        "input",
    }
)
#: co_names entries too generic to flag on their own — ``time`` is imported
#: for the (harmless) ``time.sleep`` by many task bodies.
_BYTECODE_NEEDS_ATTR = frozenset({"time"})
_BYTECODE_TIME_ATTRS = frozenset(
    {"time", "time_ns", "monotonic", "perf_counter", "localtime", "gmtime", "ctime"}
)


# -- name resolution --------------------------------------------------------

_UNRESOLVED = object()


def _canonical_root_obj(obj: Any) -> Optional[str]:
    """Canonical dotted prefix for a resolved root object."""
    if isinstance(obj, ModuleType):
        return obj.__name__
    qualname = getattr(obj, "__qualname__", None) or getattr(obj, "__name__", None)
    if qualname is None:
        return None
    module = getattr(obj, "__module__", None)
    if module == "_io":
        # CPython's C implementation of ``io``: builtin ``open`` reports it
        module = "io"
    full = qualname if module in (None, "builtins") else f"{module}.{qualname}"
    # numpy's legacy global RNG surface lives on a hidden RandomState
    # singleton in numpy.random.mtrand — normalize to the public path
    full = full.replace("numpy.random.mtrand.RandomState.", "numpy.random.")
    return full.replace("numpy.random.mtrand.", "numpy.random.")


class DynamicResolver:
    """Resolve root names of a *live* function through globals + closure."""

    def __init__(self, fn: Callable[..., Any]):
        self._names: Dict[str, Any] = dict(vars(builtins))
        self._names.update(getattr(fn, "__globals__", None) or {})
        code = getattr(fn, "__code__", None)
        closure = getattr(fn, "__closure__", None) or ()
        if code is not None and closure:
            for var, cell in zip(code.co_freevars, closure, strict=True):
                try:
                    self._names[var] = cell.cell_contents
                except ValueError:
                    pass  # empty cell: still being defined
    def canonical_root(self, name: str) -> Optional[str]:
        """Canonical dotted prefix for ``name``, or None if unresolvable."""
        if name not in self._names:
            return None
        return _canonical_root_obj(self._names[name])

    def is_module(self, name: str) -> bool:
        """True when ``name`` resolves to a module object."""
        return isinstance(self._names.get(name), ModuleType)

    def treats_as_captured(self, name: str) -> bool:
        """True when ``name`` is a captured *data value* (mutation hazard).

        Modules, classes, and callables are excluded: calling ``.append``
        on ``numpy`` is a function call, not captured-state mutation.
        """
        obj = self._names.get(name, _UNRESOLVED)
        if obj is _UNRESOLVED or isinstance(obj, ModuleType):
            return False
        return not (callable(obj) and hasattr(obj, "__name__"))


class StaticResolver:
    """Resolve root names through a module AST's import-alias table."""

    def __init__(self, tree: ast.Module, package: Sequence[str] = ()):
        self._table: Dict[str, str] = {}
        self._package = tuple(package)
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    local = alias.asname or alias.name.split(".", 1)[0]
                    target = alias.name if alias.asname else alias.name.split(".", 1)[0]
                    self._table[local] = target
            elif isinstance(node, ast.ImportFrom):
                base = self._from_base(node)
                if base is None:
                    continue
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    self._table[alias.asname or alias.name] = f"{base}.{alias.name}"

    def _from_base(self, node: ast.ImportFrom) -> Optional[str]:
        if not node.level:
            return node.module
        if not self._package or node.level > len(self._package):
            return None  # relative import with unknown package context
        parts = list(self._package[: len(self._package) - (node.level - 1)])
        if node.module:
            parts.append(node.module)
        return ".".join(parts)

    def canonical_root(self, name: str) -> Optional[str]:
        """Canonical dotted prefix for ``name`` (imports, then builtins)."""
        if name in self._table:
            return self._table[name]
        if hasattr(builtins, name):
            return name
        return None

    def is_module(self, name: str) -> bool:
        """True when ``name`` plausibly resolves to a module (any import)."""
        return name in self._table

    def treats_as_captured(self, name: str) -> bool:
        """True when mutating ``name`` is a captured-state hazard.

        Statically, an unbound name that is neither an import nor a builtin
        must come from the module (or an enclosing) scope — exactly the
        ambient state the replay contract forbids mutating.
        """
        return name not in self._table and not hasattr(builtins, name)


def _dotted(node: ast.AST) -> Optional[Tuple[str, List[str]]]:
    """Decompose ``a.b.c`` into ``("a", ["b", "c"])``; None if not a chain."""
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if not isinstance(cur, ast.Name):
        return None
    parts.reverse()
    return cur.id, parts


def _canonical(resolver: Any, node: ast.AST, local_names: Set[str]) -> Optional[str]:
    """Canonical dotted name for an expression, or None."""
    decomposed = _dotted(node)
    if decomposed is None:
        return None
    root, rest = decomposed
    if root in local_names:
        return None  # rebound locally: not the imported thing anymore
    prefix = resolver.canonical_root(root)
    if prefix is None:
        return None
    # a from-import of datetime's class: "datetime.datetime" + ["now"]
    return ".".join([prefix, *rest]) if rest else prefix


# -- the detector engine ----------------------------------------------------


class _FunctionChecker:
    """Run every RS detector over one function's AST."""

    def __init__(
        self,
        resolver: Any,
        qualname: str,
        path: str = "",
        src_lines: Optional[Sequence[str]] = None,
        line_offset: int = 0,
    ):
        self._resolver = resolver
        self._qualname = qualname
        self._path = path
        self._src_lines = src_lines or []
        self._line_offset = line_offset
        self.findings: List[Finding] = []
        self._flagged: Set[Tuple[str, int, str]] = set()  # (code, line, msg) dedupe
        self._local_imports: Dict[str, str] = {}  # in-function import aliases

    # -- helpers ------------------------------------------------------------
    def _snippet(self, node: ast.AST) -> str:
        lineno = getattr(node, "lineno", 0)
        if 1 <= lineno <= len(self._src_lines):
            return self._src_lines[lineno - 1].strip()
        return ""

    def _emit(self, code: str, message: str, node: ast.AST) -> None:
        line = getattr(node, "lineno", 0) + self._line_offset
        if (code, line, message) in self._flagged:
            return
        self._flagged.add((code, line, message))
        self.findings.append(
            Finding(
                code=code,
                message=message,
                path=self._path,
                line=line,
                symbol=self._qualname,
                snippet=self._snippet(node),
            )
        )

    def _canon(self, node: ast.AST, visible: Set[str]) -> Optional[str]:
        """Canonical dotted name, consulting in-function imports first."""
        decomposed = _dotted(node)
        if decomposed is None:
            return None
        root, rest = decomposed
        prefix = self._local_imports.get(root)
        if prefix is not None:
            return ".".join([prefix, *rest]) if rest else prefix
        return _canonical(self._resolver, node, visible)

    @staticmethod
    def _scope_bindings(fn_node: ast.AST) -> Set[str]:
        """Names bound inside ``fn_node``'s own scope (nested defs excluded)."""
        bound: Set[str] = set()
        args = getattr(fn_node, "args", None)
        if args is not None:
            for a in [
                *args.posonlyargs,
                *args.args,
                *args.kwonlyargs,
                *([args.vararg] if args.vararg else []),
                *([args.kwarg] if args.kwarg else []),
            ]:
                bound.add(a.arg)

        def visit(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                    bound.add(child.name)
                    continue  # nested scope: its bindings are not ours
                if isinstance(child, ast.Lambda):
                    continue
                if isinstance(child, ast.Name) and isinstance(child.ctx, ast.Store):
                    bound.add(child.id)
                elif isinstance(child, ast.ExceptHandler) and child.name:
                    bound.add(child.name)
                elif isinstance(child, ast.alias):
                    bound.add((child.asname or child.name).split(".", 1)[0])
                elif isinstance(child, ast.comprehension):
                    # comprehension targets live in their own scope, but
                    # treating them as local only ever *suppresses* RS104
                    for n in ast.walk(child.target):
                        if isinstance(n, ast.Name):
                            bound.add(n.id)
                visit(child)

        body = getattr(fn_node, "body", None)
        if isinstance(body, list):
            for stmt in body:
                visit(stmt)
                if isinstance(stmt, ast.Name) and isinstance(stmt.ctx, ast.Store):
                    bound.add(stmt.id)
        elif body is not None:  # Lambda
            visit(body)
        return bound

    # -- entry --------------------------------------------------------------
    def check(self, fn_node: ast.AST) -> List[Finding]:
        """Check one function/lambda node; returns the findings."""
        self._walk_scope(fn_node, scope_stack=[], top=True)
        return self.findings

    def _walk_scope(
        self, fn_node: ast.AST, scope_stack: List[Set[str]], top: bool
    ) -> None:
        bound = self._scope_bindings(fn_node)
        stack = scope_stack + [bound]
        visible: Set[str] = set().union(*stack)
        globals_declared: Set[str] = set()
        escaping_nonlocals: Set[str] = set()

        body = getattr(fn_node, "body", None)
        stmts = body if isinstance(body, list) else [body]
        for stmt in stmts:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Global):
                    globals_declared.update(node.names)
                elif isinstance(node, ast.Nonlocal) and top:
                    # a top-level nonlocal reaches OUTSIDE the task function
                    escaping_nonlocals.update(node.names)

        escaping = globals_declared | escaping_nonlocals

        # function-local imports rebind a name *to a known module/symbol* —
        # resolvable for hazard tables even though the name is scope-bound
        imports = dict(self._local_imports)
        for stmt in stmts:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Import):
                    for alias in node.names:
                        if alias.asname:
                            imports[alias.asname] = alias.name
                        else:
                            imports[alias.name.split(".", 1)[0]] = alias.name.split(".", 1)[0]
                elif isinstance(node, ast.ImportFrom) and not node.level and node.module:
                    for alias in node.names:
                        if alias.name != "*":
                            imports[alias.asname or alias.name] = f"{node.module}.{alias.name}"

        def handle(node: ast.AST) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                self._walk_scope(node, stack, top=False)
                return
            self._check_node(node, visible, escaping)
            for child in ast.iter_child_nodes(node):
                handle(child)

        prev_imports = self._local_imports
        self._local_imports = imports
        try:
            for stmt in stmts:
                handle(stmt)
        finally:
            self._local_imports = prev_imports

    # -- per-node detectors --------------------------------------------------
    def _check_node(self, node: ast.AST, visible: Set[str], escaping: Set[str]) -> None:
        if isinstance(node, ast.Call):
            self._check_call(node, visible)
        if isinstance(node, (ast.Attribute, ast.Subscript)):
            target = node.value if isinstance(node, ast.Subscript) else node
            canon = self._canon(target, visible)
            if canon in _AMBIENT_ATTRS:
                self._emit("RS103", f"ambient read of {canon}", node)
        if isinstance(node, (ast.For, ast.AsyncFor)):
            self._check_set_iter(node.iter, visible)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp)):
            for gen in node.generators:
                self._check_set_iter(gen.iter, visible)
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            self._check_assign(node, visible, escaping)
        if isinstance(node, ast.Delete):
            for tgt in node.targets:
                self._check_mutation_target(tgt, visible, "del on")

    def _check_call(self, node: ast.Call, visible: Set[str]) -> None:
        canon = self._canon(node.func, visible)
        if canon is None:
            self._check_method_mutation(node, visible)
            return
        if canon in _WALL_CLOCK:
            self._emit("RS101", f"clock read via {canon}()", node)
        elif canon in _UNSEEDED_RNG:
            self._emit("RS102", f"unseeded RNG call {canon}()", node)
        elif canon in _SEEDED_RNG_FACTORIES and not node.args and not node.keywords:
            self._emit(
                "RS102",
                f"{canon}() without a seed falls back to OS entropy — pass "
                "an explicit seed derived from the context",
                node,
            )
        elif canon in _AMBIENT_IO or canon.startswith(_AMBIENT_IO_PREFIXES):
            self._emit("RS103", f"ambient I/O call {canon}()", node)

    def _check_method_mutation(self, node: ast.Call, visible: Set[str]) -> None:
        func = node.func
        if not isinstance(func, ast.Attribute) or func.attr not in _MUTATING_METHODS:
            return
        receiver = func.value
        if (
            isinstance(receiver, ast.Name)
            and receiver.id not in visible
            and self._resolver.treats_as_captured(receiver.id)
        ):
            self._emit(
                "RS104",
                f"mutates captured state: {receiver.id}.{func.attr}(...) on a "
                "name the task does not bind",
                node,
            )

    def _check_set_iter(self, iter_node: ast.AST, visible: Set[str]) -> None:
        is_set = isinstance(iter_node, (ast.Set, ast.SetComp))
        if isinstance(iter_node, ast.Call):
            canon = self._canon(iter_node.func, visible)
            is_set = canon in ("set", "frozenset")
        if is_set:
            self._emit(
                "RS105",
                "iterates an unordered set — per-process hash salting makes "
                "the order (and anything built from it) replay-unstable; "
                "sort it first",
                iter_node,
            )

    def _check_assign(self, node: ast.AST, visible: Set[str], escaping: Set[str]) -> None:
        targets = (
            node.targets
            if isinstance(node, ast.Assign)
            else [node.target]  # AugAssign | AnnAssign
        )
        for tgt in targets:
            if isinstance(tgt, ast.Name) and tgt.id in escaping:
                self._emit(
                    "RS104",
                    f"writes escaping state: {tgt.id} is declared "
                    "global/nonlocal — cross-call state breaks replay",
                    node,
                )
            else:
                self._check_mutation_target(tgt, visible, "assignment through")

    def _check_mutation_target(self, tgt: ast.AST, visible: Set[str], verb: str) -> None:
        base: Optional[ast.AST] = None
        if isinstance(tgt, (ast.Subscript, ast.Attribute)):
            base = tgt.value
        if (
            isinstance(base, ast.Name)
            and base.id not in visible
            and (
                self._resolver.treats_as_captured(base.id)
                # setting an attribute ON a module is global-state mutation
                or (isinstance(tgt, ast.Attribute) and self._resolver.is_module(base.id))
            )
        ):
            self._emit(
                "RS104",
                f"mutates captured state: {verb} {base.id} — a name the "
                "task does not bind",
                tgt,
            )


# -- bytecode fallback ------------------------------------------------------


def _code_names(code: CodeType, seen: Set[int]) -> Set[str]:
    if id(code) in seen:
        return set()
    seen.add(id(code))
    names = set(code.co_names)
    for const in code.co_consts:
        if isinstance(const, CodeType):
            names |= _code_names(const, seen)
    return names


def _bytecode_findings(fn: Callable[..., Any], qualname: str) -> List[Finding]:
    """Heuristic scan of ``co_names`` when source is unavailable.

    The same degradation path :func:`repro.core.graph.fn_digest` takes:
    structural code-object inspection instead of source. Matches are
    *possible* hazards only — the names prove the function touches a
    suspicious module, not which attribute it reads.
    """
    code = getattr(fn, "__code__", None)
    if code is None:
        return []
    names = _code_names(code, set())
    hits = sorted(
        n
        for n in names & _BYTECODE_SUSPECTS
        if n not in _BYTECODE_NEEDS_ATTR or names & _BYTECODE_TIME_ATTRS
    )
    if not hits:
        return []
    return [
        Finding(
            code="RS900",
            message=(
                "possible determinism hazard (source unavailable; bytecode "
                f"references: {', '.join(hits)})"
            ),
            line=code.co_firstlineno,
            symbol=qualname,
        )
    ]


# -- public entry points ----------------------------------------------------


def _find_target_node(tree: ast.Module, fn: Callable[..., Any]) -> Optional[ast.AST]:
    """The def/lambda node in ``tree`` matching the live callable ``fn``."""
    name = getattr(fn, "__name__", "")
    if name == "<lambda>":
        lambdas = [n for n in ast.walk(tree) if isinstance(n, ast.Lambda)]
        return lambdas[0] if lambdas else None
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node.name == name:
            return node
    return None


def check_callable(fn: Callable[..., Any], name: str = "") -> List[Finding]:
    """Replay-safety findings for one live callable (RS1xx, RS900).

    Resolves names through the function's real globals and closure, so
    aliased imports (``import numpy as anything``) and from-imports are
    seen through. Falls back to the RS900 bytecode heuristic when source
    is unavailable (builtins, REPL definitions, ``exec`` products).
    """
    target = fn
    while hasattr(target, "__wrapped__"):
        target = target.__wrapped__
    if not isinstance(target, FunctionType):
        return []  # builtins / callable instances: nothing to parse
    qualname = name or getattr(target, "__qualname__", "") or "<task>"
    try:
        src_lines, start_line = inspect.getsourcelines(target)
        src = textwrap.dedent("".join(src_lines))
        tree = ast.parse(src)
    except (OSError, TypeError, IndentationError, SyntaxError, ValueError):
        return _bytecode_findings(target, qualname)
    fn_node = _find_target_node(tree, target)
    if fn_node is None:
        return _bytecode_findings(target, qualname)
    path = ""
    try:
        path = inspect.getsourcefile(target) or ""
    except TypeError:
        pass
    checker = _FunctionChecker(
        DynamicResolver(target),
        qualname,
        path=path,
        src_lines=src.splitlines(),
        line_offset=start_line - 1,
    )
    return checker.check(fn_node)


def check_graph(graph: Any) -> List[Finding]:
    """Replay-safety findings for every callable task in a ``ContextGraph``.

    Registry-named tasks (string ``fn``) are skipped — their implementations
    live worker-side and are checked where they are defined.
    """
    findings: List[Finding] = []
    for node in getattr(graph, "nodes", {}).values():
        fn = getattr(node, "fn", None)
        if fn is None or isinstance(fn, str):
            continue
        findings.extend(check_callable(fn, name=f"{node.id}:{getattr(fn, '__name__', 'fn')}"))
    return findings


# -- static (file) mode -----------------------------------------------------


def _is_task_decorator(dec: ast.AST) -> bool:
    """True for ``@atomic_task`` / ``@something.task("id", ...)`` decorators."""
    if isinstance(dec, ast.Name) and dec.id == "atomic_task":
        return True
    if isinstance(dec, ast.Attribute) and dec.attr == "atomic_task":
        return True
    if isinstance(dec, ast.Call):
        func = dec.func
        if isinstance(func, ast.Attribute) and func.attr == "task":
            return True
        if isinstance(func, ast.Name) and func.id == "atomic_task":
            return True
    return False


def _task_nodes(tree: ast.Module) -> List[Tuple[str, ast.AST]]:
    """(qualname, node) for every statically identifiable task function.

    A function is a task if it is decorated ``@atomic_task`` or
    ``@graph.task(...)``, or passed (by name, lambda, or def) as the ``fn``
    argument of an ``.add(...)`` / ``.add_stream(...)`` call.
    """
    defs: Dict[str, ast.AST] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, node)
    tasks: List[Tuple[str, ast.AST]] = []
    seen: Set[int] = set()

    def take(name: str, node: ast.AST) -> None:
        if id(node) not in seen:
            seen.add(id(node))
            tasks.append((name, node))

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_is_task_decorator(d) for d in node.decorator_list):
                take(node.name, node)
        elif isinstance(node, ast.Call):
            func = node.func
            if not isinstance(func, ast.Attribute) or func.attr not in ("add", "add_stream"):
                continue
            candidates: List[ast.AST] = list(node.args[1:2])
            candidates += [kw.value for kw in node.keywords if kw.arg == "fn"]
            for cand in candidates:
                if isinstance(cand, ast.Lambda):
                    take("<lambda>", cand)
                elif isinstance(cand, ast.Name) and cand.id in defs:
                    take(cand.id, defs[cand.id])
    return tasks


def check_source_tasks(
    text: str, path: str = "", package: Sequence[str] = ()
) -> List[Finding]:
    """Replay-safety findings for the task functions of one source file.

    Only statically identifiable task functions are checked (see
    :func:`_task_nodes`) — framework/helper code in the same file is the
    INV detectors' jurisdiction, not RS's.
    """
    try:
        tree = ast.parse(text)
    except SyntaxError:
        return []  # the CLI reports parse failures separately (E999)
    resolver = StaticResolver(tree, package=package)
    src_lines = text.splitlines()
    findings: List[Finding] = []
    for qualname, node in _task_nodes(tree):
        checker = _FunctionChecker(resolver, qualname, path=path, src_lines=src_lines)
        findings.extend(checker.check(node))
    return findings
