"""Distributed tracing: spans propagated as Ψ context facts.

The trace contract (docs/observability.md) in three invariants:

1. **Propagation is the context.** A span crossing a process boundary is
   carried as one reserved fact under :data:`TRACE_KEY` inside the same
   ``Context`` that already travels in every task submission — the worker
   transports (in-process and HTTP) and ``ShardedGateway`` handoffs
   forward it untouched, so no wire format changes.
2. **Tracing never changes replay identity.** ``obs.``-prefixed facts are
   excluded from ``Context.digest()`` and injected with lamport 0, so a
   traced run commits byte-identical digests to an untraced one, and the
   fact is only stamped on the transient submit-time context — it is never
   stored into a node's output context.
3. **Replays are silent.** Call sites start spans only after the
   replay/cache probes miss; stages that turn out replayed call
   :meth:`Tracer.discard`. A replayed run therefore emits zero spans.

The tracer is a process-global singleton that is toggled, never replaced:
hot call sites cache ``get_tracer()`` once and guard with a single
``tracer.enabled`` attribute read, which is the entire disabled-mode cost.

Once JAX is loaded, three things tie spans to the device:

* every span is also a ``jax.profiler.TraceAnnotation`` of its name, open
  from :meth:`Tracer.start_span` to :meth:`Tracer.end` (or
  :meth:`Tracer.discard`), so a profiler trace's host plane carries the
  program's spans on the profiler's own clock, even when a span ends on
  another thread;
* a current span (a ``contextvars`` variable) lets code inside a node or a
  task open child spans without being handed a parent;
* per-thread counters of JAX lowerings and executables obtained
  (:func:`jax_counts`) count while tracing is on, and a span that ends on
  the thread that opened it records their deltas as ``jax_lowerings`` and
  ``jax_compiles``.

This module never imports JAX: it uses it only once something else has.
"""

from __future__ import annotations

import contextvars
import sys
import threading
import time
import uuid
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, ContextManager, Dict, Iterator, List, Optional, Tuple

if TYPE_CHECKING:  # context imports are deferred to call time: this module
    # is imported by repro.core itself (gateway, server, executor), so an
    # eager import here would re-enter repro.core mid-initialization
    from repro.core.context import Context

#: The reserved context key carrying trace identity across process hops
#: (under ``repro.core.context.OBS_KEY_PREFIX``, the digest-excluded
#: namespace).
TRACE_KEY = "obs.trace"

#: Origin stamped on injected trace facts (never a worker identity).
TRACE_ORIGIN = "ψ.obs"

#: ``jax.monitoring`` events counted per thread: a jaxpr lowered to MLIR,
#: and an executable obtained (compiled, or read from the persistent cache)
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_CURRENT: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "repro_obs_current_span", default=None
)
_COUNTS = threading.local()
_NO_SCOPE = nullcontext()


def _new_id() -> str:
    """A fresh 16-hex span/trace id."""
    return uuid.uuid4().hex[:16]


@dataclass
class Span:
    """One timed operation in a trace.

    ``start_wall`` is an epoch timestamp so spans correlate with journal
    record ``wall_time``; duration is measured on the monotonic clock
    (``_t0``) so it is immune to wall-clock steps.
    """

    name: str
    trace_id: str
    span_id: str
    parent_id: str = ""
    kind: str = "internal"  # run | node | rpc | task | stream | handoff | ...
    start_wall: float = 0.0
    dur_s: float = 0.0
    status: str = "ok"
    attrs: Dict[str, Any] = field(default_factory=dict)
    _t0: float = 0.0
    _thread: int = 0  # the thread that opened the span
    _counts: Tuple[int, int] = (0, 0)  # that thread's jax_counts() at open
    _annotation: Any = None  # the open TraceAnnotation, while JAX is loaded

    def to_obj(self) -> Dict[str, Any]:
        """The JSON-serializable wire/sink form of this span."""
        return {
            "name": self.name,
            "trace": self.trace_id,
            "span": self.span_id,
            "parent": self.parent_id,
            "kind": self.kind,
            "ts": self.start_wall,
            "dur": self.dur_s,
            "status": self.status,
            "attrs": dict(self.attrs),
        }


class Tracer:
    """Process-global span factory and sink fan-out.

    Disabled by default. Call sites hold the singleton (:func:`get_tracer`)
    and check :attr:`enabled` before building spans; :meth:`configure`
    mutates the flag and sink list in place so cached references stay
    valid. All sink emission happens at :meth:`end` time.
    """

    def __init__(self) -> None:
        self.enabled = False
        self._sinks: List[Any] = []
        self._lock = threading.Lock()
        self._annotate: Any = None  # jax.profiler.TraceAnnotation once hooked

    # -- lifecycle ----------------------------------------------------------
    def configure(self, *, enabled: Optional[bool] = None) -> None:
        """Toggle tracing; ``None`` leaves the flag unchanged."""
        if enabled is not None:
            self.enabled = bool(enabled)
            if self.enabled:
                self._hook_jax()

    def _hook_jax(self) -> None:
        """Annotate spans and count lowerings, if JAX is loaded (once)."""
        if self._annotate is not None:
            return
        jax = sys.modules.get("jax")
        if jax is None:
            return
        try:
            annotate = jax.profiler.TraceAnnotation
            register = jax.monitoring.register_event_duration_secs_listener
        except AttributeError:  # still importing
            return
        with self._lock:
            if self._annotate is None:
                register(_count_jax_event)
                self._annotate = annotate

    def add_sink(self, sink: Any) -> None:
        """Attach ``sink`` (any object with ``emit(span_obj)``)."""
        with self._lock:
            if sink not in self._sinks:
                self._sinks.append(sink)

    def remove_sink(self, sink: Any) -> None:
        """Detach ``sink``; unknown sinks are ignored."""
        with self._lock:
            if sink in self._sinks:
                self._sinks.remove(sink)

    @contextmanager
    def attached(self, sink: Any, *, enable: bool = True) -> Iterator[Any]:
        """Attach ``sink`` (optionally enabling tracing) for a scope.

        Restores the previous enabled flag and detaches the sink on exit —
        the standard harness for tests and for ``Client.run(trace=True)``.
        """
        prev = self.enabled
        self.add_sink(sink)
        if enable:
            self.configure(enabled=True)
        try:
            yield sink
        finally:
            self.enabled = prev
            self.remove_sink(sink)

    # -- span construction --------------------------------------------------
    def start_span(
        self,
        name: str,
        *,
        parent: Optional[Span] = None,
        trace_id: str = "",
        parent_id: str = "",
        kind: str = "internal",
        attrs: Optional[Dict[str, Any]] = None,
    ) -> Span:
        """Open a span. Parentage comes from ``parent`` or explicit ids.

        With neither, the span is a child of the current span (see
        :meth:`use`), or else roots a brand-new trace.
        """
        if parent is None and not trace_id and not parent_id:
            parent = _CURRENT.get()
        if parent is not None:
            trace_id = parent.trace_id
            parent_id = parent.span_id
        if self._annotate is None:
            self._hook_jax()
        span = Span(
            name=name,
            trace_id=trace_id or _new_id(),
            span_id=_new_id(),
            parent_id=parent_id,
            kind=kind,
            start_wall=time.time(),  # record timestamp
            attrs=dict(attrs or {}),
            _t0=time.monotonic(),
            _thread=threading.get_ident(),
            _counts=jax_counts(),
        )
        if self._annotate is not None:
            span._annotation = self._annotate(name)
            span._annotation.__enter__()
        return span

    def end(
        self,
        span: Span,
        *,
        status: str = "ok",
        attrs: Optional[Dict[str, Any]] = None,
    ) -> Span:
        """Close ``span`` and emit it to every attached sink."""
        span.dur_s = max(0.0, time.monotonic() - span._t0)
        _close_annotation(span)
        span.status = status
        if attrs:
            span.attrs.update(attrs)
        if self._annotate is not None and span._thread == threading.get_ident():
            lowerings, compiles = jax_counts()
            span.attrs["jax_lowerings"] = lowerings - span._counts[0]
            span.attrs["jax_compiles"] = compiles - span._counts[1]
        obj = span.to_obj()
        with self._lock:
            sinks = list(self._sinks)
        for sink in sinks:
            try:
                sink.emit(obj)
            except Exception:  # a broken sink must never fail the run
                pass
        return span

    def discard(self, span: Span) -> None:
        """Drop a started span without emitting — the work was replayed."""
        _close_annotation(span)

    def use(self, span: Optional[Span]) -> ContextManager[Optional[Span]]:
        """Make ``span`` the current span for a scope (``None``: a no-op).

        Spans opened in the scope without a parent become its children.
        The scope neither opens nor ends ``span``.
        """
        return _NO_SCOPE if span is None else _Scope(self, span, owned=False)

    def span(
        self,
        name: str,
        *,
        parent: Optional[Span] = None,
        trace_id: str = "",
        parent_id: str = "",
        kind: str = "internal",
        attrs: Optional[Dict[str, Any]] = None,
    ) -> ContextManager[Optional[Span]]:
        """Context-managed span: ends ``ok`` on exit, ``error`` on raise.

        The span is the current span inside the block. Yields ``None`` (and
        does nothing) when tracing is disabled.
        """
        if not self.enabled:
            return _NO_SCOPE
        sp = self.start_span(
            name, parent=parent, trace_id=trace_id, parent_id=parent_id, kind=kind, attrs=attrs
        )
        return _Scope(self, sp, owned=True)


class _Scope:
    """The body of :meth:`Tracer.use` and :meth:`Tracer.span`."""

    __slots__ = ("tracer", "span", "owned", "_token")

    def __init__(self, tracer: Tracer, span: Span, *, owned: bool) -> None:
        self.tracer = tracer
        self.span = span
        self.owned = owned

    def __enter__(self) -> Span:
        self._token = _CURRENT.set(self.span)
        return self.span

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> bool:
        _CURRENT.reset(self._token)
        if self.owned:
            self.tracer.end(self.span, status="ok" if exc_type is None else "error")
        return False


def _close_annotation(span: Span) -> None:
    annotation, span._annotation = span._annotation, None
    if annotation is not None:
        annotation.__exit__(None, None, None)


def current_span() -> Optional[Span]:
    """The current span of this thread (or task), if any."""
    return _CURRENT.get()


def jax_counts() -> Tuple[int, int]:
    """``(lowerings, executables obtained)`` on this thread while tracing was on."""
    return getattr(_COUNTS, "lowerings", 0), getattr(_COUNTS, "compiles", 0)


def _count_jax_event(event: str, duration_secs: float, **kw: Any) -> None:
    if not _TRACER.enabled:
        return
    if event == LOWERING_EVENT:
        _COUNTS.lowerings = getattr(_COUNTS, "lowerings", 0) + 1
    elif event == COMPILE_EVENT:
        _COUNTS.compiles = getattr(_COUNTS, "compiles", 0) + 1


_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-global singleton tracer (stable — cache it freely)."""
    return _TRACER


# -- context propagation ----------------------------------------------------


def inject_trace(ctx: "Context", span: Span) -> "Context":
    """Stamp ``span``'s identity onto ``ctx`` as a transient Ψ fact.

    The fact uses lamport 0 so ``ctx.max_lamport()`` — and therefore the
    lamport (and digest) of every later real fact — is identical between
    traced and untraced runs. Any previous trace fact is replaced, never
    accumulated. The returned context is for the wire only; callers keep
    threading the *original* ``ctx`` into commit/output paths.
    """
    from repro.core.context import Context, ContextEntry

    entries = [e for e in ctx if e.key != TRACE_KEY]
    entries.append(
        ContextEntry.make(TRACE_KEY, {"t": span.trace_id, "s": span.span_id}, TRACE_ORIGIN, 0)
    )
    return Context(entries)


def extract_trace(ctx: "Context") -> Optional[Tuple[str, str]]:
    """Read ``(trace_id, parent_span_id)`` off ``ctx``, or ``None``."""
    raw = ctx.get(TRACE_KEY)
    if not isinstance(raw, dict):
        return None
    trace_id = str(raw.get("t", ""))
    span_id = str(raw.get("s", ""))
    if not trace_id or not span_id:
        return None
    return trace_id, span_id


def strip_trace(ctx: "Context") -> "Context":
    """Drop any trace fact from ``ctx`` (used before storing output ξ)."""
    from repro.core.context import Context

    if ctx.get(TRACE_KEY) is None:
        return ctx
    return Context([e for e in ctx if e.key != TRACE_KEY])


__all__ = [
    "TRACE_KEY",
    "TRACE_ORIGIN",
    "Span",
    "Tracer",
    "current_span",
    "extract_trace",
    "get_tracer",
    "inject_trace",
    "jax_counts",
    "strip_trace",
]
