"""Executors: run a ContextGraph durably, locally or through a Gateway.

Execution semantics (the paper's logical flow, §4):
  1. contract SCCs → union nodes (DAG guarantee),
  2. propagate ξ per the union rules,
  3. execute nodes in dependency order with dependency-injected inputs,
  4. journal every commit; replay skips nodes whose (id, ξ-digest, input-digest)
     already committed — durable, effectively-once execution.

Union nodes execute their members as ONE atomic unit (single commit), in
deterministic member order, with intra-group outputs injected among members.

``LocalExecutor`` runs tasks on a thread pool with dependency-counted
readiness (maximum overlap). ``ClusterExecutor`` dispatches named tasks
through a Gateway to remote/in-proc workers with the same barrier-free
dependency-counted readiness, event-driven completion consumption, global
straggler speculation, and requeue-on-eviction fault tolerance (first
commit wins — duplicates are idempotent by replay). The full dispatch/
readiness/eviction/speculation state machine is specified in
docs/distributed-execution.md.

Both executors optionally consult a cross-run ``repro.cache.ResultCache``
(keyed by fn/input/context digests) after the replay oracle and before any
execution or dispatch; hits and stores are journaled as ``CACHE_HIT`` /
``CACHE_STORE`` records so cache-accelerated runs stay fully replayable.
See docs/result-cache.md for the cache/journal contract.

Nodes declared with ``stream=`` ("source" / "map" / "reduce") execute as
*pipelined stream stages* on dedicated threads: consumers start on the
producer's first chunk, chunks flow through bounded backpressured channels
(``repro.stream``), every chunk is journaled as a ``CHUNK_COMMIT`` before
it becomes visible downstream, and a killed run resumes producers from
their last committed offset. A dependency edge INTO a stream consumer from
its stream producer is satisfied when the producer *starts*; every other
edge keeps batch semantics (satisfied at commit). See docs/streaming.md.
"""

from __future__ import annotations

import inspect
import itertools
import threading
import time
from collections import deque
from concurrent.futures import FIRST_COMPLETED, Future, ThreadPoolExecutor, wait
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Mapping, Optional, Tuple

from repro.cache import CacheKey, CachedResult, ResultCache
from repro.obs.trace import get_tracer, inject_trace
from repro.wire import unwrap_digested
from repro.stream import (
    ChannelClosed,
    ChunkLog,
    StreamCancelled,
    StreamHandle,
    StreamPlan,
    plan_streams,
    reduce_iter,
    run_map_stage,
    run_source_stage,
    stream_input_marker,
)

from .context import Context
from .durable import (
    Interrupted,
    Journal,
    JournalRecord,
    ReplayCache,
    encode_payload,
    payload_digest,
)
from .failure import RetryPolicy, StragglerWatch
from .gateway import Gateway, TaskCancelled
from .graph import ContextGraph, Node, UnionNode

__all__ = ["WithContext", "ExecutionReport", "LocalExecutor", "ClusterExecutor"]

_INLINE_LIMIT = 1 << 20  # 1 MiB: larger outputs must go through the spill store

_RUN_TOKENS = itertools.count()  # distinguishes concurrent runs on one gateway


@dataclass
class WithContext:
    """Task return wrapper: ``return WithContext(out, {"fact": 1})`` emits facts."""

    output: Any
    facts: Mapping[str, Any]


@dataclass
class ExecutionReport:
    """What a run did: outputs/contexts per node, and how each node resolved.

    Every exec node lands in exactly one of ``replayed`` (this journal
    already committed it — for stream nodes: every chunk AND the EOS came
    from the journal), ``cached`` (answered by the cross-run result cache),
    or ``executed`` (actually ran, possibly resuming a committed prefix).
    """

    outputs: Dict[str, Any]
    contexts: Dict[str, Context]
    replayed: Tuple[str, ...]
    executed: Tuple[str, ...]
    wall_s: float
    cached: Tuple[str, ...] = ()
    suspended: bool = False  # a named interrupt point suspended the run
    interrupt: str = ""  # name of the interrupt that suspended it
    interrupt_node: str = ""  # node that raised the interrupt
    frontier: Tuple[str, ...] = ()  # exec nodes still pending at suspension


def _accepts_start(fn: Callable[..., Any]) -> bool:
    """True iff ``fn`` declares an explicit ``start`` parameter.

    Only an explicit parameter counts — passing ``start`` into a bare
    ``**kwargs`` producer that ignores it would silently re-emit from 0 and
    corrupt chunk numbering, so those producers get the skip-side resume.
    """
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    return "start" in sig.parameters


class _BaseExecutor:
    """Shared durable-commit, replay-lookup, and result-cache machinery."""

    def __init__(
        self,
        journal: Optional[Journal] = None,
        retry: Optional[RetryPolicy] = None,
        cache: Optional[ResultCache] = None,
        spill_put: Optional[Callable[[str, Any], str]] = None,
        spill_get: Optional[Callable[[str], Any]] = None,
        channel_capacity: int = 8,
    ):
        self.journal = journal
        self.retry = retry or RetryPolicy()
        self.cache = cache
        self.replay = ReplayCache(journal) if journal is not None else ReplayCache()
        self.channel_capacity = channel_capacity
        self._spill_put = spill_put
        self._spill_get = spill_get

    # -- durable commit machinery -------------------------------------------
    def _commit(
        self,
        node_id: str,
        ctx_digest: str,
        in_digest: str,
        output: Any,
        attempt: int,
        meta: Optional[dict] = None,
        volatile: bool = False,
        expected: Optional[str] = None,
        deps: Optional[Iterable[str]] = None,
    ) -> None:
        """Journal one NODE_COMMIT and index it for replay.

        ``volatile`` commits carry only the output *digest* (``payload=None``
        — tensors never enter the journal); when ``expected`` is set (the
        digest a previous incarnation committed for the same identity), a
        disagreeing re-execution is surfaced as a hard non-determinism error
        before anything downstream can consume the divergent value.
        ``deps`` (the node's upstream ids) are recorded in ``meta`` for the
        lineage index (repro.journal.lineage) — provenance annotations the
        replay oracle itself ignores.
        """
        if deps:
            meta = {**(meta or {}), "deps": sorted(set(deps))}
        payload, ref = output, ""
        if self._spill_put is not None and not volatile:
            try:
                approx = payload_digest(output)  # also probes serializability
                del approx
            except Exception:
                ref = self._spill_put(node_id, output)
                payload = None
        out_digest = payload_digest(output) if ref == "" else ref
        if volatile:
            if expected is not None and expected != out_digest:
                raise RuntimeError(
                    f"non-deterministic re-execution at node {node_id!r}: "
                    f"journal={expected} recomputed={out_digest}"
                )
            payload = None
            meta = {**(meta or {}), "volatile": True}
        rec = JournalRecord(
            kind="NODE_COMMIT",
            node_id=node_id,
            context_digest=ctx_digest,
            input_digest=in_digest,
            output_digest=out_digest,
            payload=payload if ref == "" else None,
            ref=ref,
            attempt=attempt,
            meta=meta or {},
        )
        if self.journal is not None:
            self.journal.append(rec)
        self.replay.record(rec)

    @staticmethod
    def _readiness(
        exec_nodes: Mapping[str, Any],
        member_to_group: Mapping[str, str],
    ):
        """Dependency-counted scheduling state shared by both executors:
        (gdeps, deps_left, children)."""
        gdeps = ContextGraph.group_deps(exec_nodes, member_to_group)
        deps_left = {nid: len(gdeps[nid]) for nid in exec_nodes}
        children: Dict[str, List[str]] = {nid: [] for nid in exec_nodes}
        for nid in exec_nodes:
            for d in gdeps[nid]:
                children[d].append(nid)
        return gdeps, deps_left, children

    # -- cross-run result cache (repro.cache; docs/result-cache.md) ----------
    def _cache_key(
        self,
        node: "Node | UnionNode",
        ctx_digest: str,
        in_digest: str,
    ) -> Optional[CacheKey]:
        """Content-addressed key for this (fn, inputs, ξ) — None when uncached.

        Stream nodes never use the cross-run cache (chunk-granular replay
        supersedes it — docs/streaming.md §4.3); volatile nodes never do
        either (their outputs are transient tensors kept out of every store).
        """
        if self.cache is None or getattr(node, "stream", "") or getattr(node, "volatile", False):
            return None
        return CacheKey(fn=node.fn_digest(), inputs=in_digest, context=ctx_digest)

    def _cache_probe(
        self,
        node_id: str,
        key: Optional[CacheKey],
        ctx_digest: str,
        in_digest: str,
        deps: Optional[Iterable[str]] = None,
    ) -> Optional[CachedResult]:
        """Consult the result cache; a hit journals CACHE_HIT + NODE_COMMIT.

        The commit carries the cached payload, so the journal of a
        cache-accelerated run replays standalone — auditability is never
        delegated to cache availability.
        """
        if key is None:
            return None
        ent = self.cache.get(key)
        if ent is None:
            return None
        if self.journal is not None:
            self.journal.append(
                JournalRecord(
                    kind="CACHE_HIT",
                    node_id=node_id,
                    context_digest=ctx_digest,
                    input_digest=in_digest,
                    output_digest=ent.output_digest,
                    meta={"key": key.id},
                )
            )
        meta: Dict[str, Any] = {"cache": key.id}
        if ent.facts:
            meta["facts"] = dict(ent.facts)
        self._commit(node_id, ctx_digest, in_digest, ent.value, 0, meta=meta, deps=deps)
        return ent

    def _cache_store(
        self,
        node_id: str,
        key: Optional[CacheKey],
        ctx_digest: str,
        in_digest: str,
        value: Any,
        facts: Optional[Mapping[str, Any]] = None,
    ) -> None:
        """Commit a freshly-executed result into the cache (journals CACHE_STORE).

        Uncacheable outputs (unserializable by the payload codec) are skipped
        without failing the run — the node simply stays cold.
        """
        if key is None:
            return
        try:
            ent = self.cache.put(key, value, facts=facts)
        except Exception:
            self.cache.stats["uncacheable"] += 1
            return
        if self.journal is not None:
            self.journal.append(
                JournalRecord(
                    kind="CACHE_STORE",
                    node_id=node_id,
                    context_digest=ctx_digest,
                    input_digest=in_digest,
                    output_digest=ent.output_digest,
                    meta={"key": key.id},
                )
            )

    def _lookup(
        self,
        node_id: str,
        ctx_digest: str,
        in_digest: str,
    ) -> "Optional[_Found]":
        """Replay oracle: the committed output for (node, ξ, inputs), if any.

        Stream-node commits carry no payload; their value materializes from
        the journaled chunk sequence (docs/streaming.md §4.2). Volatile
        commits also carry no payload — they answer with a *verify-only*
        hit (``reexecute=True``): the caller must re-execute the node and
        check the fresh digest against ``expected``.
        """
        rec = self.replay.lookup(node_id, ctx_digest, in_digest)
        if rec is None:
            return None
        facts = rec.meta.get("facts")
        if rec.meta.get("volatile"):
            return _Found(None, facts, reexecute=True, expected=rec.output_digest)
        if rec.meta.get("stream") is not None:
            chunks = self.replay.stream_chunks(node_id, ctx_digest, in_digest)
            return _Found([c.payload for c in chunks], facts)
        if rec.ref:
            if self._spill_get is None:
                return None  # cannot resolve; re-execute
            return _Found(self._spill_get(rec.ref), facts)
        return _Found(rec.payload, facts)

    # -- stream-stage plumbing shared by both executors ----------------------
    def _stream_stage_inputs(
        self,
        node: Node,
        splan: StreamPlan,
        outputs: Mapping[str, Any],
        member_to_group: Mapping[str, str],
        stream_identity: Mapping[str, Tuple[str, str]],
    ) -> Tuple[Dict[str, Any], Dict[str, Any], Optional[str], Optional[str]]:
        """Split a stream node's deps into injectable values vs. the stream.

        Returns ``(fn_inputs, digest_inputs, stream_kwarg, stream_dep_gid)``:
        ``fn_inputs`` are the batch inputs actually passed to ``fn``;
        ``digest_inputs`` additionally carry the stream-identity marker under
        the stream kwarg, making the node's input digest replay-stable
        without hashing unbounded chunk data.
        """
        sdep = splan.stream_dep.get(node.id)
        fn_inputs: Dict[str, Any] = {}
        digest_inputs: Dict[str, Any] = {}
        stream_kwarg: Optional[str] = None
        for dep in node.deps:
            gid = member_to_group.get(dep, dep)
            kwarg = node.kwarg_for(dep)
            if gid == sdep:
                stream_kwarg = kwarg
                up_ctx_d, up_in_d = stream_identity[gid]
                digest_inputs[kwarg] = stream_input_marker(gid, up_ctx_d, up_in_d)
                continue
            out = outputs[gid]
            if gid != dep and isinstance(out, Mapping) and dep in out:
                out = out[dep]  # a specific member of a union node
            fn_inputs[kwarg] = out
            digest_inputs[kwarg] = out
        return fn_inputs, digest_inputs, stream_kwarg, sdep

    def _journal_suspend(
        self,
        suspend: Mapping[str, Interrupted],
        frontier: Tuple[str, ...],
        nodes: Optional[Mapping[str, Any]] = None,
    ) -> None:
        """Journal one SUSPEND per interrupted node; the run ends WITHOUT RUN_END.

        The frontier (exec nodes without a committed output) is recorded so a
        resume can audit what remained; an unserializable interrupt payload
        degrades to its repr rather than failing the suspension itself.

        A node declaring ``interrupt_timeout_s`` stamps its SUSPEND with the
        *absolute* answer deadline plus the on-timeout policy and (for the
        ``"default"`` policy) the journaled default answer — the deadline is
        resolved to wall time HERE, at suspension, so replaying the journal
        later reaches the identical timeout verdict (docs/durable-workflows.md).
        """
        if self.journal is None:
            return
        for nid, exc in suspend.items():
            meta: Dict[str, Any] = {"interrupt": exc.name, "frontier": list(frontier)}
            if exc.payload is not None:
                try:
                    encode_payload(exc.payload)  # probes wire serializability
                    meta["payload"] = exc.payload
                except Exception:
                    meta["payload_repr"] = repr(exc.payload)
            node = (nodes or {}).get(nid)
            timeout_s = getattr(node, "interrupt_timeout_s", None)
            if timeout_s is not None:
                meta["timeout_s"] = float(timeout_s)
                # an absolute wall deadline survives process restarts;
                # record timestamp: journaled for cross-process expiry
                meta["deadline"] = time.time() + float(timeout_s)
                policy = getattr(node, "interrupt_on_timeout", "") or "escalate"
                if policy == "default":
                    default = getattr(node, "interrupt_default", None)
                    try:
                        encode_payload(default)  # probes wire serializability
                        meta["default"] = default
                    except Exception:
                        # an unjournalable auto-answer cannot replay
                        # deterministically — degrade to escalation
                        policy = "escalate"
                meta["on_timeout"] = policy
            self.journal.append(JournalRecord(kind="SUSPEND", node_id=nid, meta=meta))
        self.journal.flush()

    def _journal_stream_start(
        self,
        nid: str,
        kind: str,
        ctx_digest: str,
        in_digest: str,
        resume_seq: int,
    ) -> None:
        """NODE_START for a stream stage, annotated with the resume offset."""
        if self.journal is not None:
            self.journal.append(
                JournalRecord(
                    kind="NODE_START",
                    node_id=nid,
                    context_digest=ctx_digest,
                    input_digest=in_digest,
                    meta={"stream": kind, "resume_seq": resume_seq},
                )
            )


@dataclass
class _Found:
    value: Any
    facts: Optional[Mapping[str, Any]] = None  # journaled WithContext facts
    reexecute: bool = False  # volatile hit: no payload — run again and verify
    expected: Optional[str] = None  # the digest the re-execution must match


def _inject_inputs(
    node: Node,
    outputs: Mapping[str, Any],
    member_to_group: Mapping[str, str],
) -> Dict[str, Any]:
    """Dependency injection: map each dep's output to the node's kwarg."""
    inputs: Dict[str, Any] = {}
    for dep in node.deps:
        gid = member_to_group.get(dep, dep)
        out = outputs[gid]
        if gid != dep and isinstance(out, Mapping) and dep in out:
            out = out[dep]  # a specific member of a union node
        inputs[node.kwarg_for(dep)] = out
    return inputs


class LocalExecutor(_BaseExecutor):
    """In-process threaded executor with dependency-counted scheduling.

    Batch nodes run on a bounded thread pool; stream stages run on
    dedicated threads (they live as long as their stream and block on
    channel backpressure, so parking them in the pool could starve it).
    """

    def __init__(self, max_workers: int = 8, **kw):
        super().__init__(**kw)
        self.max_workers = max_workers

    def run(
        self,
        graph: ContextGraph,
        run_meta: Optional[Mapping[str, Any]] = None,
    ) -> ExecutionReport:
        """Execute ``graph`` on the thread pool; returns the run's report.

        ``run_meta`` is merged into the RUN_START record (e.g. a workflow id).
        A node raising :class:`Interrupted` suspends the run: launched work
        drains to commit, nothing new starts, SUSPEND records are journaled
        with the pending frontier, and the report comes back with
        ``suspended=True`` instead of an exception.
        """
        t0 = time.monotonic()  # wall_s is a duration: clock steps must not skew it
        tracer = get_tracer()
        run_span = (
            tracer.start_span(f"run:{graph.name}", kind="run", attrs={"graph": graph.name})
            if tracer.enabled
            else None
        )
        levels, exec_nodes, member_to_group = graph.schedule()
        splan = plan_streams(exec_nodes)
        outputs: Dict[str, Any] = {}
        out_ctx: Dict[str, Context] = {}
        resolved: Dict[str, List[str]] = {"replayed": [], "cached": [], "executed": []}
        suspend: Dict[str, Interrupted] = {}
        lock = threading.Lock()

        # dependency counting for maximal overlap (scheduling-level deps)
        gdeps, deps_left, children = self._readiness(exec_nodes, member_to_group)

        stream_handles: Dict[str, StreamHandle] = {}
        stream_identity: Dict[str, Tuple[str, str]] = {}
        cancel = threading.Event()
        futures: Dict[Future, str] = {}
        pool = ThreadPoolExecutor(max_workers=self.max_workers)

        if self.journal is not None:
            self.journal.append(
                JournalRecord(
                    kind="RUN_START",
                    node_id=graph.name,
                    meta={"nodes": len(exec_nodes), **dict(run_meta or {})},
                )
            )

        def effective_ctx(nid: str) -> Context:
            node = exec_nodes[nid]
            parents = [out_ctx[d] for d in gdeps[nid]]
            base = Context.union_all(parents) if parents else graph.origin_context
            if isinstance(node, UnionNode):
                for m in sorted(node.members, key=lambda n: n.id):
                    if m.data:
                        base = base.with_data(m.data, origin=m.id)
            elif node.data:
                base = base.with_data(node.data, origin=node.id)
            return base

        def launch(nid: str) -> None:
            if splan.kinds.get(nid):
                fut: Future = Future()
                with lock:
                    futures[fut] = nid
                thread = threading.Thread(
                    target=stage_thread,
                    args=(nid, fut),
                    name=f"stream:{nid}",
                    daemon=True,
                )
                thread.start()
            else:
                f = pool.submit(run_node, nid)
                with lock:
                    futures[f] = nid

        def satisfy_stream_edges(nid: str) -> None:
            # the producer started: its stream consumers become dispatchable
            to_launch = []
            with lock:
                for c in children[nid]:
                    if (nid, c) not in splan.stream_edges:
                        continue
                    deps_left[c] -= 1
                    if deps_left[c] == 0:
                        to_launch.append(c)
            for c in to_launch:
                launch(c)

        def stage_thread(nid: str, fut: Future) -> None:
            try:
                value, ctx, status = self._run_stream_node(
                    exec_nodes[nid],
                    splan,
                    effective_ctx(nid),
                    outputs,
                    out_ctx,
                    member_to_group,
                    stream_identity,
                    stream_handles,
                    satisfy_stream_edges,
                    cancel,
                    lock,
                    parent=run_span,
                )
                with lock:
                    outputs[nid] = value
                    out_ctx[nid] = ctx
                    resolved[status].append(nid)
                fut.set_result(None)
            except BaseException as exc:
                cancel.set()
                fut.set_exception(exc)

        def run_node(nid: str) -> None:
            node = exec_nodes[nid]
            ctx = effective_ctx(nid)
            if isinstance(node, UnionNode):
                self._run_union(node, ctx, outputs, member_to_group, resolved, lock)
            else:
                inputs = _inject_inputs(node, outputs, member_to_group)
                value, status = self._run_atomic(node, ctx, inputs, parent=run_span)
                with lock:
                    if isinstance(value, WithContext):
                        ctx = ctx.with_data(value.facts, origin=node.id)
                        value = value.output
                    outputs[nid] = value
                    resolved[status].append(nid)
            with lock:
                out_ctx[nid] = ctx

        frontier = [nid for nid, c in deps_left.items() if c == 0]
        cascade_errors: List[BaseException] = []
        try:
            with pool:
                for nid in sorted(frontier):
                    launch(nid)
                while True:
                    with lock:
                        pending = list(futures)
                    if not pending:
                        break
                    done, _ = wait(pending, return_when=FIRST_COMPLETED)
                    for f in done:
                        with lock:
                            nid = futures.pop(f)
                        try:
                            f.result()  # re-raise task errors
                        except Interrupted as exc:
                            # a named interrupt point: suspend, don't fail —
                            # stop launching and let in-flight work drain
                            suspend.setdefault(nid, exc)
                            continue
                        except (StreamCancelled, ChannelClosed) as exc:
                            # a stage stopped because the run is already
                            # doomed elsewhere; keep draining so the ROOT
                            # error (the stage that actually failed)
                            # surfaces instead of this cascade
                            cascade_errors.append(exc)
                            continue
                        for c in children[nid]:
                            if (nid, c) in splan.stream_edges:
                                continue  # satisfied at stage start
                            with lock:
                                deps_left[c] -= 1
                                ready = deps_left[c] == 0
                            if ready and not suspend:
                                launch(c)
                if cascade_errors and not suspend:
                    raise cascade_errors[0]  # every failure was a cascade
        except BaseException as exc:
            # stop sibling stream stages from committing past a doomed run,
            # and unblock anything parked on a channel
            cancel.set()
            for handle in list(stream_handles.values()):
                handle.close(error=exc)
            if run_span is not None:
                tracer.end(run_span, status="error")
            raise
        finally:
            if self.journal is not None:
                self.journal.flush()

        if suspend:
            frontier = tuple(sorted(n for n in exec_nodes if n not in outputs))
            self._journal_suspend(suspend, frontier, exec_nodes)
            first_nid = next(iter(suspend))
            if run_span is not None:
                tracer.end(run_span, status="interrupt")
            return ExecutionReport(
                outputs=outputs,
                contexts=out_ctx,
                replayed=tuple(resolved["replayed"]),
                executed=tuple(resolved["executed"]),
                cached=tuple(resolved["cached"]),
                wall_s=time.monotonic() - t0,
                suspended=True,
                interrupt=suspend[first_nid].name,
                interrupt_node=first_nid,
                frontier=frontier,
            )
        if self.journal is not None:
            self.journal.append(JournalRecord(kind="RUN_END", node_id=graph.name))
            self.journal.flush()
        if run_span is not None:
            tracer.end(
                run_span,
                attrs={
                    "executed": len(resolved["executed"]),
                    "replayed": len(resolved["replayed"]),
                    "cached": len(resolved["cached"]),
                },
            )
        return ExecutionReport(
            outputs=outputs,
            contexts=out_ctx,
            replayed=tuple(resolved["replayed"]),
            executed=tuple(resolved["executed"]),
            cached=tuple(resolved["cached"]),
            wall_s=time.monotonic() - t0,
        )

    # -- stream stages --------------------------------------------------------
    def _source_invoker(
        self,
        node: Node,
        ctx: Context,
        inputs: Mapping[str, Any],
    ) -> Callable[[int], Any]:
        """invoke(start) → chunk iterable, resuming at chunk index ``start``."""
        fn = node.fn
        if fn is None or not callable(fn):
            raise ValueError(f"stream source {node.id!r} needs a callable fn")
        if _accepts_start(fn):
            return lambda start: fn(ctx, start=start, **inputs)
        return lambda start: itertools.islice(fn(ctx, **inputs), start, None)

    def _map_invoker(
        self,
        node: Node,
        ctx: Context,
        inputs: Mapping[str, Any],
        stream_kwarg: str,
    ) -> Callable[[int, Any], Any]:
        fn = node.fn
        if fn is None or not callable(fn):
            raise ValueError(f"stream map {node.id!r} needs a callable fn")
        return lambda seq, chunk: fn(ctx, **{stream_kwarg: chunk}, **inputs)

    def _reduce_invoke(
        self,
        node: Node,
        ctx: Context,
        inputs: Mapping[str, Any],
        stream_kwarg: str,
        chunk_iter: Any,
    ) -> Any:
        fn = node.fn
        if fn is None or not callable(fn):
            raise ValueError(f"stream reduce {node.id!r} needs a callable fn")
        return fn(ctx, **{stream_kwarg: chunk_iter}, **inputs)

    def _run_stream_node(
        self,
        node: Node,
        splan: StreamPlan,
        ctx: Context,
        outputs: Mapping[str, Any],
        out_ctx: Dict[str, Context],
        member_to_group: Mapping[str, str],
        stream_identity: Dict[str, Tuple[str, str]],
        stream_handles: Dict[str, StreamHandle],
        satisfy_stream_edges: Callable[[str], None],
        cancel: threading.Event,
        lock: threading.Lock,
        parent: Optional[Any] = None,
    ) -> Tuple[Any, Context, str]:
        """One stream stage, start to commit. Returns (value, ctx, status).

        The stage span wraps :meth:`_run_stream_node_inner`; a stage that
        resolves entirely by replay discards its span (zero emission).
        """
        tracer = get_tracer()
        if not tracer.enabled:
            return self._run_stream_node_inner(
                node, splan, ctx, outputs, out_ctx, member_to_group,
                stream_identity, stream_handles, satisfy_stream_edges, cancel, lock,
            )
        span = tracer.start_span(
            node.id,
            parent=parent,
            kind="stream",
            attrs={"node": node.id, "ctx": ctx.digest()},
        )
        try:
            value, out, status = self._run_stream_node_inner(
                node, splan, ctx, outputs, out_ctx, member_to_group,
                stream_identity, stream_handles, satisfy_stream_edges, cancel, lock,
            )
        except BaseException:
            tracer.end(span, status="error")
            raise
        if status == "replayed":
            tracer.discard(span)
        else:
            tracer.end(span, attrs={"status": status})
        return value, out, status

    def _run_stream_node_inner(
        self,
        node: Node,
        splan: StreamPlan,
        ctx: Context,
        outputs: Mapping[str, Any],
        out_ctx: Dict[str, Context],
        member_to_group: Mapping[str, str],
        stream_identity: Dict[str, Tuple[str, str]],
        stream_handles: Dict[str, StreamHandle],
        satisfy_stream_edges: Callable[[str], None],
        cancel: threading.Event,
        lock: threading.Lock,
    ) -> Tuple[Any, Context, str]:
        """The uninstrumented stream-stage body (see ``_run_stream_node``)."""
        nid = node.id
        kind = splan.kinds[nid]
        fn_inputs, digest_inputs, stream_kwarg, sdep = self._stream_stage_inputs(
            node, splan, outputs, member_to_group, stream_identity
        )
        ctx_d = ctx.digest()
        in_d = payload_digest(digest_inputs)

        handle: Optional[StreamHandle] = None
        if kind in ("source", "map"):
            handle = StreamHandle(
                nid,
                splan.subscribers.get(nid, ()),
                capacity=self.channel_capacity,
            )
        with lock:
            # publish identity/ctx/handle BEFORE unblocking consumers: a
            # stream stage's ξ is final at start (stages cannot emit facts),
            # and consumers union it into their own ξ the moment they launch
            out_ctx[nid] = ctx
            stream_identity[nid] = (ctx_d, in_d)
            if handle is not None:
                stream_handles[nid] = handle
        satisfy_stream_edges(nid)

        upstream = stream_handles[sdep].subscribe(nid) if sdep else None

        if kind == "reduce":
            hit = self._lookup(nid, ctx_d, in_d)
            if hit is not None:
                upstream.abandon()
                if hit.facts:
                    ctx = ctx.with_data(hit.facts, origin=nid)
                return hit.value, ctx, "replayed"
            self._journal_stream_start(nid, kind, ctx_d, in_d, 0)
            value = self._reduce_invoke(
                node, ctx, fn_inputs, stream_kwarg, reduce_iter(upstream, cancel)
            )
            facts = dict(value.facts) if isinstance(value, WithContext) else None
            if isinstance(value, WithContext):
                ctx = ctx.with_data(value.facts, origin=nid)
                value = value.output
            self._commit(
                nid, ctx_d, in_d, value, 0,
                meta={"facts": facts} if facts else None, deps=node.deps,
            )
            return value, ctx, "executed"

        log = ChunkLog(self.journal, self.replay, nid, ctx_d, in_d, deps=node.deps)
        if not log.eos:
            self._journal_stream_start(nid, kind, ctx_d, in_d, log.next_seq)
        if kind == "source":
            values, status = run_source_stage(
                nid,
                log,
                handle,
                self._source_invoker(node, ctx, fn_inputs),
                cancel,
                retries=node.retry_limit(0),
            )
        else:
            values, status = run_map_stage(
                nid,
                log,
                upstream,
                handle,
                self._map_invoker(node, ctx, fn_inputs, stream_kwarg),
                cancel,
                retries=node.retry_limit(0),
            )
        return values, ctx, status

    # -- atomic execution with retries ----------------------------------------
    def _run_atomic(
        self,
        node: Node,
        ctx: Context,
        inputs: Mapping[str, Any],
        parent: Optional[Any] = None,
    ) -> Tuple[Any, str]:
        """Resolve one node; returns (value, "replayed"|"cached"|"executed").

        ``parent`` is the enclosing run span (or None): the node span opens
        only AFTER the replay and cache probes miss, so resolved-for-free
        nodes emit zero spans.
        """
        ctx_d = ctx.digest()
        in_d = payload_digest(inputs)
        hit = self._lookup(node.id, ctx_d, in_d)
        expected: Optional[str] = None
        if hit is not None:
            if hit.reexecute:
                expected = hit.expected  # volatile: run again, verify digest
            elif hit.facts:
                # re-emit journaled context facts so downstream ξ digests
                # match the original run exactly (replay completeness)
                return WithContext(hit.value, hit.facts), "replayed"
            else:
                return hit.value, "replayed"
        key = self._cache_key(node, ctx_d, in_d)
        ent = self._cache_probe(node.id, key, ctx_d, in_d, deps=node.deps)
        if ent is not None:
            if ent.facts:
                return WithContext(ent.value, ent.facts), "cached"
            return ent.value, "cached"
        if node.fn is None:
            raise ValueError(f"node {node.id!r} has no callable")
        tracer = get_tracer()
        span = (
            tracer.start_span(
                node.id,
                parent=parent,
                kind="node",
                attrs={"node": node.id, "ctx": ctx_d, "in": in_d},
            )
            if tracer.enabled
            else None
        )
        # the node span is the current span while the node runs and
        # commits: its journal appends and the body's own spans nest in it
        with tracer.use(span):
            fn_inputs = unwrap_digested(dict(inputs))
            retry_limit = node.retry_limit(self.retry.max_attempts - 1)
            attempt = 0
            while True:
                try:
                    if self.journal is not None:
                        self.journal.append(
                            JournalRecord(
                                kind="NODE_START",
                                node_id=node.id,
                                context_digest=ctx_d,
                                input_digest=in_d,
                                attempt=attempt,
                            )
                        )
                    value = node.fn(ctx, **fn_inputs)
                    break
                except Interrupted:
                    if span is not None:
                        tracer.end(span, status="interrupt")
                    raise  # suspension request, not a failure: no retry, no NODE_FAIL
                except Exception:
                    attempt += 1
                    if attempt > retry_limit:
                        if self.journal is not None:
                            self.journal.append(
                                JournalRecord(
                                    kind="NODE_FAIL",
                                    node_id=node.id,
                                    context_digest=ctx_d,
                                    input_digest=in_d,
                                    attempt=attempt,
                                )
                            )
                        if span is not None:
                            tracer.end(span, status="error", attrs={"attempts": attempt})
                        raise
                    time.sleep(self.retry.delay(attempt))
            commit_value = value.output if isinstance(value, WithContext) else value
            facts = dict(value.facts) if isinstance(value, WithContext) else None
            meta = {"facts": facts} if facts else None
            self._commit(node.id, ctx_d, in_d, commit_value, attempt, meta=meta,
                         volatile=node.volatile, expected=expected, deps=node.deps)
            self._cache_store(node.id, key, ctx_d, in_d, commit_value, facts=facts)
        if span is not None:
            tracer.end(span, attrs={"attempts": attempt + 1})
        return value, "executed"

    def _run_union(
        self,
        group: UnionNode,
        ctx: Context,
        outputs: Dict[str, Any],
        member_to_group: Mapping[str, str],
        resolved: Dict[str, List[str]],
        lock: threading.Lock,
    ) -> None:
        """Union node = ONE atomic commit over deterministic member order."""
        ctx_d = ctx.digest()
        ext_inputs = {}
        with lock:
            for m in group.members:
                for d in m.deps:
                    gid = member_to_group.get(d, d)
                    if gid != group.id and gid in outputs:
                        ext_inputs[d] = outputs[gid]
        in_d = payload_digest(ext_inputs)
        hit = self._lookup(group.id, ctx_d, in_d)
        if hit is not None:
            with lock:
                outputs[group.id] = hit.value
                resolved["replayed"].append(group.id)
            return
        ext_deps = sorted(
            {
                d
                for m in group.members
                for d in m.deps
                if member_to_group.get(d, d) != group.id
            }
        )
        key = self._cache_key(group, ctx_d, in_d)
        ent = self._cache_probe(group.id, key, ctx_d, in_d, deps=ext_deps)
        if ent is not None:
            with lock:
                outputs[group.id] = ent.value
                resolved["cached"].append(group.id)
            return
        member_out: Dict[str, Any] = {}
        # fixed-point style deterministic order: members sorted by id; a member
        # whose intra-group dep isn't ready yet sees the PREVIOUS iteration's
        # value (co-dependent semantics), seeded by its Ψ data or None.
        order = sorted(group.members, key=lambda n: n.id)
        seed = {m.id: dict(m.data).get("__seed__") for m in order}
        for m in order:
            inputs = {}
            for d in m.deps:
                gid = member_to_group.get(d, d)
                if gid == group.id:
                    inputs[m.kwarg_for(d)] = member_out.get(d, seed.get(d))
                else:
                    out = ext_inputs.get(d)
                    inputs[m.kwarg_for(d)] = out
            if m.fn is None:
                raise ValueError(f"union member {m.id!r} has no callable")
            v = m.fn(ctx, **unwrap_digested(inputs))
            member_out[m.id] = v.output if isinstance(v, WithContext) else v
        self._commit(
            group.id, ctx_d, in_d, member_out, 0,
            meta={"members": [m.id for m in order]}, deps=ext_deps,
        )
        self._cache_store(group.id, key, ctx_d, in_d, member_out)
        with lock:
            outputs[group.id] = member_out
            resolved["executed"].append(group.id)


@dataclass
class _Inflight:
    """Scheduler-side state of a node currently dispatched through the gateway."""

    node: Node
    ctx: Context
    ctx_digest: str
    input_digest: str
    inputs: Dict[str, Any]
    futures: List[Future] = field(default_factory=list)  # still-live attempts
    copies: int = 0  # total submissions ever made (speculation budget)
    attempts: int = 0  # gateway-level requeues observed (evictions, failures)
    cache_key: Optional[CacheKey] = None  # store target once the result lands
    expected: Optional[str] = None  # volatile: digest the result must match


class ClusterExecutor(_BaseExecutor):
    """Gateway-dispatched executor: barrier-free dependency-counted dataflow.

    Node.fn may be a string (registry task name) — required for remote
    dispatch — or a callable (executed gateway-side, e.g. reductions).

    Scheduling is event-driven, not staged: a node is dispatched the moment
    its last dependency commits (no toposort-level barriers), and completions
    are consumed from a condition-variable pump fed by future callbacks — the
    scheduler blocks in ``Condition.wait``, never in a sleep-poll loop.

    Straggler speculation is global rather than per-level: on every
    ``speculation_tick_s`` wakeup without completions, any inflight node whose
    elapsed time exceeds ``straggler.threshold × median`` of same-task
    completions gets a duplicate on another worker, up to ``max_copies``.
    The first completion wins; duplicates are idempotent by durable replay.

    Fault tolerance: when the gateway evicts a dead worker (heartbeat lost or
    system-level failure), in-flight requests are requeued on survivors and
    each requeue is journaled as a ``NODE_REQUEUE`` record carrying the
    attempt count. See docs/distributed-execution.md for the state machine.

    Stream stages run on dedicated executor-side threads: a named *source*
    is dispatched once and its chunks stream back over the worker transport
    incrementally (chunk-framed HTTP — docs/streaming.md §5); a named *map*
    is dispatched once per chunk through normal gateway routing; reduce
    callables fold executor-side. Chunk commits make mid-stream worker
    death recoverable: the source is re-dispatched with ``start`` set to
    the next uncommitted offset. Stream stages are exempt from straggler
    speculation (a duplicate producer would double-emit).
    """

    def __init__(
        self,
        gateway: Gateway,
        speculative: bool = True,
        speculation_tick_s: float = 0.05,
        max_copies: int = 3,
        stream_retries: int = 2,
        **kw,
    ):
        super().__init__(**kw)
        self.gateway = gateway
        self.speculative = speculative
        self.speculation_tick_s = speculation_tick_s
        self.max_copies = max_copies
        self.stream_retries = stream_retries
        self.straggler = StragglerWatch()

    def run(
        self,
        graph: ContextGraph,
        run_meta: Optional[Mapping[str, Any]] = None,
    ) -> ExecutionReport:
        """Execute ``graph`` through the gateway; returns the run's report.

        ``run_meta`` is merged into the RUN_START record (e.g. a workflow id).
        An :class:`Interrupted` raised by an inline callable — or answered by
        a worker as an ``"interrupt"`` status — suspends the run as a clean
        drain: queued dispatches of this run are cancelled at the gateway
        (:class:`TaskCancelled` is benign — those nodes return to the pending
        frontier), in-flight work commits, SUSPEND records are journaled, and
        the gateway books the run as suspended.
        """
        t0 = time.monotonic()  # wall_s is a duration: clock steps must not skew it
        tracer = get_tracer()
        run_span = (
            tracer.start_span(f"run:{graph.name}", kind="run", attrs={"graph": graph.name})
            if tracer.enabled
            else None
        )
        _levels, exec_nodes, member_to_group = graph.schedule()  # validates DAG
        splan = plan_streams(exec_nodes)
        gdeps, deps_left, children = self._readiness(exec_nodes, member_to_group)
        run_token = f"{graph.name}#{next(_RUN_TOKENS)}"  # this run's requests

        outputs: Dict[str, Any] = {}
        out_ctx: Dict[str, Context] = {}
        resolved: Dict[str, List[str]] = {"replayed": [], "cached": [], "executed": []}
        suspend: Dict[str, Interrupted] = {}
        replayed, cached, executed = (
            resolved["replayed"],
            resolved["cached"],
            resolved["executed"],
        )
        ready = deque(sorted(nid for nid, c in deps_left.items() if c == 0))
        cv = threading.Condition()
        completions: deque = deque()  # (nid, Future) pairs, fed by callbacks
        inflight: Dict[str, _Inflight] = {}
        node_spans: Dict[str, Any] = {}  # open node spans, keyed like inflight
        stream_handles: Dict[str, StreamHandle] = {}
        stream_identity: Dict[str, Tuple[str, str]] = {}
        stream_running = [0]  # stages alive (stall detection must see them)
        cancel = threading.Event()

        if self.journal is not None:
            self.journal.append(
                JournalRecord(
                    kind="RUN_START",
                    node_id=graph.name,
                    meta={"nodes": len(exec_nodes), **dict(run_meta or {})},
                )
            )

        def pump(nid: str, fut: Future) -> None:
            # runs on gateway threads: hand the completion to the scheduler
            with cv:
                completions.append((nid, fut))
                cv.notify()

        def request_suspend(nid: str, exc: Interrupted) -> None:
            # first interrupt wins: flush this run's queued dispatches so the
            # drain is bounded, and book the suspension at the gateway
            with cv:
                first = not suspend
                suspend.setdefault(nid, exc)
                cv.notify()
            if first:
                self.gateway.cancel_run(run_token)
                self.gateway.mark_suspended(run_token, exc.name)

        def on_requeue(req: Any, reason: str) -> None:
            # gateway requeued one of our requests (eviction / worker failure);
            # requests of other runs/clients sharing the gateway chain through
            if req.meta.get("run") != run_token:
                if prev_requeue is not None:
                    prev_requeue(req, reason)
                return
            nid = req.meta.get("node", "")
            with cv:
                st = inflight.get(nid)
                if st is not None:
                    st.attempts += 1
            if st is not None and self.journal is not None:
                self.journal.append(
                    JournalRecord(
                        kind="NODE_REQUEUE",
                        node_id=nid,
                        attempt=req.attempts,
                        meta={"task": req.task_name, "reason": reason},
                    )
                )

        def done_count() -> int:
            return len(replayed) + len(cached) + len(executed)

        def finish(nid: str, value: Any, ctx: Context, status: str) -> None:
            outputs[nid] = value
            out_ctx[nid] = ctx
            resolved[status].append(nid)
            with cv:  # stage threads decrement stream edges concurrently
                for c in children[nid]:
                    if (nid, c) in splan.stream_edges:
                        continue  # satisfied when the stage started
                    deps_left[c] -= 1
                    if deps_left[c] == 0:
                        ready.append(c)

        def satisfy_stream_edges(nid: str) -> None:
            # a stage started: unblock its stream consumers and wake the pump
            with cv:
                for c in children[nid]:
                    if (nid, c) not in splan.stream_edges:
                        continue
                    deps_left[c] -= 1
                    if deps_left[c] == 0:
                        ready.append(c)
                cv.notify()

        def stage_ctx(nid: str) -> Context:
            node = exec_nodes[nid]
            parents = [out_ctx[d] for d in gdeps[nid]]
            ctx = Context.union_all(parents) if parents else graph.origin_context
            if node.data:
                ctx = ctx.with_data(node.data, origin=node.id)
            return ctx

        def stage_thread(nid: str, fut: Future) -> None:
            try:
                result = self._run_cluster_stream_node(
                    exec_nodes[nid],
                    splan,
                    stage_ctx(nid),
                    outputs,
                    out_ctx,
                    member_to_group,
                    stream_identity,
                    stream_handles,
                    satisfy_stream_edges,
                    cancel,
                    cv,
                    run_token,
                    parent=run_span,
                )
                fut.set_result(result)
            except BaseException as exc:
                cancel.set()
                fut.set_exception(exc)

        def dispatch_stream(nid: str) -> None:
            fut: Future = Future()
            with cv:
                stream_running[0] += 1
            fut.add_done_callback(lambda f, _n=nid: pump(_n, f))
            threading.Thread(
                target=stage_thread,
                args=(nid, fut),
                name=f"stream:{nid}",
                daemon=True,
            ).start()

        def dispatch(nid: str) -> None:
            node = exec_nodes[nid]
            if isinstance(node, UnionNode):
                raise NotImplementedError(
                    "union nodes execute locally; contract before remote dispatch"
                )
            if splan.kinds.get(nid):
                dispatch_stream(nid)
                return
            parents = [out_ctx[d] for d in gdeps[nid]]
            ctx = Context.union_all(parents) if parents else graph.origin_context
            if node.data:
                ctx = ctx.with_data(node.data, origin=node.id)
            inputs = _inject_inputs(node, outputs, member_to_group)
            ctx_d, in_d = ctx.digest(), payload_digest(inputs)
            hit = self._lookup(nid, ctx_d, in_d)
            expected: Optional[str] = None
            if hit is not None:
                if hit.reexecute:
                    expected = hit.expected  # volatile: run again, verify
                else:
                    if hit.facts:
                        # re-emit journaled context facts so downstream ξ
                        # digests match the original run exactly
                        ctx = ctx.with_data(hit.facts, origin=nid)
                    finish(nid, hit.value, ctx, "replayed")
                    return
            key = self._cache_key(node, ctx_d, in_d)
            ent = self._cache_probe(nid, key, ctx_d, in_d, deps=node.deps)
            if ent is not None:
                # answered before dispatch: no gateway round-trip, no worker
                if ent.facts:
                    ctx = ctx.with_data(ent.facts, origin=nid)
                finish(nid, ent.value, ctx, "cached")
                return
            if self.journal is not None:
                self.journal.append(
                    JournalRecord(
                        kind="NODE_START",
                        node_id=nid,
                        context_digest=ctx_d,
                        input_digest=in_d,
                    )
                )
            # the node span opens only after both probes missed — replayed
            # and cached nodes emit zero spans, keeping span↔NODE_COMMIT 1:1
            span = (
                tracer.start_span(
                    nid,
                    parent=run_span,
                    kind="node",
                    attrs={"node": nid, "ctx": ctx_d, "in": in_d, "run": run_token},
                )
                if tracer.enabled
                else None
            )
            if callable(node.fn):
                with tracer.use(span):  # the inline body's spans nest in the node
                    fn_inputs = unwrap_digested(dict(inputs))
                    attempt = 0
                    while True:  # immediate retries: never sleep in the scheduler
                        try:
                            value = node.fn(ctx, **fn_inputs)
                            break
                        except Interrupted as exc:
                            if span is not None:
                                tracer.end(span, status="interrupt")
                            request_suspend(nid, exc)
                            return
                        except Exception:
                            attempt += 1
                            if attempt > node.retry_limit(0):
                                if self.journal is not None:
                                    self.journal.append(
                                        JournalRecord(
                                            kind="NODE_FAIL",
                                            node_id=nid,
                                            context_digest=ctx_d,
                                            input_digest=in_d,
                                            attempt=attempt,
                                        )
                                    )
                                    self.journal.flush()
                                if span is not None:
                                    tracer.end(span, status="error", attrs={"attempts": attempt})
                                raise
                    facts = dict(value.facts) if isinstance(value, WithContext) else None
                    meta = {"facts": facts} if facts else None
                    if isinstance(value, WithContext):
                        ctx = ctx.with_data(value.facts, origin=nid)
                        value = value.output
                    self._commit(nid, ctx_d, in_d, value, attempt, meta=meta,
                                 volatile=node.volatile, expected=expected,
                                 deps=node.deps)
                    self._cache_store(nid, key, ctx_d, in_d, value, facts=facts)
                if span is not None:
                    tracer.end(span, attrs={"attempts": attempt + 1})
                finish(nid, value, ctx, "executed")
                return
            # register BEFORE submit: a requeue can fire the instant the
            # gateway pops the request, and it must find the node inflight
            st = _Inflight(node, ctx, ctx_d, in_d, dict(inputs), cache_key=key,
                           expected=expected)
            with cv:
                inflight[nid] = st
                if span is not None:
                    node_spans[nid] = span
            self.straggler.started(str(node.fn), nid)
            fut = self.gateway.submit(
                str(node.fn),
                # the wire context carries the node span's identity as a
                # transient obs.* fact; st.ctx (and every commit/output
                # path) keeps the clean, digest-identical original
                inject_trace(ctx, span) if span is not None else ctx,
                inputs,
                affinity_key=str(node.resources.get("affinity", "")),
                meta={"node": nid, "run": run_token},
            )
            with cv:
                st.futures.append(fut)
                st.copies += 1
            fut.add_done_callback(lambda f, _n=nid: pump(_n, f))

        def speculate() -> None:
            with cv:
                candidates = [
                    (nid, st)
                    for nid, st in inflight.items()
                    if st.copies < self.max_copies
                ]
            for nid, st in candidates:
                if st.node.resources.get("affinity"):
                    # pinned to worker-held state: a copy elsewhere could be
                    # wrong, a copy on the holder is useless — don't race it
                    continue
                name = str(st.node.fn)
                if not self.straggler.should_speculate(
                    name, nid, st.copies, self.max_copies
                ):
                    continue
                with cv:
                    spec_span = node_spans.get(nid)
                dup = self.gateway.submit(
                    name,
                    # a speculative copy belongs to the same node span
                    inject_trace(st.ctx, spec_span) if spec_span is not None else st.ctx,
                    dict(st.inputs),
                    meta={"node": nid, "run": run_token, "speculative": True},
                )
                with cv:
                    st.futures.append(dup)
                    st.copies += 1
                dup.add_done_callback(lambda f, _n=nid: pump(_n, f))

        prev_requeue = self.gateway.on_requeue
        self.gateway.on_requeue = on_requeue
        cascade_errors: List[BaseException] = []
        try:
            total = len(exec_nodes)
            while done_count() < total:
                while not suspend:  # suspending: park ready nodes, drain only
                    with cv:
                        nid = ready.popleft() if ready else None
                    if nid is None:
                        break
                    dispatch(nid)
                if done_count() >= total:
                    break
                with cv:
                    if (
                        suspend
                        and not inflight
                        and not stream_running[0]
                        and not completions
                    ):
                        break  # clean drain complete: everything launched committed
                    if not completions and (suspend or not ready):
                        if not inflight and not stream_running[0]:
                            if suspend:
                                break
                            if cascade_errors:
                                raise cascade_errors[0]  # all roots cascaded
                            left = total - done_count()
                            raise RuntimeError(
                                f"scheduler stalled: {left} nodes unfinished "
                                "with nothing in flight"
                            )
                        cv.wait(self.speculation_tick_s if self.speculative else None)
                    drained = []
                    while completions:
                        drained.append(completions.popleft())
                if not drained:
                    if self.speculative and not suspend:
                        speculate()
                    continue
                for nid, fut in drained:
                    if splan.kinds.get(nid):
                        with cv:
                            stream_running[0] -= 1
                        try:
                            value, ctx, status = fut.result()  # re-raise errors
                        except (StreamCancelled, ChannelClosed) as exc:
                            # cascade from a failure elsewhere: keep draining
                            # so the root error's own future surfaces it
                            cascade_errors.append(exc)
                            continue
                        finish(nid, value, ctx, status)
                        continue
                    with cv:
                        st = inflight.get(nid)
                        stale = st is None or fut not in st.futures
                    if stale:
                        continue  # duplicate of an already-committed node
                    try:
                        value = fut.result()
                    except Interrupted as exc:
                        # a worker reached a named interrupt point: suspend the
                        # run; any other copies of this node become stale
                        with cv:
                            inflight.pop(nid, None)
                            span = node_spans.pop(nid, None)
                        if span is not None:
                            tracer.end(span, status="interrupt")
                        self.straggler.finished(str(st.node.fn), nid)
                        request_suspend(nid, exc)
                        continue
                    except TaskCancelled:
                        # our own cancel_run flushed this queued dispatch; the
                        # node returns to the pending frontier. A still-running
                        # copy (speculation) is left to commit normally.
                        with cv:
                            st.futures.remove(fut)
                            if not st.futures:
                                inflight.pop(nid, None)
                                # redispatch opens a fresh span; drop this one
                                # unemitted so the node still maps to one span
                                span = node_spans.pop(nid, None)
                                if span is not None:
                                    tracer.discard(span)
                                self.straggler.finished(str(st.node.fn), nid)
                        continue
                    except Exception:
                        with cv:
                            st.futures.remove(fut)
                            copies_left = len(st.futures)
                        if copies_left:
                            continue  # a speculative copy may still win
                        with cv:
                            del inflight[nid]
                            span = node_spans.pop(nid, None)
                        if span is not None:
                            tracer.end(span, status="error", attrs={"attempts": st.attempts})
                        self.straggler.finished(str(st.node.fn), nid)
                        if self.journal is not None:
                            self.journal.append(
                                JournalRecord(
                                    kind="NODE_FAIL",
                                    node_id=nid,
                                    context_digest=st.ctx_digest,
                                    input_digest=st.input_digest,
                                    attempt=st.attempts,
                                )
                            )
                            self.journal.flush()
                        raise
                    with cv:
                        copies = st.copies
                        requeues = st.attempts
                        del inflight[nid]
                        span = node_spans.pop(nid, None)
                    self.straggler.finished(str(st.node.fn), nid)
                    with tracer.use(span):  # the commit's append nests in the node
                        self._commit(
                            nid, st.ctx_digest, st.input_digest, value,
                            requeues + copies - 1,
                            volatile=st.node.volatile, expected=st.expected,
                            deps=st.node.deps,
                        )
                    self._cache_store(
                        nid, st.cache_key, st.ctx_digest, st.input_digest, value
                    )
                    if span is not None:
                        tracer.end(
                            span, attrs={"copies": copies, "requeues": requeues}
                        )
                    finish(nid, value, st.ctx, "executed")
            if suspend:
                frontier = tuple(sorted(n for n in exec_nodes if n not in outputs))
                self._journal_suspend(suspend, frontier, exec_nodes)
            elif self.journal is not None:
                self.journal.append(JournalRecord(kind="RUN_END", node_id=graph.name))
                self.journal.flush()
        except BaseException as exc:
            cancel.set()
            for handle in list(stream_handles.values()):
                handle.close(error=exc)
            if self.journal is not None:
                self.journal.flush()
            if run_span is not None:
                tracer.end(run_span, status="error")
            raise
        finally:
            if self.gateway.on_requeue is on_requeue:  # don't clobber a later client
                self.gateway.on_requeue = prev_requeue
            with cv:
                inflight.clear()  # keep a dead chained handler's closure cheap
                node_spans.clear()
        if suspend:
            first_nid = next(iter(suspend))
            if run_span is not None:
                tracer.end(run_span, status="interrupt")
            return ExecutionReport(
                outputs=outputs,
                contexts=out_ctx,
                replayed=tuple(replayed),
                executed=tuple(executed),
                cached=tuple(cached),
                wall_s=time.monotonic() - t0,
                suspended=True,
                interrupt=suspend[first_nid].name,
                interrupt_node=first_nid,
                frontier=tuple(sorted(n for n in exec_nodes if n not in outputs)),
            )
        if run_span is not None:
            tracer.end(
                run_span,
                attrs={
                    "executed": len(executed),
                    "replayed": len(replayed),
                    "cached": len(cached),
                },
            )
        return ExecutionReport(
            outputs=outputs,
            contexts=out_ctx,
            replayed=tuple(replayed),
            executed=tuple(executed),
            cached=tuple(cached),
            wall_s=time.monotonic() - t0,
        )

    # -- stream stages over the gateway ---------------------------------------
    def _source_invoker(
        self,
        node: Node,
        ctx: Context,
        inputs: Mapping[str, Any],
        run_token: str,
    ) -> Callable[[int], Any]:
        """invoke(start) → chunk iterable, local generator or remote stream.

        Named sources are dispatched once through the gateway; the worker
        answers with an incremental chunk stream (frame-decoded by the
        transport — docs/streaming.md §5). The resolved future's value IS
        the chunk iterator, so iteration overlaps with remote production.
        The ``start`` offset is part of the task protocol: a registry task
        used as a stream source always receives ``start`` in its inputs.
        """
        fn = node.fn
        if callable(fn):
            if _accepts_start(fn):
                return lambda start: fn(ctx, start=start, **inputs)
            return lambda start: itertools.islice(fn(ctx, **inputs), start, None)
        name = str(fn)

        def invoke(start: int) -> Any:
            fut = self.gateway.submit(
                name,
                ctx,
                {**inputs, "start": start},
                affinity_key=str(node.resources.get("affinity", "")),
                meta={"node": node.id, "run": run_token, "stream": "source"},
            )
            stream = fut.result()
            if not hasattr(stream, "__iter__"):
                raise TypeError(
                    f"stream source task {name!r} returned a non-iterable "
                    f"{type(stream).__name__}; a source must be a generator"
                )
            return stream

        return invoke

    def _map_invoker(
        self,
        node: Node,
        ctx: Context,
        inputs: Mapping[str, Any],
        stream_kwarg: str,
        run_token: str,
    ) -> Callable[[int, Any], Any]:
        """Per-chunk mapper: named tasks become one routed request per chunk."""
        fn = node.fn
        if callable(fn):
            return lambda seq, chunk: fn(ctx, **{stream_kwarg: chunk}, **inputs)
        name = str(fn)

        def invoke_chunk(seq: int, chunk: Any) -> Any:
            fut = self.gateway.submit(
                name,
                ctx,
                {**inputs, stream_kwarg: chunk},
                affinity_key=str(node.resources.get("affinity", "")),
                meta={"node": node.id, "run": run_token, "seq": seq},
            )
            return fut.result()

        return invoke_chunk

    def _run_cluster_stream_node(
        self,
        node: Node,
        splan: StreamPlan,
        ctx: Context,
        outputs: Mapping[str, Any],
        out_ctx: Dict[str, Context],
        member_to_group: Mapping[str, str],
        stream_identity: Dict[str, Tuple[str, str]],
        stream_handles: Dict[str, StreamHandle],
        satisfy_stream_edges: Callable[[str], None],
        cancel: threading.Event,
        cv: threading.Condition,
        run_token: str,
        parent: Optional[Any] = None,
    ) -> Tuple[Any, Context, str]:
        """One gateway-side stream stage. Returns (value, ctx, status).

        The stage span wraps the uninstrumented body; a stage resolved
        entirely by replay discards its span (zero emission).
        """
        tracer = get_tracer()
        if not tracer.enabled:
            return self._run_cluster_stream_node_inner(
                node, splan, ctx, outputs, out_ctx, member_to_group,
                stream_identity, stream_handles, satisfy_stream_edges,
                cancel, cv, run_token,
            )
        span = tracer.start_span(
            node.id,
            parent=parent,
            kind="stream",
            attrs={"node": node.id, "ctx": ctx.digest(), "run": run_token},
        )
        try:
            value, out, status = self._run_cluster_stream_node_inner(
                node, splan, ctx, outputs, out_ctx, member_to_group,
                stream_identity, stream_handles, satisfy_stream_edges,
                cancel, cv, run_token,
            )
        except BaseException:
            tracer.end(span, status="error")
            raise
        if status == "replayed":
            tracer.discard(span)
        else:
            tracer.end(span, attrs={"status": status})
        return value, out, status

    def _run_cluster_stream_node_inner(
        self,
        node: Node,
        splan: StreamPlan,
        ctx: Context,
        outputs: Mapping[str, Any],
        out_ctx: Dict[str, Context],
        member_to_group: Mapping[str, str],
        stream_identity: Dict[str, Tuple[str, str]],
        stream_handles: Dict[str, StreamHandle],
        satisfy_stream_edges: Callable[[str], None],
        cancel: threading.Event,
        cv: threading.Condition,
        run_token: str,
    ) -> Tuple[Any, Context, str]:
        """The uninstrumented stage body (see ``_run_cluster_stream_node``)."""
        nid = node.id
        kind = splan.kinds[nid]
        fn_inputs, digest_inputs, stream_kwarg, sdep = self._stream_stage_inputs(
            node, splan, outputs, member_to_group, stream_identity
        )
        ctx_d = ctx.digest()
        in_d = payload_digest(digest_inputs)

        handle: Optional[StreamHandle] = None
        if kind in ("source", "map"):
            handle = StreamHandle(
                nid,
                splan.subscribers.get(nid, ()),
                capacity=self.channel_capacity,
            )
        with cv:
            # ctx/identity/handle are published before consumers unblock —
            # a stage's ξ is final at start (stages cannot emit facts)
            out_ctx[nid] = ctx
            stream_identity[nid] = (ctx_d, in_d)
            if handle is not None:
                stream_handles[nid] = handle
        satisfy_stream_edges(nid)

        upstream = stream_handles[sdep].subscribe(nid) if sdep else None

        if kind == "reduce":
            hit = self._lookup(nid, ctx_d, in_d)
            if hit is not None:
                upstream.abandon()
                if hit.facts:
                    ctx = ctx.with_data(hit.facts, origin=nid)
                return hit.value, ctx, "replayed"
            self._journal_stream_start(nid, kind, ctx_d, in_d, 0)
            chunk_iter = reduce_iter(upstream, cancel)
            if callable(node.fn):
                value = node.fn(ctx, **{stream_kwarg: chunk_iter}, **fn_inputs)
            else:
                # named reduce: the worker gets the materialized chunk list
                # (a registry task cannot consume a live cross-host iterator)
                fut = self.gateway.submit(
                    str(node.fn),
                    ctx,
                    {**fn_inputs, stream_kwarg: list(chunk_iter)},
                    meta={"node": nid, "run": run_token, "stream": "reduce"},
                )
                value = fut.result()
            facts = dict(value.facts) if isinstance(value, WithContext) else None
            if isinstance(value, WithContext):
                ctx = ctx.with_data(value.facts, origin=nid)
                value = value.output
            self._commit(
                nid, ctx_d, in_d, value, 0,
                meta={"facts": facts} if facts else None, deps=node.deps,
            )
            return value, ctx, "executed"

        log = ChunkLog(self.journal, self.replay, nid, ctx_d, in_d, deps=node.deps)
        if not log.eos:
            self._journal_stream_start(nid, kind, ctx_d, in_d, log.next_seq)
        if kind == "source":
            values, status = run_source_stage(
                nid,
                log,
                handle,
                self._source_invoker(node, ctx, fn_inputs, run_token),
                cancel,
                retries=max(node.retry_limit(0), self.stream_retries),
            )
        else:
            values, status = run_map_stage(
                nid,
                log,
                upstream,
                handle,
                self._map_invoker(node, ctx, fn_inputs, stream_kwarg, run_token),
                cancel,
                retries=node.retry_limit(0),
            )
        return values, ctx, status
