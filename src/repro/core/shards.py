"""Sharded gateway replicas with journal-backed handoff (ROADMAP item 1).

One gateway is a single point of failure: when it dies, every future it
holds dangles and the run is lost even though the workers — and the journal
— survived. :class:`ShardedGateway` removes that by running N independent
gateway replicas (each with the full worker fleet) and partitioning requests
across them by node-key hash. The shard map is the recovery unit:

- every submit registers a *pending entry* (task, context, inputs, routing
  kwargs) against its owner replica, resolved through a group future that is
  the only future callers ever see;
- when a replica crashes (the ``crashed`` flag set by fault injection or a
  monitor-detected death), a survivor **adopts its partition**: each orphaned
  entry is first checked against the shared journal's ``ReplayCache`` — work
  that already reached ``NODE_COMMIT`` resolves straight from the journaled
  payload (zero duplicated commits) — and everything else is resubmitted to
  the next alive replica on the hash ring (zero lost commits);
- the adoption itself is journaled as a ``GW_HANDOFF`` record so replay and
  audit can see exactly which partition moved where and why.

Duplicate-safety does not depend on timing: group futures are set-once, and
the ClusterExecutor's first-commit-wins stale detection ignores late
resolutions from a copy that lost the race, so a resubmitted task whose
original secretly completed can never double-commit.
"""

from __future__ import annotations

import threading
import time
import zlib
from concurrent.futures import Future, InvalidStateError
from typing import Any, Callable, Dict, List, Mapping, Optional

from repro.obs.trace import get_tracer
from repro.wire import payload_digest

from .context import Context, EMPTY_CONTEXT
from .durable import Journal, JournalRecord, ReplayCache
from .gateway import AllocationError, Gateway, TaskRequest, WorkerHandle

__all__ = ["ShardedGateway"]


def _set_result(fut: Future, value: Any) -> None:
    try:
        if not fut.done():
            fut.set_result(value)
    except InvalidStateError:
        pass  # a racing resolution won; set-once is the dedup


def _set_exception(fut: Future, exc: BaseException) -> None:
    try:
        if not fut.done():
            fut.set_exception(exc)
    except InvalidStateError:
        pass


def _chain(group: Future, inner: Future) -> None:
    """Propagate a replica-side future into the caller-visible group future."""
    if group.done():
        return
    exc = inner.exception()
    if exc is not None:
        _set_exception(group, exc)
    else:
        _set_result(group, inner.result())


class _PendingSubmit:
    """One routed request: everything needed to re-route it after a crash."""

    __slots__ = ("task_name", "ctx", "inputs", "kwargs", "key", "group", "replica", "inner")

    def __init__(
        self,
        task_name: str,
        ctx: Context,
        inputs: Dict[str, Any],
        kwargs: Dict[str, Any],
        key: str,
        group: Future,
    ):
        self.task_name = task_name
        self.ctx = ctx
        self.inputs = inputs
        self.kwargs = kwargs
        self.key = key
        self.group = group
        self.replica: int = -1
        self.inner: Optional[Future] = None


class ShardedGateway:
    """N gateway replicas behind one Gateway-shaped surface.

    Construction kwargs beyond ``shards``/``journal`` are forwarded to each
    replica's ``Gateway(...)`` constructor. The executor-facing surface
    (``submit`` / ``cancel_run`` / ``mark_suspended`` / ``on_requeue``)
    matches :class:`Gateway` so the ClusterExecutor drives shards unmodified.
    """

    def __init__(
        self,
        workers: Any,
        *,
        shards: int = 2,
        journal: Optional[Journal] = None,
        name: str = "shardedgw",
        **gateway_kw: Any,
    ):
        if shards < 1:
            raise ValueError("shards must be >= 1")
        self.name = name
        self.journal = journal
        self.replicas: List[Gateway] = [
            Gateway(workers, name=f"{name}:r{i}", **gateway_kw) for i in range(shards)
        ]
        self._alive = set(range(shards))
        self._pending: Dict[int, Dict[int, _PendingSubmit]] = {
            i: {} for i in range(shards)
        }
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._monitor: Optional[threading.Thread] = None
        self._on_requeue: Optional[Callable[[TaskRequest, str], None]] = None
        self._on_worker_down: Optional[Callable[[WorkerHandle], None]] = None
        self.metrics = {"handoffs": 0, "recovered": 0, "resubmitted": 0}
        for replica in self.replicas:
            replica.on_requeue = self._forward_requeue
            replica.on_worker_down = self._forward_worker_down

    # -- observer forwarding (executor installs these on the façade) ---------
    @property
    def on_requeue(self) -> Optional[Callable[[TaskRequest, str], None]]:
        """Requeue observer, forwarded from every replica."""
        return self._on_requeue

    @on_requeue.setter
    def on_requeue(self, cb: Optional[Callable[[TaskRequest, str], None]]) -> None:
        """Install the requeue observer (fans out through every replica)."""
        self._on_requeue = cb

    @property
    def on_worker_down(self) -> Optional[Callable[[WorkerHandle], None]]:
        """Worker-death observer, forwarded from every replica."""
        return self._on_worker_down

    @on_worker_down.setter
    def on_worker_down(self, cb: Optional[Callable[[WorkerHandle], None]]) -> None:
        """Install the worker-death observer (fans out through every replica)."""
        self._on_worker_down = cb

    def _forward_requeue(self, req: TaskRequest, reason: str) -> None:
        cb = self._on_requeue
        if cb is not None:
            cb(req, reason)

    def _forward_worker_down(self, handle: WorkerHandle) -> None:
        cb = self._on_worker_down
        if cb is not None:
            cb(handle)

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "ShardedGateway":
        """Start every replica plus the crash monitor."""
        for replica in self.replicas:
            replica.start()
        self._stop.clear()
        self._monitor = threading.Thread(
            target=self._monitor_loop, name=f"{self.name}:monitor", daemon=True
        )
        self._monitor.start()
        return self

    def stop(self) -> None:
        """Stop the monitor and every still-alive replica."""
        self._stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=2)
        for replica in self.replicas:
            if not replica.crashed:
                replica.stop()

    def __enter__(self) -> "ShardedGateway":
        return self.start()

    def __exit__(self, *exc: Any) -> None:
        self.stop()

    def _monitor_loop(self) -> None:
        while not self._stop.wait(0.05):
            for idx, replica in enumerate(self.replicas):
                if replica.crashed:
                    with self._lock:
                        needs_handoff = idx in self._alive
                    if needs_handoff:
                        self.handoff(idx)

    # -- routing ------------------------------------------------------------
    def _owner(self, key: str) -> int:
        """Hash-ring owner: crc32 start slot, successor fallback over alive."""
        n = len(self.replicas)
        start = zlib.crc32(key.encode("utf-8", "replace")) % n
        for off in range(n):
            idx = (start + off) % n
            if idx in self._alive:
                return idx
        raise AllocationError("no live gateway replicas")

    def submit(
        self,
        task_name: str,
        ctx: Context = EMPTY_CONTEXT,
        inputs: Optional[Mapping[str, Any]] = None,
        *,
        priority: int = 0,
        affinity_key: str = "",
        max_attempts: int = 3,
        meta: Optional[Mapping[str, Any]] = None,
    ) -> Future:
        """Route one task to its partition owner; returns the group Future."""
        meta_d = dict(meta or {})
        key = str(meta_d.get("node") or affinity_key or task_name)
        group: Future = Future()
        entry = _PendingSubmit(
            task_name=task_name,
            ctx=ctx,
            inputs=dict(inputs or {}),
            kwargs={
                "priority": priority,
                "affinity_key": affinity_key,
                "max_attempts": max_attempts,
                "meta": meta_d,
            },
            key=key,
            group=group,
        )
        group.add_done_callback(lambda _f, e=entry: self._forget(e))
        try:
            self._route(entry)
        except Exception as exc:  # no alive replicas at all
            _set_exception(group, exc)
        return group

    def map(
        self,
        task_name: str,
        inputs_list: Any,
        ctx: Context = EMPTY_CONTEXT,
        **kw: Any,
    ) -> List[Future]:
        """Submit one task per input mapping; returns the Futures in order."""
        return [self.submit(task_name, ctx, inp, **kw) for inp in inputs_list]

    def _route(self, entry: _PendingSubmit) -> None:
        with self._lock:
            idx = self._owner(entry.key)
            entry.replica = idx
            self._pending[idx][id(entry.group)] = entry
            replica = self.replicas[idx]
        inner = replica.submit(entry.task_name, entry.ctx, entry.inputs, **entry.kwargs)
        entry.inner = inner
        inner.add_done_callback(lambda f, g=entry.group: _chain(g, f))

    def _forget(self, entry: _PendingSubmit) -> None:
        with self._lock:
            self._pending.get(entry.replica, {}).pop(id(entry.group), None)

    # -- handoff ------------------------------------------------------------
    def handoff(self, dead_idx: int, reason: str = "gateway replica crashed") -> int:
        """Adopt a dead replica's partition from journaled dispatch state.

        Every orphaned pending entry is either *recovered* (its node already
        reached ``NODE_COMMIT`` in the shared journal — resolve the group
        future straight from the journaled payload, no re-execution) or
        *resubmitted* to the next alive replica on the ring. Appends one
        ``GW_HANDOFF`` audit record; returns the number of orphans handled.
        """
        with self._lock:
            if dead_idx not in self._alive:
                return 0  # already handed off (monitor/test race)
            self._alive.discard(dead_idx)
            orphans = list(self._pending.pop(dead_idx, {}).values())
        tracer = get_tracer()
        span = (
            tracer.start_span(
                f"handoff:{self.replicas[dead_idx].name}",
                kind="handoff",
                attrs={"from": self.replicas[dead_idx].name, "reason": reason},
            )
            if tracer.enabled
            else None
        )
        replica = self.replicas[dead_idx]
        if not replica.crashed:
            replica.stop()  # administrative removal: same adoption path
        replay = ReplayCache(self.journal) if self.journal is not None else None
        recovered = resubmitted = 0
        for entry in orphans:
            if entry.group.done():
                continue
            rec = None
            node_id = str(entry.kwargs["meta"].get("node") or "")
            if replay is not None and node_id:
                rec = replay.lookup(
                    node_id, entry.ctx.digest(), payload_digest(entry.inputs)
                )
            if rec is not None and rec.payload is not None:
                _set_result(entry.group, rec.payload)
                recovered += 1
                continue
            try:
                self._route(entry)
            except Exception as exc:  # every replica is gone
                _set_exception(entry.group, exc)
                continue
            resubmitted += 1
        self.metrics["handoffs"] += 1
        self.metrics["recovered"] += recovered
        self.metrics["resubmitted"] += resubmitted
        if self.journal is not None:
            with self._lock:
                survivors = sorted(self._alive)
            self.journal.append(
                JournalRecord(
                    kind="GW_HANDOFF",
                    node_id="",
                    wall_time=time.time(),  # record timestamp
                    meta={
                        "from": self.replicas[dead_idx].name,
                        "to": [self.replicas[i].name for i in survivors],
                        "reason": reason,
                        "recovered": recovered,
                        "resubmitted": resubmitted,
                    },
                )
            )
            self.journal.flush()
        if span is not None:
            tracer.end(span, attrs={"recovered": recovered, "resubmitted": resubmitted})
        return recovered + resubmitted

    # -- run-level control (suspension) --------------------------------------
    def cancel_run(self, run_token: str) -> int:
        """Withdraw queued requests for a run on every alive replica."""
        with self._lock:
            alive = [self.replicas[i] for i in sorted(self._alive)]
        return sum(r.cancel_run(run_token) for r in alive)

    def mark_suspended(self, run_token: str, interrupt: str) -> None:
        """Book a suspension on every alive replica (any survivor can report)."""
        with self._lock:
            alive = [self.replicas[i] for i in sorted(self._alive)]
        for r in alive:
            r.mark_suspended(run_token, interrupt)

    # -- introspection -------------------------------------------------------
    def live_workers(self) -> List[WorkerHandle]:
        """Live workers as seen by the first alive replica."""
        with self._lock:
            alive = sorted(self._alive)
        if not alive:
            return []
        return self.replicas[alive[0]].live_workers()

    def stats(self) -> Dict[str, Any]:
        """Gateway-shaped snapshot merged over alive replicas, plus ring state.

        Depths, dispatch metrics and per-worker counts are summed across the
        alive replicas; a worker is live if any replica sees it live, and
        its gauges (misses, latencies, ages) take the worst replica's view.
        ``metrics`` also carries the ring's handoff counters.
        """
        with self._lock:
            alive = sorted(self._alive)
            pending = {
                self.replicas[i].name: len(m) for i, m in self._pending.items()
            }
        replicas = {self.replicas[i].name: self.replicas[i].stats() for i in alive}
        metrics: Dict[str, Any] = dict(self.metrics)
        workers: Dict[str, Dict[str, Any]] = {}
        suspended: Dict[str, Any] = {}
        for snap in replicas.values():
            for key, val in snap["metrics"].items():
                metrics[key] = metrics.get(key, 0) + val
            suspended.update(snap["suspended_runs"])
            for wname, w in snap["workers"].items():
                merged = workers.get(wname)
                if merged is None:
                    workers[wname] = dict(w)
                    continue
                for key, val in w.items():
                    if key in ("live", "app_live"):
                        merged[key] = merged[key] or val
                    elif key in ("inflight", "completed"):
                        merged[key] += val
                    else:
                        merged[key] = max(merged[key], val)
        return {
            "workers": workers,
            "queue_depth": sum(s["queue_depth"] for s in replicas.values()),
            "silo_depth": sum(s["silo_depth"] for s in replicas.values()),
            "suspended_runs": suspended,
            "live_workers": sum(1 for w in workers.values() if w["live"] and w["app_live"]),
            "metrics": metrics,
            "mean_alloc_us": metrics.get("alloc_ns_total", 0)
            / max(1, metrics.get("alloc_calls", 0))
            / 1e3,
            "shards": len(self.replicas),
            "alive": [self.replicas[i].name for i in alive],
            "pending": pending,
            "replicas": replicas,
        }
