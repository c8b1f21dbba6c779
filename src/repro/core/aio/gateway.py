"""AsyncGateway: the asyncio control-plane runtime behind the sync Gateway API.

The threaded Gateway dedicates a pool of dispatch threads plus condition-
variable wakeups to pump the queue — a hard ceiling of a few hundred inflight
requests per host. This runtime replaces the pump with a single event loop on
a dedicated thread: one dispatcher coroutine pops and allocates, each worker
invocation is an asyncio task (bounded by a semaphore, not a thread), and
heartbeat probes fan out concurrently with ``asyncio.gather`` instead of a
serial walk. Workers exposing coroutine endpoints (``run_task_async`` /
``heartbeat_async`` on :class:`~repro.core.aio.server.AsyncWorkerClient`) are
awaited natively; plain sync workers are offloaded to a small thread pool so
both kinds interoperate behind one gateway.

The public surface is *identical* to the threaded Gateway — ``submit`` still
returns a ``concurrent.futures.Future``, ``stats``/``cancel_run``/
``mark_suspended`` are inherited unchanged — so the ClusterExecutor and every
existing test drive this runtime unmodified (``REPRO_RUNTIME=async``
dispatches plain ``Gateway(...)`` construction here). All scheduling policy
(allocation chain, failure taxonomy, eviction, quarantine) is shared with the
base class via ``_allocate`` / ``_on_invoke_error`` / ``_on_result`` /
``_apply_probe``; this module only swaps the concurrency substrate.
"""

from __future__ import annotations

import asyncio
import heapq
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, Optional

from repro.obs.trace import get_tracer
from repro.wire import PayloadDecodeError

from ..context import Context
from ..gateway import AllocationError, Gateway, TaskRequest, WorkerHandle

__all__ = ["AsyncGateway"]


class AsyncGateway(Gateway):
    """Event-loop gateway runtime: same semantics, coroutine concurrency.

    ``max_inflight_rpc`` bounds concurrently-outstanding worker invocations
    (asyncio tasks are cheap, so this is 256 versus the threaded runtime's
    8 dispatch threads); ``offload_threads`` sizes the pool that runs plain
    sync workers (in-proc test workers, legacy ``WorkerClient`` transports).
    """

    def __init__(
        self,
        *args: Any,
        max_inflight_rpc: int = 256,
        offload_threads: int = 32,
        **kw: Any,
    ):
        if getattr(self, "__dispatched_init__", False):
            return  # Gateway.__new__ already ran this constructor fully
        super().__init__(*args, **kw)
        self._max_rpc = max_inflight_rpc
        self._offload_threads = offload_threads
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._loop_thread: Optional[threading.Thread] = None
        self._offload: Optional[ThreadPoolExecutor] = None
        self._wake: Optional[asyncio.Event] = None
        self._stopped: Optional[asyncio.Event] = None
        self._rpc_sem: Optional[asyncio.Semaphore] = None

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "AsyncGateway":
        """Start the loop thread; probe workers once, synchronously."""
        ready = threading.Event()
        self._loop_thread = threading.Thread(
            target=self._loop_main, args=(ready,), name=f"{self.name}:aio", daemon=True
        )
        self._loop_thread.start()
        ready.wait()
        if self._loop is not None:
            # synchronous first heartbeat pass: start with fresh context,
            # exactly like the threaded runtime's start()
            asyncio.run_coroutine_threadsafe(self._probe_all(), self._loop).result()
        return self

    def stop(self) -> None:
        """Signal the loop to exit, join its thread, release the offload pool."""
        self._stop.set()
        loop = self._loop
        if loop is not None:
            try:
                loop.call_soon_threadsafe(self._signal_stop)
            except RuntimeError:
                pass  # loop already closed
        if self._loop_thread is not None:
            self._loop_thread.join(timeout=5)
        if self._offload is not None:
            self._offload.shutdown(wait=False, cancel_futures=True)

    def _signal_stop(self) -> None:
        if self._stopped is not None:
            self._stopped.set()
        if self._wake is not None:
            self._wake.set()

    def _loop_main(self, ready: threading.Event) -> None:
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        self._loop = loop
        try:
            loop.run_until_complete(self._main(ready))
        finally:
            pending = asyncio.all_tasks(loop)
            for task in pending:
                task.cancel()
            if pending:
                loop.run_until_complete(asyncio.gather(*pending, return_exceptions=True))
            loop.close()
            self._loop = None
            ready.set()  # never leave start() blocked if startup itself died

    async def _main(self, ready: threading.Event) -> None:
        self._wake = asyncio.Event()
        self._stopped = asyncio.Event()
        self._rpc_sem = asyncio.Semaphore(self._max_rpc)
        self._offload = ThreadPoolExecutor(
            max_workers=self._offload_threads, thread_name_prefix=f"{self.name}:offload"
        )
        pumps = [
            asyncio.create_task(self._dispatch_pump()),
            asyncio.create_task(self._heartbeat_pump()),
        ]
        ready.set()
        await self._stopped.wait()
        for pump in pumps:
            pump.cancel()
        await asyncio.gather(*pumps, return_exceptions=True)

    # -- submission ---------------------------------------------------------
    def submit(self, *args: Any, **kw: Any) -> Future:
        """Enqueue one task (thread-safe) and nudge the loop's dispatcher."""
        fut = super().submit(*args, **kw)
        self._nudge()
        return fut

    def _resubmit(self, req: TaskRequest, reason: str = "", *, notify: bool = True) -> None:
        super()._resubmit(req, reason, notify=notify)
        self._nudge()

    def _nudge(self) -> None:
        loop = self._loop
        if loop is None:
            return
        try:
            loop.call_soon_threadsafe(self._wake_event)
        except RuntimeError:
            pass  # loop shut down — a crashed replica leaves futures dangling

    def _wake_event(self) -> None:
        if self._wake is not None:
            self._wake.set()

    # -- dispatch -----------------------------------------------------------
    def _pop_nowait(self) -> Optional[TaskRequest]:
        with self._cv:
            if self.silo and self._silo:
                return heapq.heappop(self._silo)[2]
            if self._queue:
                return self._queue.popleft()
        return None

    async def _dispatch_pump(self) -> None:
        assert self._wake is not None and self._rpc_sem is not None
        while not self._stop.is_set():
            req = self._pop_nowait()
            if req is None:
                self._wake.clear()
                if self._queue or self._silo:
                    continue  # raced with a submit between pop and clear
                try:
                    await asyncio.wait_for(self._wake.wait(), timeout=0.1)
                except asyncio.TimeoutError:
                    pass
                continue
            handle = self._allocate(req)
            if handle is None:
                # no live workers: same degrade-not-drop policy as the
                # threaded runtime — burn the backoff budget, never attempts
                await asyncio.sleep(0.05)
                req.backoffs += 1
                if req.backoffs >= req.max_attempts * 4:
                    self._fail(
                        req,
                        req.last_error or AllocationError("no live workers available"),
                    )
                    self.metrics["rejected"] += 1
                else:
                    self._resubmit(req, "no live workers (backoff)", notify=False)
                continue
            # register inflight at ALLOCATION time, exactly like the threaded
            # runtime's _run_on: the pump drains a queued burst without
            # yielding, so deferring this into the spawned task would let the
            # whole burst allocate against stale inflight counts and pile onto
            # one worker (least_loaded ties always break the same way)
            with self._track_lock:
                handle.inflight += 1
                handle.inflight_reqs[id(req)] = req
            await self._rpc_sem.acquire()
            task = asyncio.create_task(self._run_on_async(handle, req))
            task.add_done_callback(lambda _t: self._rpc_sem.release())

    async def _run_on_async(self, handle: WorkerHandle, req: TaskRequest) -> None:
        span = self._rpc_span(handle, req)  # same span contract as _run_on
        t0 = time.monotonic()  # interval math must survive wall-clock steps
        try:
            result = await self._invoke(handle, req, self._wire_ctx(req, span))
        except asyncio.CancelledError:
            raise
        except (ConnectionError, TimeoutError, PayloadDecodeError) as exc:
            if span is not None:
                get_tracer().end(span, status="error", attrs={"error": type(exc).__name__})
            self._on_invoke_error(handle, req, exc)
            return
        if span is not None:
            get_tracer().end(span, status=str(result.get("status", "ok")))
        self._on_result(handle, req, result, time.monotonic() - t0)

    async def _invoke(
        self, handle: WorkerHandle, req: TaskRequest, ctx: Context
    ) -> Dict[str, Any]:
        run_async = getattr(handle.worker, "run_task_async", None)
        if run_async is not None:
            return await run_async(req.task_name, ctx, req.inputs)
        return await asyncio.get_running_loop().run_in_executor(
            self._offload, handle.worker.run_task, req.task_name, ctx, req.inputs
        )

    # -- heartbeats ---------------------------------------------------------
    async def _heartbeat_pump(self) -> None:
        assert self._stopped is not None
        while not self._stop.is_set():
            try:
                await asyncio.wait_for(self._stopped.wait(), timeout=self._hb_interval)
                return
            except asyncio.TimeoutError:
                pass
            await self._probe_all()

    async def _probe_all(self) -> None:
        await asyncio.gather(*(self._probe_one(h) for h in self.handles))

    async def _probe_one(self, h: WorkerHandle) -> None:
        tel = None
        t0 = time.perf_counter()
        try:
            hb_async = getattr(h.worker, "heartbeat_async", None)
            if hb_async is not None:
                tel = await hb_async()
            else:
                tel = await asyncio.get_running_loop().run_in_executor(
                    self._offload, h.worker.heartbeat
                )
        except Exception:
            tel = None
        if tel is not None:
            # async HTTP probes stamp their own RTT; stamp offloaded in-proc
            # workers with the loop-measured probe time (same rule as sync)
            tel.setdefault("probe_latency_s", time.perf_counter() - t0)
        self._apply_probe(h, tel)
