"""One unified entry point: ``repro.Client``.

Historically the repo grew four parallel front doors — ``LocalExecutor``,
``ClusterExecutor`` (+ hand-built ``Gateway``), ``WorkflowRunner``, and the
trainers — each wiring its own journal, cache, and run directory. ``Client``
consolidates that construction in one place::

    import repro

    with repro.Client("./state") as client:
        report = client.run(graph)                  # durable local run
        report = client.stream(stream_graph)        # chunked dataflow run

    workers = [InProcWorker(f"w{i}", registry) for i in range(4)]
    with repro.Client("./state", cluster=workers, shards=2) as client:
        report = client.run(graph)                  # sharded gateway dispatch
        wf = client.workflow("order")
        res = wf.run({"region": "eu"})
        res = wf.resume(res.workflow_id, inputs={"approve": True})

Layout under ``base_dir``::

    runs/<run_id>/journal.wal    one durable journal per .run()/.stream() id
    workflows/                   the WorkflowStore (journals + meta.json)
    .cache/                      content-addressed ResultCache shared by all

Re-running the same ``run_id`` resumes from its journal (replay, then
continue) — that is the durability contract, not an error. ``cluster``
accepts a list of workers (the client builds and owns a :class:`Gateway`,
or a :class:`ShardedGateway` when ``shards > 1``) or a prebuilt
gateway-like object (caller keeps ownership).
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Mapping, Optional, Sequence, Union

from repro.cache import ResultCache
from repro.core import ShardedGateway
from repro.core.durable import Journal
from repro.core.executor import ClusterExecutor, ExecutionReport, LocalExecutor
from repro.core.gateway import Gateway
from repro.core.graph import ContextGraph
from repro.journal import CompactionStats, LineageIndex, compact_journal
from repro.obs.metrics import MetricsRegistry, cache_collector, gateway_collector
from repro.obs.metrics import metrics as _global_metrics
from repro.obs.sinks import JsonlSink
from repro.obs.trace import get_tracer
from repro.workflow import WorkflowRegistry, WorkflowRunner
from repro.workflow.api import WorkflowResult

__all__ = ["Client", "WorkflowHandle"]


class WorkflowHandle:
    """``client.workflow(name)``: the named workflow's run/resume/fork/status."""

    def __init__(self, runner: WorkflowRunner, workflow: str):
        self._runner = runner
        self.workflow = workflow

    def run(
        self,
        args: Optional[Mapping[str, Any]] = None,
        workflow_id: Optional[str] = None,
    ) -> WorkflowResult:
        """Start a new durable incarnation of this workflow."""
        return self._runner.run(self.workflow, args=args, workflow_id=workflow_id)

    def resume(
        self,
        workflow_id: str,
        inputs: Optional[Mapping[str, Any]] = None,
    ) -> WorkflowResult:
        """Answer the pending interrupt (or just re-run) a suspended id."""
        return self._runner.resume(workflow_id, inputs=inputs)

    def fork(self, workflow_id: str, **kw: Any) -> WorkflowResult:
        """Branch a child from a committed prefix; see WorkflowRunner.fork."""
        return self._runner.fork(workflow_id, **kw)

    def status(self, workflow_id: str) -> Dict[str, Any]:
        """Store meta plus pending-interrupt detail for one id."""
        return self._runner.status(workflow_id)

    def lineage(self, workflow_id: str) -> LineageIndex:
        """Provenance projection over one workflow id's journal."""
        with Journal(
            self._runner.store.journal_path(workflow_id), sync="never"
        ) as j:
            return LineageIndex.build(j)

    def compact(
        self, workflow_id: str, keep_since: Optional[int] = None
    ) -> CompactionStats:
        """Compact one workflow id's journal (offline; see compact_journal)."""
        return compact_journal(
            self._runner.store.journal_path(workflow_id), keep_since=keep_since
        )


class Client:
    """Unified façade over local, cluster, workflow, and training execution.

    Parameters
    ----------
    base_dir:
        Root of all durable state (journals, workflow store, result cache).
    cluster:
        ``None`` for in-process execution; a sequence of workers to have the
        client build and own a gateway; or a prebuilt gateway-like object
        (anything with ``submit``/``start``/``stop``) the caller owns.
    shards:
        With a worker list and ``shards > 1``, build a
        :class:`~repro.core.ShardedGateway` with that many replicas.
    workflows:
        The :class:`WorkflowRegistry` naming graph factories for
        :meth:`workflow`; an empty registry is created when omitted so
        callers can ``client.workflows.register(...)`` directly.
    cache:
        ``True`` (default) shares one content-addressed ResultCache across
        every run and workflow under ``base_dir/.cache``.
    remote_cache:
        Optional shared filesystem path: chains the local cache to a
        :class:`~repro.cache.TieredCacheBackend` remote tier so a fleet of
        clients on different hosts deduplicates work across hosts (reads
        promote remote hits into the local tier; remote publishes are
        best-effort). Requires ``cache=True``.
    trace:
        ``True`` enables distributed tracing for every :meth:`run` /
        :meth:`stream`, writing a span log to ``runs/<run_id>/spans.jsonl``
        (the input ``python -m repro trace`` merges with the journal).
        ``None`` (default) defers to the ``REPRO_TRACE`` environment
        variable (``1``/``true``/``on`` enable).
    """

    def __init__(
        self,
        base_dir: str,
        *,
        cluster: Union[None, Sequence[Any], Any] = None,
        shards: int = 1,
        workflows: Optional[WorkflowRegistry] = None,
        cache: bool = True,
        remote_cache: Optional[str] = None,
        trace: Optional[bool] = None,
        journal_sync: str = "always",
        max_workers: int = 8,
        gateway_options: Optional[Mapping[str, Any]] = None,
    ):
        self.base_dir = base_dir
        os.makedirs(base_dir, exist_ok=True)
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        self.shards = shards
        self.journal_sync = journal_sync
        self.max_workers = max_workers
        self.workflows = workflows if workflows is not None else WorkflowRegistry()
        if trace is None:
            trace = os.environ.get("REPRO_TRACE", "").lower() in ("1", "true", "on")
        self.trace = bool(trace)
        self._collectors: List[str] = []
        if cache:
            self.cache: Optional[ResultCache] = ResultCache(
                os.path.join(base_dir, ".cache"), remote_root=remote_cache
            )
            self._bind_collector("cache", cache_collector(self.cache))
        elif remote_cache is not None:
            raise ValueError("remote_cache requires cache=True")
        else:
            self.cache = None
        self._gateway_options = dict(gateway_options or {})
        self._gateway: Optional[Any] = None
        self._owns_gateway = False
        self._workers: Optional[List[Any]] = None
        self._runner: Optional[WorkflowRunner] = None
        self._closed = False
        if cluster is None:
            pass
        elif isinstance(cluster, (list, tuple)):
            self._workers = list(cluster)
        elif hasattr(cluster, "submit"):
            self._gateway = cluster  # prebuilt; caller owns its lifecycle
        else:
            raise TypeError(
                "cluster must be None, a sequence of workers, or a "
                f"gateway-like object; got {type(cluster).__name__}"
            )

    # -- execution -----------------------------------------------------------
    def run(
        self,
        graph: ContextGraph,
        run_id: Optional[str] = None,
        run_meta: Optional[Mapping[str, Any]] = None,
    ) -> ExecutionReport:
        """Execute ``graph`` durably; local or cluster per the constructor.

        The journal lives at ``runs/<run_id>/journal.wal`` (``run_id``
        defaults to the graph's name); re-running the same id replays the
        committed prefix and continues — the crash-recovery path and the
        happy path are the same call.
        """
        self._check_open()
        rid = run_id or graph.name or "run"
        run_dir = os.path.join(self.base_dir, "runs", rid)
        os.makedirs(run_dir, exist_ok=True)
        with Journal(
            os.path.join(run_dir, "journal.wal"), sync=self.journal_sync
        ) as journal:
            ex = self._executor(journal)
            meta = dict(run_meta) if run_meta else None
            if not self.trace:
                return ex.run(graph, run_meta=meta)
            sink = JsonlSink(os.path.join(run_dir, "spans.jsonl"))
            with sink, get_tracer().attached(sink):
                return ex.run(graph, run_meta=meta)

    def stream(
        self,
        graph: ContextGraph,
        run_id: Optional[str] = None,
        run_meta: Optional[Mapping[str, Any]] = None,
    ) -> ExecutionReport:
        """Run a chunked-dataflow graph (requires at least one stream stage).

        Same durability contract as :meth:`run` — chunk-granular
        ``CHUNK_COMMIT`` records, resumable mid-stream — with an explicit
        guard so a batch graph routed here fails loudly instead of silently
        degrading to batch semantics.
        """
        if not any(n.stream for n in graph.nodes.values()):
            raise ValueError(
                f"graph {graph.name!r} declares no stream stages; use .run()"
            )
        return self.run(graph, run_id=run_id, run_meta=run_meta)

    def workflow(self, name: str) -> WorkflowHandle:
        """A handle on the named workflow (must be in ``self.workflows``)."""
        self._check_open()
        self.workflows.get(name)  # fail fast on unknown names
        return WorkflowHandle(self._workflow_runner(), name)

    def train(self, trainer: Any) -> Dict[str, Any]:
        """Run a (Distributed)Trainer's durable loop to completion.

        Trainers own their run directory and journal (``TrainConfig.run_dir``)
        — the client just drives the loop, so recovery/replay semantics are
        exactly those of ``trainer.train()``.
        """
        self._check_open()
        if not hasattr(trainer, "train"):
            raise TypeError(
                f"train() expects a trainer with a .train() loop; "
                f"got {type(trainer).__name__}"
            )
        return trainer.train()

    # -- journal lifecycle (docs/journal-lifecycle.md) -----------------------
    def journal_path(self, run_id: str) -> str:
        """The durable journal path behind one ``run_id``."""
        return os.path.join(self.base_dir, "runs", run_id, "journal.wal")

    def lineage(self, run_id: str) -> LineageIndex:
        """Provenance projection over one run's journal.

        Derived and disposable — rebuilt from the journal (compacted or not)
        on every call; answers ``provenance``/``consumers``/``produced``
        queries with bounded traversals. Raises ``FileNotFoundError`` for an
        unknown ``run_id``.
        """
        self._check_open()
        path = self.journal_path(run_id)
        if not os.path.exists(path):
            raise FileNotFoundError(f"no journal for run_id {run_id!r} at {path}")
        with Journal(path, sync="never") as j:
            return LineageIndex.build(j)

    def compact(
        self, run_id: str, keep_since: Optional[int] = None
    ) -> CompactionStats:
        """Fold one run's committed journal prefix into a SNAPSHOT record.

        Offline operation: call it between runs, never while the run is
        executing. ``keep_since`` retains logical seqs >= that value as
        addressable suffix records (e.g. fork points); ``None`` folds all.
        """
        self._check_open()
        return compact_journal(self.journal_path(run_id), keep_since=keep_since)

    def metrics(self) -> MetricsRegistry:
        """The process-global metrics registry with this client's collectors.

        The cache collector is bound at construction; the gateway collector
        on first gateway use. ``metrics().snapshot()`` /
        ``metrics().to_prometheus()`` then report identical shapes for a
        single gateway and a sharded one.
        """
        return _global_metrics()

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        """Stop the client-owned gateway (idempotent; prebuilt ones are not)."""
        self._closed = True
        for name in self._collectors:
            _global_metrics().unregister_collector(name)
        self._collectors.clear()
        if self._owns_gateway and self._gateway is not None:
            self._gateway.stop()
            self._gateway = None
            self._owns_gateway = False

    def __enter__(self) -> "Client":
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()

    # -- internals -----------------------------------------------------------
    def _check_open(self) -> None:
        if self._closed:
            raise RuntimeError("Client is closed")

    def _bind_collector(self, kind: str, fn: Any) -> None:
        """Register ``fn`` under a name unique to this client instance."""
        name = f"client{id(self)}.{kind}"
        _global_metrics().register_collector(name, fn)
        self._collectors.append(name)

    def gateway(self) -> Optional[Any]:
        """The live gateway (started on first use); None for local clients."""
        if self._gateway is None and self._workers is not None:
            if self.shards > 1:
                self._gateway = ShardedGateway(
                    self._workers, shards=self.shards, **self._gateway_options
                )
            else:
                self._gateway = Gateway(self._workers, **self._gateway_options)
            self._gateway.start()
            self._owns_gateway = True
            if hasattr(self._gateway, "stats"):
                self._bind_collector("gateway", gateway_collector(self._gateway))
        return self._gateway

    def _executor(self, journal: Journal) -> Any:
        gw = self.gateway()
        if gw is not None:
            return ClusterExecutor(gw, journal=journal, cache=self.cache)
        return LocalExecutor(
            max_workers=self.max_workers, journal=journal, cache=self.cache
        )

    def _workflow_runner(self) -> WorkflowRunner:
        if self._runner is None:
            factory = None
            if self._workers is not None or self._gateway is not None:

                def factory(**kw: Any) -> ClusterExecutor:
                    return ClusterExecutor(self.gateway(), **kw)

            self._runner = WorkflowRunner(
                self.workflows,
                os.path.join(self.base_dir, "workflows"),
                executor_factory=factory,
                journal_sync=self.journal_sync,
                max_workers=self.max_workers,
                cache=self.cache,
            )
        return self._runner
