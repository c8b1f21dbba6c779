"""Sharded gateway replicas (repro.core.shards) and the threaded control plane.

The contract under test:
  - a ``ShardedGateway`` partitions requests across N threaded ``Gateway``
    replicas and serves them end to end, over in-process and HTTP workers,
    including incremental chunk streams and suspend/resume workflows,
  - its failure taxonomy is the Gateway's: a stopped worker is a system
    failure (evicted, its request finishes on a live worker), an
    application error surfaces typed, and neither hands a partition off,
  - killing a replica mid-run loses nothing and duplicates nothing: a
    survivor adopts the partition, the journal audits the handoff
    (GW_HANDOFF), and NODE_COMMITs stay exactly one-per-node,
  - with no replica killed it journals the same kinds as one ``Gateway``
    and reports the same metric names,
  - one Gateway drains a queued burst on its fixed dispatch pool.
"""

import collections
import threading
import time

import pytest
from _faults import faults  # noqa: F401 — fixture

from repro.client import Client
from repro.core import (
    AllocationError,
    ClusterExecutor,
    ContextGraph,
    Gateway,
    InProcWorker,
    Journal,
    ShardedGateway,
    TaskRegistry,
    WorkerClient,
    WorkerServer,
    interrupt,
)
from repro.obs.sinks import RingSink
from repro.obs.trace import get_tracer


def _registry():
    reg = TaskRegistry()

    @reg.task("add")
    def add(ctx, a, b):
        return a + b

    @reg.task("mul2")
    def mul2(ctx, a):
        return a * 2

    return reg


def _chain_graph(n=6):
    g = ContextGraph(name="chain")
    g.add("seed", lambda ctx: 1)
    prev = "seed"
    for i in range(n):
        nid = f"d{i}"
        g.add(nid, "mul2", deps=[prev], aliases={prev: "a"})
        prev = nid
    return g, prev


def _http_client(server, timeout=5.0):
    return WorkerClient(
        server.name, server.address, server.heartbeat_server.address, timeout=timeout
    )


def _gateway_metric_names(snapshot):
    return {
        name
        for section in ("counters", "gauges")
        for name in snapshot[section]
        if name.startswith(("repro_gateway_", "repro_worker_"))
    }


# ---------------------------------------------------------------------------
# partitioning and serving
# ---------------------------------------------------------------------------


def test_sharded_gateway_partitions_and_serves():
    reg = _registry()
    workers = [InProcWorker(f"w{i}", reg) for i in range(3)]
    with ShardedGateway(workers, shards=3) as sgw:
        futs = [
            sgw.submit("add", inputs={"a": i, "b": 1}, meta={"node": f"n{i}"})
            for i in range(12)
        ]
        assert [f.result(timeout=10) for f in futs] == [i + 1 for i in range(12)]
        stats = sgw.stats()
        assert stats["shards"] == 3 and len(stats["alive"]) == 3


def test_sharded_gateway_over_http_workers_end_to_end():
    reg = _registry()
    with WorkerServer("ws0", reg) as s0, WorkerServer("ws1", reg) as s1:
        with ShardedGateway([_http_client(s0), _http_client(s1)], shards=2) as sgw:
            assert sgw.submit("add", inputs={"a": 4, "b": 5}).result(timeout=10) == 9
            futs = [
                sgw.submit("mul2", inputs={"a": i}, meta={"node": f"n{i}"})
                for i in range(10)
            ]
            assert [f.result(timeout=10) for f in futs] == [2 * i for i in range(10)]
            stats = sgw.stats()
    # both replicas dispatched, and the merged view counts every request
    assert all(r["metrics"]["scheduled"] > 0 for r in stats["replicas"].values())
    assert stats["metrics"]["scheduled"] == 11
    assert s0.state.completed + s1.state.completed == 11


def test_sharded_map_in_order_and_client_metrics_match_single_gateway(tmp_path):
    reg = _registry()
    with ShardedGateway([InProcWorker(f"w{i}", reg) for i in range(2)], shards=2) as sgw:
        futs = sgw.map("mul2", [{"a": i} for i in range(16)])
        assert [f.result(timeout=10) for f in futs] == [2 * i for i in range(16)]

    graph, last = _chain_graph(3)
    names = {}
    for shards in (1, 2):
        workers = [InProcWorker(f"w{i}", reg) for i in range(2)]
        with Client(str(tmp_path / f"s{shards}"), cluster=workers, shards=shards) as client:
            assert client.run(graph).outputs[last] == 2**3
            snapshot = client.metrics().snapshot()
        names[shards] = _gateway_metric_names(snapshot)
        if shards == 2:
            assert snapshot["gauges"]["repro_gateway_live_workers"] == 2.0
            assert snapshot["counters"]["repro_gateway_scheduled_total"] == 3.0
    # every single-gateway name is there; the ring adds only its handoff counters
    assert names[1] <= names[2]
    assert names[2] - names[1] == {
        "repro_gateway_handoffs_total",
        "repro_gateway_recovered_total",
        "repro_gateway_resubmitted_total",
    }


# ---------------------------------------------------------------------------
# journal equivalence and streams
# ---------------------------------------------------------------------------


def _journal_kinds(gateway, journal_path):
    graph, last = _chain_graph(5)
    with Journal(journal_path, sync="never") as journal:
        with gateway:
            report = ClusterExecutor(gateway, journal=journal, speculative=False).run(graph)
        assert report.outputs[last] == 2**5
        return dict(journal.kinds())


def test_sharded_and_single_gateway_journal_same_kinds(tmp_path):
    reg = _registry()
    single = _journal_kinds(
        Gateway([InProcWorker(f"w{i}", reg) for i in range(3)]), str(tmp_path / "one.wal")
    )
    sharded = _journal_kinds(
        ShardedGateway([InProcWorker(f"w{i}", reg) for i in range(3)], shards=2),
        str(tmp_path / "two.wal"),
    )
    assert sharded == single
    assert single["NODE_COMMIT"] == 6  # seed + 5 chain nodes, exactly once
    assert "GW_HANDOFF" not in sharded


def test_client_sharded_stream_over_http_workers_is_incremental(tmp_path):
    mapped_first = threading.Event()
    stalled = threading.Event()
    reg = TaskRegistry()

    @reg.task("gen")
    def gen(ctx, start=0):
        for i in range(start, 6):
            # chunk 3 waits until chunk 0 was mapped: a transport that
            # buffered the whole body would hold chunk 0 back until EOS
            if i == 3 and not mapped_first.wait(5.0):
                stalled.set()
            yield i

    @reg.task("double")
    def double(ctx, chunk):
        if chunk == 0:
            mapped_first.set()
        return chunk * 2

    g = ContextGraph(name="sharded-stream")
    g.add_stream("src", "gen")
    g.add("m", "double", deps=["src"], stream="map", aliases={"src": "chunk"})
    g.add("r", lambda ctx, m: sum(m), deps=["m"], stream="reduce")
    with WorkerServer("ws0", reg) as s0, WorkerServer("ws1", reg) as s1:
        workers = [_http_client(s0), _http_client(s1)]
        with Client(str(tmp_path), cluster=workers, shards=2) as client:
            report = client.stream(g, run_id="st")
            journal_path = client.journal_path("st")
    assert not stalled.is_set()
    assert report.outputs["r"] == sum(2 * i for i in range(6))
    with Journal(journal_path, sync="never") as j:
        kinds = dict(j.kinds())
    assert kinds["CHUNK_COMMIT"] == 12  # 6 source + 6 map
    assert kinds["STREAM_EOS"] == 2
    assert "GW_HANDOFF" not in kinds


# ---------------------------------------------------------------------------
# failure taxonomy under shards
# ---------------------------------------------------------------------------


def _hold_registry(name, gate):
    reg = TaskRegistry()

    @reg.task("warm")
    def warm(ctx):
        return name

    @reg.task("hold")
    def hold(ctx):
        gate.wait(10.0)
        return name

    return reg


def test_stopped_worker_under_shards_is_evicted_and_request_completes_elsewhere():
    gates = {"ws0": threading.Event(), "ws1": threading.Event()}
    servers = {n: WorkerServer(n, _hold_registry(n, gates[n])).start() for n in gates}
    victim = None
    try:
        clients = [_http_client(servers[n], timeout=5.0) for n in sorted(servers)]
        with ShardedGateway(
            clients, shards=2, heartbeat_interval_s=0.05, evict_after_misses=2
        ) as sgw:
            # plant the affinity, so the held request lands on a known worker
            victim = sgw.submit("warm", affinity_key="pin").result(timeout=10)
            survivor = "ws1" if victim == "ws0" else "ws0"
            gates[survivor].set()
            fut = sgw.submit("hold", affinity_key="pin")
            deadline = time.monotonic() + 5
            while servers[victim].state.busy == 0 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert servers[victim].state.busy == 1  # in flight on the victim
            servers[victim].stop()  # both ports down: system-level death
            assert fut.result(timeout=10) == survivor
            stats = sgw.stats()
            assert sgw.metrics["handoffs"] == 0
            gates[victim].set()  # let the stale copy's handler return
    finally:
        for name, gate in gates.items():
            gate.set()
            if name != victim:
                servers[name].stop()
    assert stats["metrics"]["evicted"] >= 1
    for snap in stats["replicas"].values():
        assert snap["workers"][victim]["live"] is False
        assert snap["workers"][survivor]["live"] is True


def test_application_error_under_shards_surfaces_typed_without_handoff():
    reg = _registry()

    @reg.task("boom")
    def boom(ctx):
        raise ValueError("boom")

    died = collections.Counter()

    def die_once(task_name):
        if task_name == "mul2" and not died[task_name]:
            died[task_name] += 1
            raise ValueError("first call dies")

    workers = [InProcWorker(f"w{i}", reg) for i in range(2)]
    for w in workers:
        w.fail_injector = die_once
    with ShardedGateway(workers, shards=2) as sgw:
        # one application failure: rerouted, and the caller sees the answer
        assert sgw.submit("mul2", inputs={"a": 4}, max_attempts=3).result(timeout=10) == 8
        # a persistent one exhausts its attempts and surfaces the task's error
        with pytest.raises(RuntimeError, match="ValueError: boom"):
            sgw.submit("boom", max_attempts=2).result(timeout=10)
        stats = sgw.stats()
    assert died["mul2"] == 1
    assert sgw.metrics["handoffs"] == 0 and len(stats["alive"]) == 2
    assert stats["metrics"]["requeued"] >= 2


# ---------------------------------------------------------------------------
# replica kill: journal-backed handoff
# ---------------------------------------------------------------------------


def test_sharded_replica_kill_zero_lost_zero_duplicated(tmp_path, faults):
    """The acceptance audit: kill a replica mid-run, check the journal."""
    reg = _registry()
    graph, last = _chain_graph(8)
    n_nodes = len(graph.nodes)
    workers = [InProcWorker(f"w{i}", reg) for i in range(3)]
    journal_path = str(tmp_path / "sharded.wal")
    with Journal(journal_path, sync="always") as journal:
        with ShardedGateway(workers, shards=2, journal=journal) as sgw:
            # arm one replica to crash right after accepting a submission
            faults.fail_gateway(sgw.replicas[0], after=1)
            ex = ClusterExecutor(sgw, journal=journal, speculative=False)
            report = ex.run(graph)
        assert report.outputs[last] == 2**8
        kinds = dict(journal.kinds())
    # zero lost: the run completed; zero duplicated: one commit per node
    assert kinds["NODE_COMMIT"] == n_nodes, kinds
    assert kinds["RUN_END"] == 1
    assert kinds.get("GW_HANDOFF", 0) >= 1
    assert sgw.metrics["handoffs"] >= 1
    assert sgw.metrics["recovered"] + sgw.metrics["resubmitted"] >= 1

    # replay incarnation: same journal, no gateway work at all
    with Journal(journal_path, sync="always") as journal:
        with ShardedGateway(
            [InProcWorker(f"v{i}", reg) for i in range(2)], shards=2, journal=journal
        ) as sgw2:
            ex2 = ClusterExecutor(sgw2, journal=journal, speculative=False)
            report2 = ex2.run(graph)
        assert report2.outputs[last] == 2**8
        kinds2 = dict(journal.kinds())
    assert kinds2["NODE_COMMIT"] == n_nodes  # replay added zero new commits


def test_handoff_with_all_replicas_dead_fails_typed():
    reg = _registry()
    workers = [InProcWorker("w0", reg)]
    with ShardedGateway(workers, shards=1) as sgw:
        sgw.replicas[0].crash()
        deadline = time.time() + 3
        while time.time() < deadline and 0 in sgw._alive:
            time.sleep(0.02)
        fut = sgw.submit("add", inputs={"a": 1, "b": 1})
        with pytest.raises(AllocationError):
            fut.result(timeout=5)


# ---------------------------------------------------------------------------
# tracing and workflows through shards
# ---------------------------------------------------------------------------


def test_spans_propagate_through_shards_over_http_workers(tmp_path):
    reg = _registry()
    graph, last = _chain_graph(4)
    tracer = get_tracer()
    ring = RingSink()
    with WorkerServer("ws0", reg) as s0, WorkerServer("ws1", reg) as s1:
        clients = [_http_client(s0), _http_client(s1)]
        with Journal(str(tmp_path / "t.wal"), sync="always") as j:
            with ShardedGateway(clients, shards=2, journal=j) as sgw:
                with tracer.attached(ring):
                    rep = ClusterExecutor(sgw, journal=j, speculative=False).run(graph)
            kinds = dict(j.kinds())
    assert rep.outputs[last] == 2**4
    spans = ring.spans()
    assert len({sp["trace"] for sp in spans}) == 1
    by_kind = collections.defaultdict(list)
    for sp in spans:
        by_kind[sp["kind"]].append(sp)
    node_spans = by_kind["node"]
    assert len(node_spans) == kinds["NODE_COMMIT"] == len(graph.nodes)
    node_ids = {sp["span"] for sp in node_spans}
    rpc_ids = {sp["span"] for sp in by_kind["rpc"]}
    assert by_kind["rpc"] and all(sp["parent"] in node_ids for sp in by_kind["rpc"])
    # worker-side task spans hang off the rpc that carried them, one per
    # gateway-dispatched node (the lambda seed runs inline)
    assert len(by_kind["task"]) == len(graph.nodes) - 1
    assert all(sp["parent"] in rpc_ids for sp in by_kind["task"])
    assert {sp["attrs"]["worker"] for sp in by_kind["rpc"]} <= {"ws0", "ws1"}


def _approval_workflow(args):
    g = ContextGraph(name="approval")
    g.add("a", "seed")
    g.add("g", "gate", deps=["a"], interrupt="go")
    g.add("b", "double", deps=["g"])
    return g


def test_client_sharded_workflow_suspends_and_resumes_once_per_node(tmp_path):
    reg = TaskRegistry()
    reg.register("seed", lambda ctx: 3)
    reg.register("gate", lambda ctx, a: a + interrupt(ctx, "go"))
    reg.register("double", lambda ctx, g: g * 2)
    workers = [InProcWorker(f"w{i}", reg) for i in range(2)]
    with Client(str(tmp_path), cluster=workers, shards=2) as client:
        client.workflows.register("approval", _approval_workflow)
        handle = client.workflow("approval")
        res = handle.run(workflow_id="ap-1")
        assert res.suspended and res.interrupt == "go" and res.node == "g"
        done = handle.resume("ap-1", inputs={"go": 7})
        assert client.gateway().stats()["shards"] == 2
        journal_path = handle._runner.store.journal_path("ap-1")
    assert done.status == "completed"
    assert done.outputs == {"a": 3, "g": 10, "b": 20}
    assert "a" in done.report.replayed and "a" not in done.report.executed
    with Journal(journal_path, sync="never") as j:
        records = list(j.records())
    kinds = collections.Counter(r.kind for r in records)
    assert kinds["SUSPEND"] == 1 and kinds["RESUME"] == 1
    commits = collections.Counter(r.node_id for r in records if r.kind == "NODE_COMMIT")
    assert commits == {"a": 1, "g": 1, "b": 1}  # no node executed twice
    assert "GW_HANDOFF" not in kinds


# ---------------------------------------------------------------------------
# one threaded Gateway: a queued burst on the fixed dispatch pool
# ---------------------------------------------------------------------------


def test_gateway_drains_queued_burst_on_fixed_dispatch_pool(monkeypatch):
    reg = _registry()
    workers = [InProcWorker(f"w{i}", reg) for i in range(4)]
    with Gateway(workers) as gw:
        started = []
        original_start = threading.Thread.start

        def counting_start(thread):
            started.append(thread.name)
            original_start(thread)

        monkeypatch.setattr(threading.Thread, "start", counting_start)
        futs = gw.map("add", [{"a": i, "b": 1} for i in range(1000)])
        assert [f.result(timeout=60) for f in futs] == [i + 1 for i in range(1000)]
        monkeypatch.undo()
        dispatchers = [t for t in gw._threads if ":dispatch" in t.name]
        assert len(dispatchers) == 8  # the default pool, not a thread per task
        assert started == []
        assert sum(h.completed for h in gw.handles) >= 1000
        assert gw.metrics["scheduled"] == 1000
