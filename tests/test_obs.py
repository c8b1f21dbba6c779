"""Observability layer (repro.obs): tracing, metrics, timelines.

The contract under test (docs/observability.md):
  - trace identity rides the run's Ψ context as a digest-excluded,
    lamport-0 ``obs.trace`` fact — injecting it never changes replay
    identity and it survives the wire roundtrip,
  - spans nest across the gateway→worker HTTP hop into one coherent
    trace, 1:1 with journal NODE_COMMITs,
  - a replica-kill handoff keeps the trace coherent (single trace id,
    no duplicate node spans, a ``handoff`` span audits the adoption),
  - a journal-replay incarnation emits zero duplicate spans,
  - ``MetricsRegistry`` renders instruments and collectors (gateway,
    cache, channel) into one snapshot,
  - ``Timeline`` reconstructs per-node timings + critical path from a
    journal, compacted or not.
"""

import json
import time

import pytest
from _faults import faults  # noqa: F401 — fixture

from repro.core import (
    ClusterExecutor,
    Context,
    ContextGraph,
    Gateway,
    InProcWorker,
    Journal,
    LocalExecutor,
    ShardedGateway,
    TaskRegistry,
    WorkerClient,
    WorkerServer,
)
from repro.obs.metrics import MetricsRegistry, cache_collector, channel_collector
from repro.obs.sinks import JsonlSink, RingSink, chrome_trace, read_spans
from repro.obs.timeline import Timeline
from repro.obs.trace import (
    TRACE_KEY,
    current_span,
    extract_trace,
    get_tracer,
    inject_trace,
    jax_counts,
    strip_trace,
)


@pytest.fixture(autouse=True)
def _tracer_clean():
    """Every test leaves the global tracer disabled and sink-free."""
    tracer = get_tracer()
    yield
    tracer.configure(enabled=False)
    for sink in list(tracer._sinks):
        tracer.remove_sink(sink)


def _registry():
    reg = TaskRegistry()

    @reg.task("add")
    def add(ctx, a, b):
        return a + b

    @reg.task("mul2")
    def mul2(ctx, a):
        return a * 2

    return reg


def _chain_graph(n=4):
    g = ContextGraph(name="chain")
    g.add("seed", lambda ctx: 1)
    prev = "seed"
    for i in range(n):
        nid = f"d{i}"
        g.add(nid, "mul2", deps=[prev], aliases={prev: "a"})
        prev = nid
    return g, prev


# ---------------------------------------------------------------------------
# trace propagation: the Ψ-fact contract
# ---------------------------------------------------------------------------


def test_trace_fact_never_changes_replay_identity():
    tracer = get_tracer()
    ctx = Context.origin({"env": "t"}).with_data({"step": 1}, origin="w0")
    span = tracer.start_span("run:x", kind="run")
    traced = inject_trace(ctx, span)
    # digest-excluded and lamport-neutral: identical replay identity
    assert traced.digest() == ctx.digest()
    assert traced.max_lamport() == ctx.max_lamport()
    # later facts stamp the same lamport/digest on both paths
    a = ctx.with_data({"next": 2}, origin="w1")
    b = traced.with_data({"next": 2}, origin="w1")
    assert a.digest() == strip_trace(b).digest()
    # the fact itself roundtrips the wire and extracts
    back = Context.from_wire(traced.to_wire())
    assert extract_trace(back) == (span.trace_id, span.span_id)
    # re-injection replaces, never accumulates
    again = inject_trace(traced, tracer.start_span("run:y", kind="run"))
    assert sum(1 for e in again if e.key == TRACE_KEY) == 1
    assert extract_trace(ctx) is None
    assert strip_trace(ctx) is ctx


def test_disabled_tracer_is_inert():
    tracer = get_tracer()
    ring = RingSink()
    tracer.add_sink(ring)
    try:
        with tracer.span("nope") as sp:
            assert sp is None
        assert ring.spans() == []
    finally:
        tracer.remove_sink(ring)


def test_attached_scope_restores_and_detaches():
    tracer = get_tracer()
    ring = RingSink(capacity=2)
    assert not tracer.enabled
    with tracer.attached(ring):
        assert tracer.enabled
        for i in range(3):
            with tracer.span(f"s{i}"):
                pass
    assert not tracer.enabled
    assert ring not in tracer._sinks
    assert [s["name"] for s in ring.spans()] == ["s1", "s2"]  # capacity bound


def test_span_error_status_and_broken_sink_swallowed():
    tracer = get_tracer()

    class Broken:
        def emit(self, obj):
            raise RuntimeError("sink down")

    ring = RingSink()
    broken = Broken()
    tracer.add_sink(broken)
    with tracer.attached(ring):
        with pytest.raises(ValueError):
            with tracer.span("boom", kind="task"):
                raise ValueError("x")
    tracer.remove_sink(broken)
    [sp] = ring.spans()
    assert sp["status"] == "error" and sp["kind"] == "task"
    assert sp["dur"] >= 0.0


def test_jsonl_sink_roundtrip_skips_torn_lines(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    tracer = get_tracer()
    with JsonlSink(path) as sink, tracer.attached(sink):
        with tracer.span("a", kind="node", attrs={"node": "a"}):
            pass
        with tracer.span("b", kind="node", attrs={"node": "b"}):
            pass
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"torn": ')  # simulated mid-write crash
    got = list(read_spans(path))
    assert [s["name"] for s in got] == ["a", "b"]


def test_chrome_trace_export_shape():
    spans = [
        {
            "name": "n1",
            "kind": "node",
            "ts": 100.0,
            "dur": 0.5,
            "status": "ok",
            "attrs": {"worker": "w0"},
        },
        {
            "name": "rpc:add",
            "kind": "rpc",
            "ts": 100.1,
            "dur": 0.2,
            "status": "ok",
            "attrs": {"worker": "w1"},
        },
    ]
    doc = chrome_trace(spans)
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(events) == 2
    assert events[0]["ts"] == pytest.approx(100.0e6)
    assert events[0]["dur"] == pytest.approx(0.5e6)
    lanes = {e.get("args", {}).get("name") for e in doc["traceEvents"] if e.get("ph") == "M"}
    assert "w0" in lanes and "w1" in lanes


# ---------------------------------------------------------------------------
# end-to-end span propagation: threaded HTTP transport
# ---------------------------------------------------------------------------


def test_span_propagation_over_http_worker(tmp_path):
    reg = _registry()
    graph, last = _chain_graph(3)
    tracer = get_tracer()
    ring = RingSink()
    with WorkerServer("w0", reg) as ws:
        client = WorkerClient("w0", ws.address, ws.heartbeat_server.address)
        with Gateway([client]) as gw:
            with Journal(str(tmp_path / "j.wal"), sync="always") as j:
                with tracer.attached(ring):
                    rep = ClusterExecutor(gw, journal=j, speculative=False).run(graph)
                kinds = dict(j.kinds())
    assert rep.outputs[last] == 2**3
    spans = ring.spans()
    by_kind = {}
    for sp in spans:
        by_kind.setdefault(sp["kind"], []).append(sp)
    # one coherent trace across the run → gateway → HTTP worker hop
    assert len({sp["trace"] for sp in spans}) == 1
    [run_span] = by_kind["run"]
    assert run_span["parent"] == ""
    # node spans correlate 1:1 with journal NODE_COMMITs
    node_spans = by_kind["node"]
    assert len(node_spans) == kinds["NODE_COMMIT"] == len(graph.nodes)
    assert {sp["attrs"]["node"] for sp in node_spans} == set(graph.nodes)
    assert all(sp["parent"] == run_span["span"] for sp in node_spans)
    # rpc spans hang off the node spans (gateway-dispatched "mul2" nodes; the
    # lambda seed runs inline without an rpc hop), worker-side task spans off
    # the rpc that carried them
    node_ids = {sp["span"] for sp in node_spans}
    assert by_kind["rpc"] and all(sp["parent"] in node_ids for sp in by_kind["rpc"])
    rpc_ids = {sp["span"] for sp in by_kind["rpc"]}
    assert by_kind["task"] and all(sp["parent"] in rpc_ids for sp in by_kind["task"])
    assert all(sp["attrs"]["worker"] == "w0" for sp in by_kind["rpc"])
    assert all(sp["attrs"]["queued_s"] >= 0.0 for sp in by_kind["rpc"])


def test_replica_kill_handoff_keeps_one_coherent_trace(tmp_path, faults):
    reg = _registry()
    graph, last = _chain_graph(6)
    tracer = get_tracer()
    ring = RingSink()
    workers = [InProcWorker(f"w{i}", reg) for i in range(3)]
    with Journal(str(tmp_path / "s.wal"), sync="always") as journal:
        with ShardedGateway(workers, shards=2, journal=journal) as sgw:
            faults.fail_gateway(sgw.replicas[0], after=1)
            with tracer.attached(ring):
                rep = ClusterExecutor(sgw, journal=journal, speculative=False).run(graph)
        kinds = dict(journal.kinds())
    assert rep.outputs[last] == 2**6
    assert kinds.get("GW_HANDOFF", 0) >= 1
    spans = ring.spans()
    traces = {sp["trace"] for sp in spans if sp["kind"] != "handoff"}
    assert len(traces) == 1  # the trace survives the replica death
    node_spans = [sp for sp in spans if sp["kind"] == "node"]
    assert len(node_spans) == kinds["NODE_COMMIT"] == len(graph.nodes)
    handoffs = [sp for sp in spans if sp["kind"] == "handoff"]
    assert handoffs
    adopted = handoffs[0]["attrs"]
    assert adopted["recovered"] + adopted["resubmitted"] >= 1


def test_journal_replay_emits_zero_duplicate_spans(tmp_path):
    graph, last = _chain_graph(3)
    reg = _registry()
    workers = [InProcWorker("w0", reg)]
    path = str(tmp_path / "r.wal")
    tracer = get_tracer()
    first = RingSink()
    with Journal(path, sync="always") as j:
        with Gateway(workers) as gw:
            with tracer.attached(first):
                ClusterExecutor(gw, journal=j, speculative=False).run(graph)
    assert [sp for sp in first.spans() if sp["kind"] == "node"]
    replay = RingSink()
    with Journal(path, sync="always") as j:
        with Gateway([InProcWorker("v0", reg)]) as gw:
            with tracer.attached(replay):
                rep = ClusterExecutor(gw, journal=j, speculative=False).run(graph)
    assert rep.replayed and not rep.executed
    kinds = {sp["kind"] for sp in replay.spans()}
    # the replay incarnation's own run span is all that may appear
    assert "node" not in kinds and "rpc" not in kinds and "task" not in kinds


def test_local_executor_replay_is_span_silent(tmp_path):
    g = ContextGraph(name="loc")
    g.add("a", lambda ctx: 2)
    g.add("b", lambda ctx, a: a + 3, deps=["a"])
    path = str(tmp_path / "l.wal")
    tracer = get_tracer()
    first, replay = RingSink(), RingSink()
    with Journal(path, sync="always") as j:
        with tracer.attached(first):
            LocalExecutor(journal=j).run(g)
    assert len([s for s in first.spans() if s["kind"] == "node"]) == 2
    with Journal(path, sync="always") as j:
        with tracer.attached(replay):
            rep = LocalExecutor(journal=j).run(g)
    assert set(rep.replayed) == {"a", "b"}
    assert [s for s in replay.spans() if s["kind"] == "node"] == []


# ---------------------------------------------------------------------------
# metrics: registry and collectors
# ---------------------------------------------------------------------------


def test_registry_instruments_and_prometheus():
    reg = MetricsRegistry()
    reg.counter("repro_x_total").inc(3)
    reg.gauge("repro_depth", shard="0").set(7)
    with reg.timer("repro_lat_s"):
        time.sleep(0.001)
    snap = reg.snapshot()
    assert snap["counters"]["repro_x_total"] == 3.0
    assert snap["gauges"]['repro_depth{shard="0"}'] == 7.0
    hist = snap["histograms"]["repro_lat_s"]
    assert hist["count"] == 1 and hist["sum"] > 0
    text = reg.to_prometheus()
    assert "repro_x_total 3" in text
    assert 'repro_depth{shard="0"} 7' in text
    assert 'repro_lat_s_bucket{le="+Inf"} 1' in text
    json.loads(reg.to_json())  # stable JSON document


def test_collector_failure_degrades_to_error_gauge():
    reg = MetricsRegistry()
    reg.register_collector("bad", lambda: 1 / 0)
    reg.register_collector("good", lambda: {"repro_ok_total": 2})
    snap = reg.snapshot()
    assert snap["gauges"]["repro_collector_errors"] == 1.0
    assert snap["counters"]["repro_ok_total"] == 2.0  # _total → counter side


def test_cache_and_channel_collectors(tmp_path):
    from repro.cache import CacheKey, ResultCache
    from repro.stream import Channel

    cache = ResultCache(str(tmp_path / "c"))
    key = CacheKey("f" * 16, "1" * 16, "c" * 16)
    cache.put(key, 1)
    cache.get(key)
    got = cache_collector(cache)()
    assert got["repro_cache_stores_total"] == 1.0
    assert got["repro_cache_hits_total"] == 1.0

    ch = Channel(capacity=4)
    ch.put(0, "a")
    collect = channel_collector(ch, "s0")
    got = collect()
    assert got['repro_channel_puts_total{channel="s0"}'] == 1.0
    assert got['repro_channel_depth{channel="s0"}'] == 1.0
    assert 'repro_channel_put_blocked_s{channel="s0"}' in got


def test_stream_run_feeds_chunk_counters(tmp_path):
    from repro.obs.metrics import metrics, reset_metrics

    reset_metrics()
    g = ContextGraph(name="st")
    g.add("src", lambda ctx: iter(range(5)), stream="source")
    g.add("total", lambda ctx, src: sum(src), deps=["src"], stream="reduce")
    with Journal(str(tmp_path / "s.wal"), sync="always") as j:
        rep = LocalExecutor(journal=j).run(g)
    assert rep.outputs["total"] == 10
    snap = metrics().snapshot()
    assert snap["counters"]["repro_stream_chunks_committed_total"] >= 5.0
    assert snap["counters"]["repro_stream_eos_total"] >= 1.0
    reset_metrics()


# ---------------------------------------------------------------------------
# timeline reconstruction
# ---------------------------------------------------------------------------


def _diamond_graph():
    g = ContextGraph(name="dia")
    g.add("a", lambda ctx: 1)
    g.add("b", lambda ctx, a: a + 1, deps=["a"])
    g.add("c", lambda ctx, a: a + 2, deps=["a"])
    g.add("d", lambda ctx, b, c: b + c, deps=["b", "c"])
    return g


def test_timeline_from_journal_with_spans(tmp_path):
    path = str(tmp_path / "t.wal")
    spans_path = str(tmp_path / "spans.jsonl")
    tracer = get_tracer()
    with Journal(path, sync="always") as j:
        with JsonlSink(spans_path) as sink, tracer.attached(sink):
            LocalExecutor(journal=j).run(_diamond_graph())
    tl = Timeline.from_journal(path, spans=read_spans(spans_path))
    assert set(tl.nodes) == {"a", "b", "c", "d"}
    assert tl.nodes["d"].deps == ("b", "c")
    assert all(nt.source == "spans" for nt in tl.nodes.values())
    nodes, dur = tl.critical_path()
    assert nodes[0] == "a" and nodes[-1] == "d" and len(nodes) == 3
    assert dur >= 0.0
    text = tl.render_text()
    assert "critical path" in text and "d" in text
    doc = tl.to_chrome()
    assert len([e for e in doc["traceEvents"] if e.get("ph") == "X"]) == 4


def test_timeline_posthoc_on_compacted_journal(tmp_path):
    from repro.journal import compact_journal

    path = str(tmp_path / "t.wal")
    with Journal(path, sync="always") as j:
        LocalExecutor(journal=j).run(_diamond_graph())
    before = Timeline.from_journal(path)
    assert all(nt.dur_s >= 0.0 for nt in before.nodes.values())
    stats = compact_journal(path)
    assert stats.folded > 0
    after = Timeline.from_journal(path)
    # NODE_START folded away → zero-duration commit events, same structure
    assert set(after.nodes) == set(before.nodes)
    assert after.nodes["d"].deps == ("b", "c")
    assert all(nt.status == "committed" for nt in after.nodes.values())
    nodes, _dur = after.critical_path()
    assert nodes  # dependency chain still reconstructable


# ---------------------------------------------------------------------------
# spans on the device trace's clock, the current span, lowering counters
# ---------------------------------------------------------------------------


def test_spans_reach_the_profiler_host_plane(tmp_path):
    """Each span is a TraceAnnotation: its name lands on the xplane's host
    plane, also when the span ends on another thread."""
    import glob
    import threading

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    tracer = get_tracer()
    ring = RingSink()
    step = jax.jit(lambda x: x * 2.0 + 1.0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.attached(ring):
            with tracer.span("obs.outer"):
                step(jnp.ones(8)).block_until_ready()
            handed = tracer.start_span("obs.handed_over")
            ender = threading.Thread(target=tracer.end, args=(handed,))
            ender.start()
            ender.join()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {
        e.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
    }
    assert {"obs.outer", "obs.handed_over"} <= host
    assert {s["name"] for s in ring.spans()} == {"obs.outer", "obs.handed_over"}
    # ended elsewhere: no lowering deltas, which belong to the opening thread
    handed_obj = next(s for s in ring.spans() if s["name"] == "obs.handed_over")
    assert "jax_lowerings" not in handed_obj["attrs"]


def test_current_span_parents_spans_opened_without_one():
    tracer = get_tracer()
    ring = RingSink()
    with tracer.attached(ring):
        root = tracer.start_span("task:x", kind="task")
        with tracer.use(root):
            with tracer.span("inner") as inner:
                assert current_span() is inner
                leaf = tracer.start_span("leaf")
                tracer.end(leaf)
            assert current_span() is root
        assert current_span() is None
        tracer.end(root)
        lone = tracer.start_span("lone")
        tracer.end(lone)
    by_name = {s["name"]: s for s in ring.spans()}
    assert by_name["inner"]["parent"] == by_name["task:x"]["span"]
    assert by_name["leaf"]["parent"] == by_name["inner"]["span"]
    assert {s["trace"] for n, s in by_name.items() if n != "lone"} == {root.trace_id}
    assert by_name["lone"]["parent"] == "" and by_name["lone"]["trace"] != root.trace_id


def _toy_serving_model():
    """Stand-in for a decoder with ``launch.serve.build_registry``'s surface."""
    import jax
    import jax.numpy as jnp

    class Toy:
        def prefill(self, params, batch, pad_to):
            last = batch["tokens"][:, -1]
            return jax.nn.one_hot((last + params) % 11, 11), jnp.zeros((), jnp.int32)

        def decode_step(self, params, cache, batch):
            return jax.nn.one_hot((batch["token"] + cache + 1) % 11, 11), cache + 1

    return Toy()


def test_serve_spans_nest_under_task_generate():
    """serve.prefill and serve.decode find task:generate as the current span;
    the task span is the child of the gateway's rpc span."""
    from repro.launch.serve import build_registry

    reg = build_registry(None, _toy_serving_model(), 3)
    tracer = get_tracer()
    ring = RingSink()
    with Gateway([InProcWorker("w0", reg)]) as gw:
        with tracer.attached(ring):
            outs = [
                gw.submit("generate", inputs={"prompt": [1, 2, 5], "new_tokens": 4}).result(
                    timeout=60
                )
                for _ in range(2)
            ]
    assert outs[0] == outs[1] == {"tokens": [8, 9, 0, 3]}
    spans = ring.spans()
    tasks = {s["span"]: s for s in spans if s["name"] == "task:generate"}
    rpcs = {s["span"]: s for s in spans if s["name"] == "rpc:generate"}
    assert len(tasks) == len(rpcs) == 2
    assert all(t["parent"] in rpcs for t in tasks.values())
    for name, attr, value in (("serve.prefill", "prompt_tokens", 3), ("serve.decode", "tokens", 4)):
        got = [s for s in spans if s["name"] == name]
        assert len(got) == 2 and all(s["parent"] in tasks for s in got)
        assert all(s["attrs"][attr] == value for s in got)
    # the first request lowers at least the jitted decode; the second finds
    # everything cached
    lowered = sorted(t["attrs"]["jax_lowerings"] for t in tasks.values())
    assert lowered[0] == 0 and lowered[1] >= 1


def test_lowering_counter_is_per_thread_and_off_when_untraced():
    import threading

    import jax

    tracer = get_tracer()
    before = jax_counts()
    jax.jit(lambda x: x + 1.0)(1.0)  # tracing off: not counted
    assert jax_counts() == before
    ring = RingSink()
    seen = {}

    def other_thread():
        start = jax_counts()
        jax.jit(lambda x: x * 3.0)(2.0)
        seen["delta"] = tuple(b - a for a, b in zip(start, jax_counts()))

    with tracer.attached(ring):
        worker = threading.Thread(target=other_thread)
        worker.start()
        worker.join()
        assert jax_counts() == before  # the other thread's lowering is its own
        with tracer.span("lowers"):
            jax.jit(lambda x: x - 5.0)(1.0)
        with tracer.span("cached"):
            jax.jit(lambda x: x * 3.0)  # nothing called: nothing lowered
    assert seen["delta"] == (1, 1)
    attrs = {s["name"]: s["attrs"] for s in ring.spans()}
    assert (attrs["lowers"]["jax_lowerings"], attrs["lowers"]["jax_compiles"]) == (1, 1)
    assert (attrs["cached"]["jax_lowerings"], attrs["cached"]["jax_compiles"]) == (0, 0)


def test_traced_training_commits_the_untraced_journal(tmp_path, monkeypatch):
    """train.* and journal.* spans, and the same journal records, byte for
    byte, as an untraced run."""
    import types

    import repro.core.durable as durable
    from repro.configs import get_config, smoke_variant
    from repro.optim.adamw import AdamWConfig
    from repro.train.trainer import TrainConfig, Trainer

    # record timestamps are the one field two runs may differ in
    monkeypatch.setattr(durable, "time", types.SimpleNamespace(time=lambda: 1.0e9))
    cfg = smoke_variant(get_config("serpytor-demo-100m"))

    def train(run_dir):
        tc = TrainConfig(run_dir=str(run_dir), num_steps=3, checkpoint_every=3, log_every=100,
                         global_batch=2, seq_len=16, heartbeat=False, journal_sync="batch",
                         opt=AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=3))
        Trainer(cfg, tc).train()
        with open(run_dir / "journal.wal", "rb") as fh:
            data = fh.read()
        # concurrent data@N nodes commit in any order: compare the records
        frames, off = [], 0
        while off < len(data):
            size, _crc = durable._HEADER.unpack_from(data, off)
            end = off + durable._HEADER.size + size
            frames.append(data[off:end])
            off = end
        return sorted(frames)

    plain = train(tmp_path / "plain")
    ring = RingSink()
    with get_tracer().attached(ring):
        traced = train(tmp_path / "traced")
    assert traced == plain
    spans = ring.spans()
    steps = {s["span"] for s in spans if s["name"].startswith("step@")}
    assert len(steps) == 3
    for name in ("train.batch", "train.step", "train.sync", "train.digest"):
        got = [s for s in spans if s["name"] == name]
        assert len(got) == 3 and all(s["parent"] in steps for s in got), name
    kinds = [s["attrs"]["kind"] for s in spans
             if s["name"] == "journal.append" and s["parent"] in steps]
    assert kinds.count("NODE_START") == kinds.count("NODE_COMMIT") == 3
