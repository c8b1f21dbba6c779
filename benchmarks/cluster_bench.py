"""Cluster scheduling benchmark: level-barrier vs barrier-free dataflow.

Workload: K independent diamond graphs

    src_i -> (left_i, right_i) -> join_i

of named registry tasks on an in-proc cluster where ONE worker has injected
latency — the skewed-straggler regime that stage barriers are worst at
(SparkNet's observation, and the motivation for PR 2's scheduler rework).

Two runners over the *same* gateway/worker setup:

  - ``barrier``: dispatches toposort level by level and waits out each level
    before dispatching the next — the pre-dataflow ClusterExecutor semantics,
    reimplemented here as the baseline.
  - ``dataflow``: ``ClusterExecutor`` — a node dispatches the moment its deps
    commit, completions are event-driven, speculation is global.

Under the barrier, every level's wall-clock is the slow worker's wall-clock;
under dataflow only the diamonds whose tasks actually landed on the slow
worker are delayed (and speculation covers even those).

Run:   PYTHONPATH=src python -m benchmarks.cluster_bench
       PYTHONPATH=src python -m benchmarks.cluster_bench --smoke --json out.json

Prints CSV-ish lines like benchmarks/run.py; ``--json`` additionally writes a
machine-readable result blob (consumed by the CI bench-smoke artifact step).
"""

from __future__ import annotations

import argparse
import json
import os
import time

from repro.core import (
    EMPTY_CONTEXT,
    ClusterExecutor,
    ContextGraph,
    Gateway,
    InProcWorker,
    Journal,
    TaskRegistry,
)


def build_registry(task_s: float) -> TaskRegistry:
    reg = TaskRegistry()

    @reg.task("work")
    def work(ctx, **kw):
        time.sleep(task_s)
        return sum(v for v in kw.values() if isinstance(v, int)) + 1

    return reg


def make_workers(reg: TaskRegistry, n: int, slow_extra_s: float) -> list:
    workers = [InProcWorker(f"w{i}", reg) for i in range(n)]
    workers[-1].latency_s = slow_extra_s  # the skewed straggler
    return workers


def build_diamonds(k: int) -> ContextGraph:
    g = ContextGraph(name="skewed-diamonds")
    for i in range(k):
        g.add(f"src{i}", "work")
        g.add(f"left{i}", "work", deps=[f"src{i}"])
        g.add(f"right{i}", "work", deps=[f"src{i}"])
        g.add(f"join{i}", "work", deps=[f"left{i}", f"right{i}"])
    return g


def run_barrier(gateway: Gateway, graph: ContextGraph) -> dict:
    """Level-synchronous baseline: no level-N+1 dispatch until level N drains."""
    levels, exec_nodes, member_to_group = graph.schedule()
    outputs: dict = {}
    for level in levels:
        futs = {}
        for nid in level:
            node = exec_nodes[nid]
            inputs = {node.kwarg_for(d): outputs[member_to_group.get(d, d)] for d in node.deps}
            if callable(node.fn):
                outputs[nid] = node.fn(EMPTY_CONTEXT, **inputs)
            else:
                futs[nid] = gateway.submit(str(node.fn), inputs=inputs)
        for nid, fut in futs.items():  # <- the stage barrier
            outputs[nid] = fut.result(timeout=120)
    return outputs


def bench(args: argparse.Namespace) -> dict:
    k = 3 if args.smoke else args.diamonds
    task_s = 0.002 if args.smoke else args.task_s
    slow_s = 0.01 if args.smoke else args.slow_s
    expected = {f"join{i}": 5 for i in range(k)}  # src=1, arms=2 each, join=2+2+1

    from repro.wire import payload_digest

    payload_digest({"warmup": 0})  # pull in numpy etc. outside the timed region

    reg = build_registry(task_s)
    with Gateway(make_workers(reg, args.workers, slow_s)) as gw:
        t0 = time.perf_counter()
        barrier_out = run_barrier(gw, build_diamonds(k))
        barrier_s = time.perf_counter() - t0

    journal_path = os.path.join(args.out, "cluster_bench.wal")
    if os.path.exists(journal_path):
        os.remove(journal_path)  # a stale journal would replay, not execute
    reg = build_registry(task_s)
    with Gateway(make_workers(reg, args.workers, slow_s)) as gw:
        with Journal(journal_path, sync="batch") as j:
            ex = ClusterExecutor(gw, journal=j, speculation_tick_s=0.01)
            t0 = time.perf_counter()
            rep = ex.run(build_diamonds(k))
            dataflow_s = time.perf_counter() - t0

    for nid, want in expected.items():
        assert barrier_out[nid] == want, f"barrier {nid}: {barrier_out[nid]}"
        assert rep.outputs[nid] == want, f"dataflow {nid}: {rep.outputs[nid]}"

    speedup = barrier_s / dataflow_s if dataflow_s else float("inf")
    result = {
        "diamonds": k,
        "workers": args.workers,
        "task_s": task_s,
        "slow_extra_s": slow_s,
        "barrier_wall_s": round(barrier_s, 4),
        "dataflow_wall_s": round(dataflow_s, 4),
        "speedup": round(speedup, 2),
        "outputs_ok": True,
        "journal": journal_path,
    }
    print(f"barrier_wall_s,{barrier_s * 1e3:.1f}ms")
    print(f"dataflow_wall_s,{dataflow_s * 1e3:.1f}ms")
    print(f"speedup,{speedup:.2f}x")
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--diamonds", type=int, default=12)
    ap.add_argument("--task-s", type=float, default=0.01)
    ap.add_argument(
        "--slow-s",
        type=float,
        default=0.12,
        help="extra per-task latency injected on one worker",
    )
    ap.add_argument("--workers", type=int, default=3)
    ap.add_argument(
        "--repeat",
        type=int,
        default=3,
        help="take the best-of-N of each mode's wall clock",
    )
    ap.add_argument("--smoke", action="store_true", help="tiny sizes, assert-no-crash")
    ap.add_argument("--json", type=str, default="", help="write the result blob to this path")
    ap.add_argument("--out", type=str, default=".", help="directory for the run journal")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    runs = [bench(args) for _ in range(1 if args.smoke else args.repeat)]
    best = dict(runs[0])
    # best-of-N per MODE (not per run): each mode's floor is its honest cost
    best["barrier_wall_s"] = min(r["barrier_wall_s"] for r in runs)
    best["dataflow_wall_s"] = min(r["dataflow_wall_s"] for r in runs)
    best["speedup"] = round(best["barrier_wall_s"] / best["dataflow_wall_s"], 2)
    if len(runs) > 1:
        best["runs"] = runs
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(best, fh, indent=2)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
