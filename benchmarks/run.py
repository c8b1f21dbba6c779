"""Benchmark harness — one function per paper claim (the paper has no numeric
tables; Figures 1-2 are architectural, so the claims in the abstract/§1/§5
define the benchmark set). Prints ``name,us_per_call,derived`` CSV.

  bench_setup_overhead      claim: "little setup" vs a Spark-style bring-up
  bench_gateway_scheduling  claim: gateway allocation must stay fast (§5)
  bench_graph_execution     claim: "fast speeds" — framework overhead per node
  bench_journal_overhead    durable-execution tax (sync vs batch vs off)
  bench_context_overhead    ξ-union + digest cost per node
  bench_heavy_stage_vs_gateway  end-to-end task throughput vs the baseline
  bench_train_step          end-to-end jitted train step (demo model)

Run:  PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import statistics
import time
import traceback
from typing import Callable, List, Tuple

ROWS: List[Tuple[str, float, str]] = []


def record(name: str, us: float, derived: str = "") -> None:
    ROWS.append((name, us, derived))
    print(f"{name},{us:.2f},{derived}", flush=True)


def timeit(fn: Callable[[], None], repeat: int = 5) -> float:
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1e6)
    return statistics.median(ts)


# ---------------------------------------------------------------------------
def bench_setup_overhead(quick: bool) -> None:
    """SerPyTor cluster bring-up vs Spark-style heavyweight bring-up."""
    from benchmarks.baseline_heavy import HeavyCluster
    from repro.core import Gateway, InProcWorker, TaskRegistry

    reg = TaskRegistry()
    reg.register("noop", lambda ctx: None)

    def serpytor_setup():
        workers = [InProcWorker(f"w{i}", reg) for i in range(4)]
        gw = Gateway(workers, heartbeat_interval_s=10).start()
        gw.stop()

    def heavy_setup():
        hc = HeavyCluster(num_workers=4)
        hc.setup()
        hc.teardown()

    us_s = timeit(serpytor_setup, 3 if quick else 7)
    us_h = timeit(heavy_setup, 3 if quick else 7)
    record("setup_overhead_serpytor", us_s, "4 workers+gateway")
    record("setup_overhead_heavy_baseline", us_h,
           f"spark-style bring-up; ratio={us_h/us_s:.1f}x")


def bench_gateway_scheduling(quick: bool) -> None:
    from repro.core import Gateway, InProcWorker, TaskRegistry

    reg = TaskRegistry()
    reg.register("noop", lambda ctx: 0)
    n = 200 if quick else 1000
    for algo in ("round_robin", "least_loaded", "power_of_two",
                 "context_affinity"):
        workers = [InProcWorker(f"w{i}", reg) for i in range(8)]
        with Gateway(workers, allocation=(algo,),
                     heartbeat_interval_s=10) as gw:
            futs = gw.map("noop", [{} for _ in range(n)])
            [f.result(timeout=60) for f in futs]
            record(f"gateway_alloc_{algo}", gw.mean_alloc_us(),
                   f"{n} tasks, 8 workers")


def bench_graph_execution(quick: bool) -> None:
    """Per-node framework overhead: chain + fanout graphs of noop tasks."""
    from repro.core import Context, ContextGraph, LocalExecutor

    n = 50 if quick else 200

    def chain():
        g = ContextGraph(origin=Context.origin({"b": 1}))
        prev = None
        for i in range(n):
            g.add(f"n{i}", lambda ctx, **kw: 0,
                  deps=[prev] if prev else [])
            prev = f"n{i}"
        LocalExecutor(max_workers=4).run(g)

    def fanout():
        g = ContextGraph(origin=Context.origin({"b": 1}))
        g.add("src", lambda ctx: 0)
        for i in range(n):
            g.add(f"n{i}", lambda ctx, src: 0, deps=["src"])
        LocalExecutor(max_workers=8).run(g)

    us = timeit(chain, 3)
    record("graph_exec_chain_per_node", us / n, f"{n}-node chain")
    us = timeit(fanout, 3)
    record("graph_exec_fanout_per_node", us / n, f"{n}-wide fanout")


def bench_journal_overhead(quick: bool) -> None:
    import os
    import tempfile

    from repro.core import Context, ContextGraph, Journal, LocalExecutor

    n = 30 if quick else 100

    def run(sync):
        with tempfile.TemporaryDirectory() as d:
            g = ContextGraph(origin=Context.origin({"b": 1}))
            prev = None
            for i in range(n):
                g.add(f"n{i}", lambda ctx, **kw: {"x": 1},
                      deps=[prev] if prev else [])
                prev = f"n{i}"
            if sync == "off":
                LocalExecutor().run(g)
            else:
                with Journal(os.path.join(d, "j.wal"), sync=sync) as j:
                    LocalExecutor(journal=j).run(g)

    base = timeit(lambda: run("off"), 3)
    for sync in ("never", "batch", "always"):
        us = timeit(lambda s=sync: run(s), 3)
        record(f"journal_overhead_{sync}", (us - base) / n,
               f"per-node delta vs no-journal ({base/n:.1f}us baseline)")


def bench_context_overhead(quick: bool) -> None:
    from repro.core import Context

    big = Context.origin({f"k{i}": i for i in range(100)})
    small = Context.origin({"a": 1})
    us = timeit(lambda: [big | small for _ in range(100)], 5) / 100
    record("context_union_100fact", us, "union of 100-fact + 1-fact contexts")
    us = timeit(lambda: [Context.origin({"x": 1}).digest()
                         for _ in range(100)], 5) / 100
    record("context_digest", us, "fresh 1-fact context digest")


def bench_heavy_stage_vs_gateway(quick: bool) -> None:
    """End-to-end: many small tasks through both frameworks."""
    from benchmarks.baseline_heavy import HeavyCluster
    from repro.core import Gateway, InProcWorker, TaskRegistry

    n = 64 if quick else 256
    work = lambda x: sum(i * i for i in range(200))

    reg = TaskRegistry()
    reg.register("work", lambda ctx, x: work(x))

    def serpytor():
        workers = [InProcWorker(f"w{i}", reg) for i in range(4)]
        with Gateway(workers, allocation=("round_robin",),
                     heartbeat_interval_s=10) as gw:
            futs = gw.map("work", [{"x": i} for i in range(n)])
            [f.result(timeout=60) for f in futs]

    def heavy():
        hc = HeavyCluster(num_workers=4)
        hc.setup()
        hc.run_stage(work, list(range(n)))
        hc.teardown()

    us_s = timeit(serpytor, 3)
    us_h = timeit(heavy, 3)
    record("e2e_tasks_serpytor", us_s / n, f"{n} tasks incl. setup")
    record("e2e_tasks_heavy_baseline", us_h / n,
           f"{n} tasks incl. setup; ratio={us_h/us_s:.2f}x")


def bench_train_step(quick: bool) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import build
    from repro.optim.adamw import AdamWConfig, adamw_init
    from repro.train.steps import make_train_step

    cfg = dataclasses.replace(
        get_config("serpytor-demo-100m"), num_layers=4, d_model=256,
        num_heads=8, num_kv_heads=4, head_dim=32, d_ff=1024, vocab_size=8192)
    model = build(cfg)
    params, _ = model.init(jax.random.key(0))
    opt = adamw_init(params, AdamWConfig())
    step = jax.jit(make_train_step(model, AdamWConfig()),
                   donate_argnums=(0, 1))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 256)), jnp.int32)
    params, opt, _ = step(params, opt, {"tokens": toks})  # compile
    n_tokens = toks.size

    def one():
        nonlocal params, opt
        params, opt, m = step(params, opt, {"tokens": toks})
        jax.block_until_ready(m["loss"])

    us = timeit(one, 3 if quick else 5)
    dev = jax.devices()[0]
    record("train_step_10m", us,
           f"{n_tokens} tok/step; {n_tokens/(us/1e6):.0f} tok/s on {dev.platform} "
           f"{dev.device_kind} x{len(jax.devices())}")


BENCHES = [bench_setup_overhead, bench_gateway_scheduling,
           bench_graph_execution, bench_journal_overhead,
           bench_context_overhead, bench_heavy_stage_vs_gateway,
           bench_train_step]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failed = []
    for bench in BENCHES:
        if args.only and args.only not in bench.__name__:
            continue
        try:
            bench(args.quick)
        except Exception:
            traceback.print_exc()
            failed.append(bench.__name__)
    import csv
    import os

    os.makedirs("results", exist_ok=True)
    with open("results/benchmarks.csv", "w", newline="") as fh:
        w = csv.writer(fh)
        w.writerow(["name", "us_per_call", "derived"])
        w.writerows(ROWS)
    if failed:
        raise SystemExit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
