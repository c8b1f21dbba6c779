"""Serving through a ``Gateway`` over ``WorkerServer``s, open loop.

The workers run the program's ``generate`` task (``repro.launch.serve``'s
``build_registry``: prefill, then one greedy decode step per token) on the
benchmark's weights. Set-up warms every prompt length of the mix on every
worker, then one request through the gateway. In the window one thread
submits each request at its due time; a request's latency runs from its
due time to its result at the client.

``correct`` compares a sample of the served answers, drawn from the seed and
holding a request of the longest prompt, with the plain reference: the
widest gap by which a served token's reference logit lies below the
reference's best.
"""
from __future__ import annotations

import contextlib
import gc
import math
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from bench import compare, inputs
from bench.harness import Cell, Check, GcPauses, Outcome, Run, load_family

#: seconds past the window's close that the driver waits for answers
GRACE_S = 60.0


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile; missing answers are +inf."""
    xs = sorted(values)
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]


def run(cell: Cell, run: Run) -> Outcome:
    from repro.core import Context, Gateway, WorkerClient, WorkerServer
    from repro.launch.serve import build_registry
    from repro.models import build

    cfg, mix, st = cell.config, cell.traffic, cell.settings
    seed = cell.seed
    vocab = cfg["vocab_size"]
    family = load_family(cell)
    mcfg = family.program_config(cfg, attn_impl=st.get("attn_impl", "auto"))
    model = build(mcfg)
    family.check_layout(cfg, model)
    params = family.make_weights(cfg, seed)
    regs = [build_registry(mcfg, model, params) for _ in range(int(mix["workers"]))]
    for i, reg in enumerate(regs):
        for r in inputs.warmup_requests(mix, seed=seed, vocab=vocab):
            out = reg.get("generate")(Context.origin({"session": f"warm{i}"}), r.prompt,
                                      r.new_tokens)
            if len(out["tokens"]) != r.new_tokens:
                raise RuntimeError(f"warm-up answer has {len(out['tokens'])} tokens")
    servers = [WorkerServer(f"w{i}", reg).start() for i, reg in enumerate(regs)]
    reqs = inputs.requests(mix, rate=float(st["rate"]), seconds=cell.seconds, seed=seed,
                            vocab=vocab)
    done_t: Dict[int, float] = {}
    sent_t: Dict[int, float] = {}
    futs: Dict[int, Future] = {}
    lock = threading.Lock()
    pauses = GcPauses()
    try:
        clients = [WorkerClient(s.name, s.address, s.heartbeat_server.address, timeout=300)
                   for s in servers]
        with Gateway(clients, allocation=tuple(mix["allocation"])) as gw:
            w = inputs.warmup_requests(mix, seed=seed + 1, vocab=vocab)[0]
            gw.submit("generate", Context.origin({"session": "warm"}),
                      {"prompt": w.prompt, "new_tokens": w.new_tokens},
                      affinity_key="warm").result(timeout=300)
            run.settle()
            tracing = contextlib.ExitStack()
            tracing.enter_context(run.traced())  # the profiler starts before the window
            loads_before, hits_before = run.compiles.loads, run.compiles.hits
            gw_before = dict(gw.metrics)
            gc.callbacks.append(pauses)
            t_open = time.perf_counter()
            setup_s = t_open - run.t_start
            trace_end = t_open + float(st.get("trace_seconds", cell.seconds))

            def wait_until(t: float) -> None:
                if cell.trace and trace_end <= t:
                    now = time.perf_counter()
                    if trace_end > now:
                        time.sleep(trace_end - now)
                    tracing.close()
                now = time.perf_counter()
                if t > now:
                    time.sleep(t - now)

            try:
                for r in reqs:
                    wait_until(t_open + r.due_s)
                    sent_t[r.index] = time.perf_counter()
                    f = gw.submit("generate", Context.origin({"session": r.session}),
                                  {"prompt": r.prompt, "new_tokens": r.new_tokens},
                                  affinity_key=r.session)

                    def stamp(_f: Future, i: int = r.index) -> None:
                        with lock:
                            done_t[i] = time.perf_counter()

                    f.add_done_callback(stamp)
                    futs[r.index] = f
                wait_until(trace_end)
            finally:
                tracing.close()
            t_close = t_open + cell.seconds
            deadline = max(t_close, time.perf_counter()) + GRACE_S
            answers: Dict[int, Optional[List[int]]] = {}
            for i, f in futs.items():
                try:
                    answers[i] = list(f.result(timeout=max(0.0, deadline - time.perf_counter()))
                                      ["tokens"])
                except Exception as e:  # a request that failed or never came back
                    run.note(f"request {i} failed: {type(e).__name__}: {e}")
                    answers[i] = None
            gc.callbacks.remove(pauses)
            gw_moved = {k: gw.metrics[k] - gw_before[k] for k in ("requeued", "evicted")}
        run.mark("window")
        loads_in_window = run.compiles.loads - loads_before
        hits_in_window = run.compiles.hits - hits_before
        if loads_in_window:
            secs = run.compiles.seconds[loads_before:]
            run.note(f"loaded in the window: {sorted(set(run.compiles.names[loads_before:]))}, "
                     f"{hits_in_window} of {loads_in_window} from the persistent cache; "
                     f"seconds each: median {float(np.median(secs)):.4f}, max {max(secs):.4f}")
        memory_peak = run.memory_peak_bytes()
    finally:
        for s in servers:
            s.stop()
    del regs, servers, params, model
    gc.collect()

    lat: List[float] = []
    ok: Dict[int, List[int]] = {}
    for r in reqs:
        toks = answers.get(r.index)
        good = (toks is not None and len(toks) == r.new_tokens
                and all(0 <= t < vocab for t in toks))
        if good:
            ok[r.index] = toks
            lat.append(done_t[r.index] - (t_open + r.due_s))
        else:
            lat.append(math.inf)
    failed = len(reqs) - len(ok)
    q = int(st["tail_percentile"])
    tail = percentile(lat, float(q))
    if not math.isfinite(tail):
        tail = cell.seconds + GRACE_S
    finished = [done_t[i] for i in ok]
    span = (max(finished) - t_open) if finished else math.inf
    tokens_per_s = sum(len(t) for t in ok.values()) / span if finished else 0.0
    late = [sent_t[r.index] - (t_open + r.due_s) for r in reqs if r.index in sent_t]
    by_due = [x for _, x in sorted(zip([r.due_s for r in reqs], lat, strict=True))]
    third = max(1, len(by_due) // 3)
    backlog = (percentile(by_due[:third], 50.0), percentile(by_due[-third:], 50.0))
    run.note(f"window: {len(reqs)} requests at {st['rate']}/s over {cell.seconds} s; "
             f"{failed} failed; p50 latency {percentile(lat, 50.0):.4f} s, p{q} {tail:.4f} s, "
             f"p90 {percentile(lat, 90.0):.4f} s")
    run.note(f"latencies by due time (s): {[round(x, 4) for x in by_due]}")
    run.note(f"generator lateness: median {float(np.median(late)):.6f} s, "
             f"max {max(late):.6f} s; executables loaded in the window: {loads_in_window}; "
             f"median latency of the first and last third {backlog[0]:.4f} s, {backlog[1]:.4f} s")
    run.note(f"gateway in the window: {gw_moved}; {pauses.summary()}")

    sample = sample_requests(reqs, ok, int(st["check_requests"]), seed)
    gaps = check_sample(family, cfg, seed, sample, st.get("controls", ()))
    run.mark("reference check")
    limits = st["limits"]
    checks = {"logit_gap": Check(gaps["logit_gap"], float(limits["logit_gap"]))}
    counters = {
        "latency_p50_s": percentile(lat, 50.0),
        "latency_p90_s": percentile(lat, 90.0),
        "latency_thirds_p50_s": backlog,
        "controls": {k: v for k, v in gaps.items() if k.startswith("control_")},
    }
    return Outcome(
        setup_s=setup_s,
        end_to_end={f"serve_latency_p{q}_s": tail, "serve_tokens_per_s": tokens_per_s},
        attempted=len(reqs),
        failed=failed,
        checks=checks,
        memory_peak_bytes=memory_peak,
        counters=counters,
    )


def sample_requests(reqs: List[inputs.Request], ok: Dict[int, List[int]], k: int, seed: int
                    ) -> List[tuple]:
    """``k`` finished requests drawn from the seed, the longest prompt among them.

    A request that failed is in no sample: it already counts as failed.
    """
    done = [r for r in reqs if r.index in ok]
    if not done:
        return []
    rng = np.random.default_rng(seed ^ 0xC0FFEE)
    longest = max(done, key=lambda r: len(r.prompt))
    rest = [r for r in done if r.index != longest.index]
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[: max(0, k - 1)]]
    return [(r.prompt, ok[r.index]) for r in pick]


def check_sample(family, cfg: Dict[str, Any], seed: int, sample: List[tuple],
                 controls: Sequence[str] = ()) -> Dict[str, float]:
    """Gap of the served tokens under the float32 reference of ``family``.

    For each precision in ``controls`` also the gap of the tokens that the
    reference computed in that precision puts first (``control_<p>``).
    """
    import jax

    if not sample:
        return {"logit_gap": math.inf}
    params = family.make_weights(cfg, seed)
    ref = family.position_logits(cfg)
    out = {"logit_gap": 0.0}
    out.update({f"control_{c}": 0.0 for c in controls})
    for prompt, served in sample:
        toks = np.asarray(prompt + served[:-1], np.int32)[None, :]
        pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        lg = np.asarray(jax.device_get(ref(params, toks, pos)))
        out["logit_gap"] = max(out["logit_gap"], compare.logit_gap(lg, served))
        for c in controls:
            lc = np.asarray(jax.device_get(family.position_logits(cfg, c)(params, toks, pos)))
            out[f"control_{c}"] = max(out[f"control_{c}"],
                                      compare.logit_gap(lg, lc.argmax(axis=-1)))
    return out
