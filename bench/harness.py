"""Run one cell: find its files by name, drive it, reduce, print the result.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
harness reads, each by its name:

* ``configs/<config>.json``: the model configuration as it is run;
* ``families/<family>.py``: what the benchmark knows of the configuration's
  architecture, named by its ``bench_family`` key (``dense`` when absent):
  the program mapping, the plain reference and the counters;
* ``traffic/<traffic>.json``: the traffic mix, including the driver that
  plays it (``drivers/<driver>.py``);
* ``workloads/<cell>.json``: what belongs to the cell alone (its offered
  rate, its warm-up, the limits of its correctness checks);
* ``metrics/<metric>.py``: one reader per per-layer metric;
* ``peaks.json``: the chip's peaks, by ``device_kind``.

A driver returns an :class:`Outcome`; the harness turns it into the one JSON
line the benchmark prints.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: scratch space of a run (run dirs, traces), inside the checkout
OUT_DIR = ROOT / "bench-out"

#: exit codes
EXIT_NO_CHIP = 3
EXIT_BAD_CELL = 4


class BenchError(Exception):
    """The cell cannot run here (no chip, unknown name, missing file)."""

    def __init__(self, msg: str, code: int = EXIT_BAD_CELL):
        super().__init__(msg)
        self.code = code


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------


def load_json(path: Path) -> Any:
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    with open(path) as fh:
        return json.load(fh)


def load_module(path: Path) -> ModuleType:
    """Import one file of the benchmark by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    mod_name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``workloads`` with every file it names, loaded."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    settings: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    seed: int = 0
    seconds: float = 10.0
    trace: bool = False
    bench_dir: Path = BENCH_DIR
    out_dir: Path = OUT_DIR

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def find_cell(name: str, *, bench_dir: Path = BENCH_DIR, spec: Optional[dict] = None) -> Cell:
    """Load the cell ``name`` and every file it names."""
    if spec is None:
        spec = load_json(bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {name} names unknown config {w['config']!r}")
    config = load_json(bench_dir.parent / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    settings = load_json(bench_dir / "workloads" / f"{name}.json")

    def applies(m: dict) -> bool:
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if applies(m) and m["moves"] in reported]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        settings=settings,
        end_to_end=e2e,
        per_layer=per_layer,
        bench_dir=bench_dir,
    )


def load_driver(cell: Cell) -> ModuleType:
    return load_module(cell.bench_dir / "drivers" / f"{cell.driver}.py")


def load_family(cell: Cell) -> ModuleType:
    family = cell.config.get("bench_family", "dense")
    return load_module(cell.bench_dir / "families" / f"{family}.py")


def load_reader(cell: Cell, metric: str) -> Callable[["Observations"], Optional[float]]:
    return load_module(cell.bench_dir / "metrics" / f"{metric}.py").read


def peaks_for(kind: str, bench_dir: Path = BENCH_DIR) -> Dict[str, float]:
    """The peaks row of ``device_kind`` ``kind``; unknown kinds are an error."""
    table = load_json(bench_dir / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json ({sorted(table)})")
    return table[kind]


# ---------------------------------------------------------------------------
# what a driver hands back
# ---------------------------------------------------------------------------


@dataclass
class Check:
    """One number compared with the plain reference, and its limit."""

    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class Observations:
    """What per-layer readers read: spans, counters, the reduced trace."""

    cell: Cell
    spans: List[Dict[str, Any]] = field(default_factory=list)
    counters: Dict[str, Any] = field(default_factory=dict)
    trace: Any = None  # bench.trace.TraceSummary of the traced window
    peaks: Dict[str, float] = field(default_factory=dict)
    chips: int = 1


@dataclass
class Outcome:
    setup_s: float
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: Dict[str, Check]
    memory_peak_bytes: int
    counters: Dict[str, Any] = field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and self.failed == 0 and all(c.ok for c in self.checks.values())


# ---------------------------------------------------------------------------
# run-time services for drivers
# ---------------------------------------------------------------------------


class SpanSink:
    """In-memory sink for the program's ``repro.obs`` spans."""

    def __init__(self) -> None:
        self.spans: List[Dict[str, Any]] = []

    def emit(self, obj: Dict[str, Any]) -> None:
        self.spans.append(obj)


class CompileCounter:
    """Counts executables the process obtains, and how many came from the cache.

    ``loads`` counts every executable (compiled, or read from the persistent
    cache), ``hits`` those read from the cache; ``seconds`` holds the time
    each load took, in order.
    """

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.loads = 0
        self.hits = 0
        self.names: List[str] = []
        self.seconds: List[float] = []
        self._mon = mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw: Any) -> None:
        if event == self.EVENT:
            self.loads += 1
            self.names.append(str(kw.get("fun_name", "?")))
            self.seconds.append(float(secs))

    def _on_event(self, event: str, **kw: Any) -> None:
        if event == self.HIT:
            self.hits += 1

    def close(self) -> None:
        self._mon.unregister_event_duration_listener(self._on_duration)
        self._mon.unregister_event_listener(self._on_event)


class GcPauses:
    """A ``gc.callbacks`` hook: how long each garbage collection held the process."""

    def __init__(self) -> None:
        self._t0 = 0.0
        self.pauses: List[tuple] = []  # (generation, seconds)

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"], time.perf_counter() - self._t0))

    def summary(self) -> str:
        if not self.pauses:
            return "no garbage collection"
        gen, longest = max(self.pauses, key=lambda p: p[1])
        return (f"{len(self.pauses)} garbage collections, {sum(p[1] for p in self.pauses):.4f} s "
                f"in all, the longest {longest:.4f} s (generation {gen})")


class Run:
    """Services one run offers its driver: clock, tracing, memory, notes."""

    def __init__(self, cell: Cell, t_start: float):
        self.cell = cell
        self.t_start = t_start
        self.sink = SpanSink()
        self.trace_dir = cell.out_dir / "trace"
        self.traced_window: Optional[Tuple[float, float]] = None
        self.compiles = CompileCounter()

    def note(self, msg: str) -> None:
        """An informational line (printed on stderr before the result)."""
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    def mark(self, phase: str) -> None:
        """Note that ``phase`` ended, with the seconds since process start."""
        self.note(f"{phase} done at {time.perf_counter() - self.t_start:.1f} s")

    @contextlib.contextmanager
    def traced(self) -> Iterator[None]:
        """Profile the device and collect the program's spans (``--trace 1``).

        Outside a traced run this does nothing.
        """
        if not self.cell.trace:
            yield
            return
        import jax

        from repro.obs.trace import get_tracer

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        self.trace_dir.mkdir(parents=True, exist_ok=True)
        # the Python tracer would time every Python call of a host-bound
        # path (serving) and slow it several-fold; the runtime's own host
        # events stay on
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(self.trace_dir), profiler_options=options)
        t0 = time.perf_counter()
        try:
            with get_tracer().attached(self.sink):
                yield
        finally:
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.traced_window = (t0, t1)

    def settle(self) -> None:
        """Last step of set-up: the program's cache thresholds, no garbage, no dirty pages.

        Set-up writes the compile cache, weights' host copies and run
        directories; without this the window's first fsyncs would pay for
        that writeback.
        """
        program_cache_settings()
        gc.collect()
        os.sync()

    def memory_peak_bytes(self) -> int:
        """Peak bytes in use on the fullest chip so far."""
        import jax

        peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in jax.devices()]
        return max(peaks) if peaks else 0


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def check_chips(cell: Cell) -> Dict[str, Any]:
    """The device dict of the result; raises unless JAX holds enough TPUs."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise BenchError(f"no TPU: JAX backend is {backend!r}; the benchmark runs on the chip only",
                         EXIT_NO_CHIP)
    devs = jax.devices()
    if len(devs) < cell.chips:
        raise BenchError(f"cell {cell.name} needs {cell.chips} chips, JAX found {len(devs)}",
                         EXIT_NO_CHIP)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


#: the persistent cache's thresholds, as JAX and the program leave them
CACHE_THRESHOLDS = ("jax_persistent_cache_min_compile_time_secs",
                    "jax_persistent_cache_min_entry_size_bytes")
_program_thresholds: Dict[str, Any] = {}


def configure_jax() -> str:
    """Persistent compilation cache at the program's place; every program cached.

    Set-up caches even the programs that compile in under a second, so that
    a run's set-up finds all of them after the first run. The window runs
    with the program's own thresholds again (:func:`program_cache_settings`).
    """
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    for name in CACHE_THRESHOLDS:
        _program_thresholds.setdefault(name, getattr(jax.config, name))
    jax.config.update(CACHE_THRESHOLDS[0], 0)
    jax.config.update(CACHE_THRESHOLDS[1], -1)
    return where


def program_cache_settings() -> None:
    """Give the persistent cache back the thresholds the program runs with."""
    import jax

    for name, value in _program_thresholds.items():
        jax.config.update(name, value)


def reduce_per_layer(cell: Cell, run: Run, outcome: Outcome, device: Dict[str, Any]
                     ) -> Tuple[Dict[str, float], Dict[str, Any], Optional[dict]]:
    """Per-layer metric values, the trace's device fields and its breakdown."""
    from bench import trace as tr

    summary = None
    extra: Dict[str, Any] = {}
    breakdown = None
    if run.traced_window is not None:
        t0, t1 = run.traced_window
        summary = tr.summarize(tr.find_xplane(run.trace_dir), window_s=t1 - t0,
                               chips=device["count"])
        extra = {"busy_s": summary.busy_s, "window_s": summary.window_s}
        breakdown = summary.breakdown()
    obs = Observations(
        cell=cell,
        spans=run.sink.spans,
        counters=outcome.counters,
        trace=summary,
        peaks=peaks_for(device["kind"], cell.bench_dir) if device["platform"] == "tpu" else {},
        chips=device["count"],
    )
    values: Dict[str, float] = {}
    for m in cell.per_layer:
        v = load_reader(cell, m["name"])(obs)
        if v is not None:
            values[m["name"]] = float(v)
    return values, extra, breakdown


def result_line(cell: Cell, outcome: Outcome, device: Dict[str, Any],
                per_layer: Dict[str, float], breakdown: Optional[dict]) -> Dict[str, Any]:
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if cell.trace:
        values = per_layer
    else:
        values = dict(outcome.end_to_end)
        values["setup_s"] = outcome.setup_s
    line: Dict[str, Any] = {
        "correct": outcome.correct,
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in values.items() if k in units},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": c.value, "limit": c.limit} for k, c in outcome.checks.items()}
    return line


def run_cell(cell: Cell, *, t_start: float, require_tpu: bool = True,
             compile_cache: bool = True) -> Dict[str, Any]:
    """Drive ``cell`` once and return its result line (a dict).

    ``require_tpu=False`` and ``compile_cache=False`` are for rehearsals on
    the CPU, which must neither refuse to run nor fill the chip's cache.
    """
    if require_tpu:
        device = check_chips(cell)
    else:
        import jax

        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    src = str(cell.bench_dir.parent / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        raise BenchError(f"the program is not in this checkout ({e})") from e
    where = configure_jax() if compile_cache else "off"
    shutil.rmtree(cell.out_dir, ignore_errors=True)
    cell.out_dir.mkdir(parents=True, exist_ok=True)
    run = Run(cell, t_start)
    run.note(f"cell {cell.name} seed {cell.seed} seconds {cell.seconds} trace {int(cell.trace)} "
             f"on {device['count']} x {device['kind']}; compile cache {where}")
    try:
        outcome = load_driver(cell).run(cell, run)
        device["memory_peak_bytes"] = outcome.memory_peak_bytes
        per_layer, extra, breakdown = reduce_per_layer(cell, run, outcome, device)
        device.update(extra)
        run.mark("per-layer reduction")
    finally:
        run.compiles.close()
        shutil.rmtree(cell.out_dir, ignore_errors=True)
    return result_line(cell, outcome, device, per_layer, breakdown)


def parse_args(argv: List[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="bench/run.py", description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv: List[str], *, t_start: float) -> int:
    args = parse_args(argv)
    try:
        cell = find_cell(args.workload)
        cell.seed, cell.seconds, cell.trace = args.seed, args.seconds, bool(args.trace)
        line = run_cell(cell, t_start=t_start)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return e.code
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
