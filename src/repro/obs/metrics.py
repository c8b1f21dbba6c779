"""Unified metrics: one registry over push instruments and pull collectors.

Two complementary surfaces feed one snapshot:

- **Push instruments** — :class:`Counter`, :class:`Gauge`,
  :class:`Histogram` created through :class:`MetricsRegistry`; call sites
  hold the instrument and update it directly (a lock-guarded float add).
- **Pull collectors** — zero-copy adapters over the stats surfaces that
  predate this module (``Gateway.stats()``, ``Channel.stats``,
  ``ResultCache.stats`` including the tiered backend's ``remote_errors``).
  A collector is just a callable returning ``{metric_name: value}``; it is
  polled at snapshot time, so the owning objects keep their cheap ad-hoc
  dicts and nothing on their hot paths changes.

Naming scheme (docs/observability.md): ``repro_<subsystem>_<what>[_total]``
with Prometheus-style ``{label="value"}`` suffixes baked into the name.
Snapshots are plain dicts; :meth:`MetricsRegistry.to_prometheus` renders
text exposition format and :meth:`MetricsRegistry.to_json` a stable JSON
document. All timing helpers use the monotonic clock.
"""

from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Dict, Iterator, List, Mapping, Tuple

#: Default histogram bucket upper bounds, in seconds (latency-oriented).
DEFAULT_BUCKETS: Tuple[float, ...] = (
    0.001,
    0.005,
    0.01,
    0.05,
    0.1,
    0.5,
    1.0,
    5.0,
    10.0,
    30.0,
    60.0,
)


def _labeled(name: str, labels: Mapping[str, str]) -> str:
    """Render ``name{k="v",...}`` with labels sorted for determinism."""
    if not labels:
        return name
    inner = ",".join(f'{k}="{labels[k]}"' for k in sorted(labels))
    return f"{name}{{{inner}}}"


class Counter:
    """A monotonically increasing float (use ``*_total`` names)."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, n: float = 1.0) -> None:
        """Add ``n`` (must be >= 0) to the counter."""
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        """Current cumulative value."""
        return self._value


class Gauge:
    """A point-in-time float that can go up and down."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        """Replace the gauge's value."""
        with self._lock:
            self._value = float(v)

    def add(self, n: float) -> None:
        """Adjust the gauge by ``n`` (negative to decrement)."""
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        """Current value."""
        return self._value


class Histogram:
    """Bucketed distribution of observations (Prometheus-compatible)."""

    __slots__ = ("name", "buckets", "_counts", "_sum", "_count", "_lock")

    def __init__(self, name: str, buckets: Tuple[float, ...] = DEFAULT_BUCKETS):
        self.name = name
        self.buckets = tuple(sorted(buckets))
        self._counts = [0] * (len(self.buckets) + 1)  # +inf tail bucket
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        """Record one observation."""
        idx = len(self.buckets)
        for i, ub in enumerate(self.buckets):
            if v <= ub:
                idx = i
                break
        with self._lock:
            self._counts[idx] += 1
            self._sum += v
            self._count += 1

    @contextmanager
    def time(self) -> Iterator[None]:
        """Observe the monotonic duration of the ``with`` body."""
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.observe(time.monotonic() - t0)

    def snapshot(self) -> Dict[str, Any]:
        """Cumulative bucket counts plus sum/count."""
        with self._lock:
            counts = list(self._counts)
            total, s = self._count, self._sum
        cum: Dict[str, int] = {}
        acc = 0
        for ub, c in zip(self.buckets, counts, strict=False):  # counts has a +Inf slot
            acc += c
            cum[repr(ub)] = acc
        cum["+Inf"] = total
        return {"buckets": cum, "sum": s, "count": total}


class MetricsRegistry:
    """Instrument factory + collector host behind one snapshot API."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}
        self._collectors: Dict[str, Callable[[], Mapping[str, float]]] = {}

    # -- instruments --------------------------------------------------------
    def counter(self, name: str, **labels: str) -> Counter:
        """Get or create the counter ``name`` (labels baked into the name)."""
        key = _labeled(name, labels)
        with self._lock:
            inst = self._counters.get(key)
            if inst is None:
                inst = self._counters[key] = Counter(key)
        return inst

    def gauge(self, name: str, **labels: str) -> Gauge:
        """Get or create the gauge ``name``."""
        key = _labeled(name, labels)
        with self._lock:
            inst = self._gauges.get(key)
            if inst is None:
                inst = self._gauges[key] = Gauge(key)
        return inst

    def histogram(
        self, name: str, buckets: Tuple[float, ...] = DEFAULT_BUCKETS, **labels: str
    ) -> Histogram:
        """Get or create the histogram ``name``."""
        key = _labeled(name, labels)
        with self._lock:
            inst = self._histograms.get(key)
            if inst is None:
                inst = self._histograms[key] = Histogram(key, buckets)
        return inst

    @contextmanager
    def timer(self, name: str, **labels: str) -> Iterator[None]:
        """Shorthand: time the ``with`` body into histogram ``name``."""
        with self.histogram(name, **labels).time():
            yield

    # -- collectors ---------------------------------------------------------
    def register_collector(self, name: str, fn: Callable[[], Mapping[str, float]]) -> None:
        """(Re)register pull-collector ``name`` — polled at snapshot time."""
        with self._lock:
            self._collectors[name] = fn

    def unregister_collector(self, name: str) -> None:
        """Drop collector ``name``; unknown names are ignored."""
        with self._lock:
            self._collectors.pop(name, None)

    # -- export -------------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """One flat view: counters, gauges (push + polled), histograms.

        Collector failures degrade to a ``repro_collector_errors`` entry
        rather than failing the snapshot — observability must never take
        down the observed.
        """
        with self._lock:
            counters = {k: c.value for k, c in self._counters.items()}
            gauges = {k: g.value for k, g in self._gauges.items()}
            hists = {k: h.snapshot() for k, h in self._histograms.items()}
            collectors = list(self._collectors.items())
        errors = 0
        for _name, fn in collectors:
            try:
                polled = fn()
            except Exception:
                errors += 1
                continue
            for k, v in polled.items():
                if k.split("{", 1)[0].endswith("_total"):
                    counters[k] = float(v)
                else:
                    gauges[k] = float(v)
        if errors:
            gauges["repro_collector_errors"] = float(errors)
        return {"counters": counters, "gauges": gauges, "histograms": hists}

    def to_json(self) -> str:
        """The snapshot as a stable (sorted-keys) JSON document."""
        return json.dumps(self.snapshot(), sort_keys=True)

    def to_prometheus(self) -> str:
        """The snapshot in Prometheus text exposition format."""
        snap = self.snapshot()
        lines: List[str] = []
        for name in sorted(snap["counters"]):
            lines.append(f"{name} {_fmt(snap['counters'][name])}")
        for name in sorted(snap["gauges"]):
            lines.append(f"{name} {_fmt(snap['gauges'][name])}")
        for name in sorted(snap["histograms"]):
            h = snap["histograms"][name]
            base, labels = _split_labels(name)
            for ub, c in h["buckets"].items():
                le = ",".join(filter(None, [labels, f'le="{ub}"']))
                lines.append(f"{base}_bucket{{{le}}} {c}")
            suffix = f"{{{labels}}}" if labels else ""
            lines.append(f"{base}_sum{suffix} {_fmt(h['sum'])}")
            lines.append(f"{base}_count{suffix} {h['count']}")
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        """Drop every instrument and collector (test isolation)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()
            self._collectors.clear()


def _fmt(v: float) -> str:
    """Integers render bare; floats keep their repr."""
    return str(int(v)) if float(v).is_integer() else repr(float(v))


def _split_labels(key: str) -> Tuple[str, str]:
    """Split ``name{a="b"}`` into (``name``, ``a="b"``)."""
    if "{" not in key:
        return key, ""
    base, _, rest = key.partition("{")
    return base, rest.rstrip("}")


# -- collectors over the pre-existing stats surfaces -------------------------


def gateway_collector(gateway: Any) -> Callable[[], Dict[str, float]]:
    """Adapter over ``Gateway.stats()`` / ``ShardedGateway.stats()``.

    Emits gateway-level gauges, the cumulative ``metrics`` dict as
    ``repro_gateway_<key>_total`` counters, and per-worker gauges labeled
    ``{worker="..."}``.
    """

    def collect() -> Dict[str, float]:
        stats = gateway.stats()
        out: Dict[str, float] = {
            "repro_gateway_queue_depth": float(stats.get("queue_depth", 0)),
            "repro_gateway_silo_depth": float(stats.get("silo_depth", 0)),
            "repro_gateway_live_workers": float(stats.get("live_workers", 0)),
            "repro_gateway_suspended_runs": float(len(stats.get("suspended_runs") or ())),
            "repro_gateway_mean_alloc_us": float(stats.get("mean_alloc_us", 0.0)),
        }
        for key, val in (stats.get("metrics") or {}).items():
            out[f"repro_gateway_{key}_total"] = float(val)
        for wname, w in (stats.get("workers") or {}).items():
            lab = {"worker": wname}
            out[_labeled("repro_worker_live", lab)] = 1.0 if w.get("live") else 0.0
            out[_labeled("repro_worker_inflight", lab)] = float(w.get("inflight", 0))
            out[_labeled("repro_worker_completed_total", lab)] = float(w.get("completed", 0))
            out[_labeled("repro_worker_hb_misses", lab)] = float(w.get("hb_misses", 0))
            out[_labeled("repro_worker_ewma_latency_s", lab)] = float(
                w.get("ewma_latency_s", 0.0)
            )
        return out

    return collect


def cache_collector(cache: Any) -> Callable[[], Dict[str, float]]:
    """Adapter over ``ResultCache.stats`` plus tiered-backend counters.

    Surfaces the tiered backend's ``remote_hits``/``promotions``/
    ``remote_errors`` when the cache has one, so a lossy shared tier is
    visible without any cache-side changes.
    """

    def collect() -> Dict[str, float]:
        out = {f"repro_cache_{k}_total": float(v) for k, v in cache.stats.items()}
        backend = getattr(cache, "backend", None)
        for attr in ("remote_hits", "promotions", "remote_errors"):
            if hasattr(backend, attr):
                out[f"repro_cache_{attr}_total"] = float(getattr(backend, attr))
        if hasattr(backend, "corrupt_drops"):
            out["repro_cache_corrupt_drops_total"] = float(backend.corrupt_drops)
        return out

    return collect


def channel_collector(channel: Any, name: str) -> Callable[[], Dict[str, float]]:
    """Adapter over a stream ``Channel.stats`` dict (incl. ``put_blocked_s``)."""

    def collect() -> Dict[str, float]:
        lab = {"channel": name}
        out: Dict[str, float] = {}
        for key, val in channel.stats.items():
            suffix = "_total" if key in ("puts", "gets", "dropped") else ""
            out[_labeled(f"repro_channel_{key}{suffix}", lab)] = float(val)
        out[_labeled("repro_channel_depth", lab)] = float(channel.depth())
        return out

    return collect


_REGISTRY = MetricsRegistry()


def metrics() -> MetricsRegistry:
    """The process-global registry (stable singleton — cache it freely)."""
    return _REGISTRY


def reset_metrics() -> None:
    """Clear the global registry (test isolation helper)."""
    _REGISTRY.reset()


__all__ = [
    "DEFAULT_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "cache_collector",
    "channel_collector",
    "gateway_collector",
    "metrics",
    "reset_metrics",
]
