"""Observability layer: distributed tracing, unified metrics, run timelines.

The paper's thesis is context-aware execution — ``repro.obs`` turns that
same context machinery into the observability substrate:

- :mod:`repro.obs.trace` — ``Span``/``Tracer``. Trace identity rides the
  run's Ψ context as a reserved ``obs.``-prefixed fact, so spans nest
  correctly across the gateway→worker hop (in-process and HTTP) and
  across ``ShardedGateway`` handoffs, with zero transport changes. Off by
  default; a disabled tracer is one attribute read per call site.
- :mod:`repro.obs.metrics` — ``MetricsRegistry`` with counters, gauges
  and histograms plus pull-collectors that absorb the pre-existing
  ad-hoc stats surfaces (``Gateway.stats()``, ``Channel.stats``,
  ``ResultCache.stats``) behind one snapshot API with Prometheus text
  and JSON export.
- :mod:`repro.obs.sinks` — span sinks (in-memory ring, JSONL file) and
  the Chrome-trace/Perfetto exporter.
- :mod:`repro.obs.timeline` — per-node timeline + critical path
  reconstructed post-hoc from a journal (compacted or not), optionally
  enriched by a span log; backs ``python -m repro trace``.

Attribute access is lazy: ``repro.obs`` sits *below* ``repro.core`` and
``repro.stream`` in the import graph (both instrument through it), so the
package must not eagerly import submodules that reach back up into them.

See docs/observability.md for the span model and propagation contract.
"""

import importlib

_EXPORTS = {
    "Span": "trace",
    "Tracer": "trace",
    "current_span": "trace",
    "extract_trace": "trace",
    "get_tracer": "trace",
    "inject_trace": "trace",
    "jax_counts": "trace",
    "strip_trace": "trace",
    "Counter": "metrics",
    "Gauge": "metrics",
    "Histogram": "metrics",
    "MetricsRegistry": "metrics",
    "cache_collector": "metrics",
    "channel_collector": "metrics",
    "gateway_collector": "metrics",
    "reset_metrics": "metrics",
    "JsonlSink": "sinks",
    "RingSink": "sinks",
    "chrome_trace": "sinks",
    "read_spans": "sinks",
    "write_chrome_trace": "sinks",
    "NodeTiming": "timeline",
    "Timeline": "timeline",
}

__all__ = ["trace", "metrics", "sinks", "timeline", *sorted(_EXPORTS)]


def __getattr__(name):
    """Resolve exported names (and submodules) on first access."""
    if name in ("trace", "metrics", "sinks", "timeline"):
        return importlib.import_module(f"repro.obs.{name}")
    try:
        module = _EXPORTS[name]
    except KeyError:
        raise AttributeError(f"module 'repro.obs' has no attribute {name!r}") from None
    return getattr(importlib.import_module(f"repro.obs.{module}"), name)
