"""Unified telemetry: run-wide spans, a metrics registry and ONE event
pipeline.

Every layer emits into this subsystem and every tool reads from it:

- :mod:`.events` — the single ``emit()`` every JSONL record flows
  through (streams: failure/serve/validate/telemetry; legacy
  ``HETU_FAILURE_LOG``-style sinks plus the merged
  ``$HETU_TELEMETRY_LOG``), ``span()`` context managers, and the
  event-shape contract.
- :mod:`.metrics` — thread-safe counters/gauges/histograms behind
  ``snapshot()``.
- :mod:`.slo` — declarative serving SLOs (TTFT / per-stream tok/s)
  with sliding-window burn rates behind the engine's ``health()``.
- :mod:`.flight` — the chaos flight recorder: an always-on bounded
  ring of recent records dumped to ``$HETU_FLIGHT_LOG`` on faults.
- :mod:`.trace` — merge/tail the streams, export Perfetto traces
  (``bin/hetu_trace.py``); request-lifecycle tracks + counter tracks.
- :mod:`.top` — the live terminal dashboard (``bin/hetu_top.py``).

``HETU_TELEMETRY=0`` turns spans and metric recording into no-ops, and
leaves the compile watch (``hetu_tpu/compile_cache.py``: what JAX
traced, lowered, compiled or loaded, as ``compile`` records and
``compile.*`` counters) uninstalled.
"""

from . import flight, metrics, slo, top, trace  # noqa: F401
from .events import (  # noqa: F401
    REQUIRED_FIELDS, STREAMS, TelemetrySink, counter, emit, enabled,
    gauge, get_sink, histogram, inc, make_record, observe, open_spans,
    reset, set_gauge, snapshot, span, spanned, spanned_calls,
    validate_record,
)
from .metrics import REGISTRY, percentile  # noqa: F401
from .. import compile_cache  # noqa: E402  (it records through .events)

compile_cache.watch()

__all__ = [
    "REQUIRED_FIELDS", "STREAMS", "REGISTRY", "TelemetrySink",
    "counter", "emit", "enabled", "flight", "gauge", "get_sink",
    "histogram", "inc", "make_record", "metrics", "observe",
    "open_spans", "percentile", "reset", "set_gauge", "slo", "snapshot",
    "span", "spanned", "spanned_calls", "top", "trace",
    "validate_record",
]
