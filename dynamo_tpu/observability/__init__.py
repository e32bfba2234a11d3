"""Unified engine telemetry plane.

Two surfaces over the same worker internals:

- :mod:`metrics` — ``EngineMetrics``: the Prometheus registry for engine
  layers (step composition, page pool, prefill queue, KV transfer), plus
  text federation so the frontend's ``/metrics`` can serve every worker's
  registry as one document.
- :mod:`service` — runtime-transport endpoints (``debug_traces``,
  ``metrics_scrape``) that make every worker's span ring and registry
  remotely queryable, the fan-out client, and the timeline assembler behind
  ``GET /debug/traces/{request_id}``.
- :mod:`http` — the optional per-worker debug HTTP surface (``/metrics``,
  ``/debug/traces/{request_id}``, ``/debug/incidents``) for scraping workers
  directly.
- :mod:`incidents` — capture-on-anomaly black-box bundles: a size-capped
  on-disk store of flight/span/loss snapshots written at anomaly rising
  edges, engine-step crashes, and SLO burn-rate alerts.
"""

from dynamo_tpu.observability.anomaly import ANOMALY_KINDS, AnomalySentinel
from dynamo_tpu.observability.compile import CompileTracker, timed_dispatch
from dynamo_tpu.observability.flight import FlightRecorder
from dynamo_tpu.observability.incidents import (
    INCIDENT_KINDS,
    IncidentCapture,
    IncidentStore,
)
from dynamo_tpu.observability.metrics import EngineMetrics, federate_text, observe_kv_phase
from dynamo_tpu.observability.service import (
    DEBUG_EXPLAIN_ENDPOINT,
    DEBUG_INCIDENTS_ENDPOINT,
    DEBUG_TRACES_ENDPOINT,
    FLIGHT_ENDPOINT,
    METRICS_SCRAPE_ENDPOINT,
    PROFILE_ENDPOINT,
    ExplainQueryService,
    FlightQueryService,
    IncidentQueryService,
    MetricsScrapeService,
    ProfileCaptureService,
    SpanQueryService,
    WorkerTelemetryClient,
    assemble_timeline,
)
from dynamo_tpu.observability.slo import ALERT_KINDS, SloAccountant, StreamingQuantiles

__all__ = [
    "ANOMALY_KINDS",
    "ALERT_KINDS",
    "AnomalySentinel",
    "CompileTracker",
    "timed_dispatch",
    "FlightRecorder",
    "INCIDENT_KINDS",
    "IncidentCapture",
    "IncidentStore",
    "EngineMetrics",
    "federate_text",
    "observe_kv_phase",
    "PROFILE_ENDPOINT",
    "ProfileCaptureService",
    "DEBUG_EXPLAIN_ENDPOINT",
    "DEBUG_INCIDENTS_ENDPOINT",
    "DEBUG_TRACES_ENDPOINT",
    "FLIGHT_ENDPOINT",
    "METRICS_SCRAPE_ENDPOINT",
    "ExplainQueryService",
    "FlightQueryService",
    "IncidentQueryService",
    "MetricsScrapeService",
    "SpanQueryService",
    "WorkerTelemetryClient",
    "assemble_timeline",
    "SloAccountant",
    "StreamingQuantiles",
    "LOSS_CAUSES",
    "build_explain",
]


def __getattr__(name):
    # attribution imports engine.core (for the pinned BARRIER_REASONS), and
    # engine.core imports this package's flight module at import time — so
    # the attribution symbols resolve lazily to keep the package importable
    # from either side.
    if name in ("LOSS_CAUSES", "EXTRA_LOSS_CAUSES", "build_explain"):
        from dynamo_tpu.observability import attribution

        return getattr(attribution, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
