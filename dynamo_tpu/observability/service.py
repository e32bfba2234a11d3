"""Worker telemetry endpoints + the frontend-side fan-out client.

Every worker serves extra runtime endpoints next to ``generate``:

- ``debug_traces`` (:class:`SpanQueryService`) — query the process-local
  span ring (``tracing.SPANS``) by request or trace id;
- ``metrics_scrape`` (:class:`MetricsScrapeService`) — render the process's
  :class:`~dynamo_tpu.observability.metrics.EngineMetrics` registry;
- ``debug_flight`` (:class:`FlightQueryService`) — the engine flight ring;
- ``debug_explain`` (:class:`ExplainQueryService`) — windowed STEP/COMPILE
  records + lost-time totals, the worker half of
  ``GET /debug/explain/{request_id}`` (``attribution.build_explain``);
- ``debug_incidents`` (:class:`IncidentQueryService`) — the worker's
  on-disk incident bundles (``observability/incidents.py``), the worker
  half of ``GET /debug/incidents[/{id}]``;
- ``debug_profile`` (:class:`ProfileCaptureService`) — arms a bounded
  ``jax.profiler`` device trace on the worker, the worker half of
  ``POST /debug/profile/{worker}``.

They ride the same discovery + stream transport as serving traffic, so the
frontend needs no extra connectivity to reach them:
:class:`WorkerTelemetryClient` scans the ``instances/`` prefix for telemetry
endpoints and fans a query out to every live worker.
:func:`assemble_timeline` merges the union of span docs (frontend-local +
every worker's) into one ordered timeline — the body of
``GET /debug/traces/{request_id}``.
"""

from __future__ import annotations

import asyncio
import logging
import os
import time
from typing import Any, AsyncIterator

from dynamo_tpu.runtime.component import INSTANCE_PREFIX, DistributedRuntime, Instance
from dynamo_tpu.runtime.engine import AsyncEngine, Context

logger = logging.getLogger(__name__)

DEBUG_TRACES_ENDPOINT = "debug_traces"
METRICS_SCRAPE_ENDPOINT = "metrics_scrape"
FLIGHT_ENDPOINT = "debug_flight"
DEBUG_EXPLAIN_ENDPOINT = "debug_explain"
DEBUG_INCIDENTS_ENDPOINT = "debug_incidents"
PROFILE_ENDPOINT = "debug_profile"

_FANOUT_TIMEOUT = 5.0


class SpanQueryService(AsyncEngine[Any, dict]):
    """Answers ``{"request_id"?, "trace_id"?}`` with this process's spans."""

    def __init__(self, *, host: str = "") -> None:
        self.host = host or f"pid-{os.getpid()}"

    async def generate(self, request: Any, context: Context) -> AsyncIterator[dict]:
        from dynamo_tpu.tracing import SPANS

        request = request or {}
        spans = SPANS.query(
            request_id=request.get("request_id"), trace_id=request.get("trace_id")
        )
        yield {"host": self.host, "spans": spans}


class MetricsScrapeService(AsyncEngine[Any, dict]):
    """Answers any request with the worker's rendered Prometheus text."""

    def __init__(self, metrics) -> None:
        self.metrics = metrics

    async def generate(self, request: Any, context: Context) -> AsyncIterator[dict]:
        yield {"text": (await self.metrics.render()).decode()}


class FlightQueryService(AsyncEngine[Any, dict]):
    """Answers ``{"last"?: N, "kind"?: str}`` with this worker's flight ring.

    ``worker`` is the engine worker id the frontend addresses
    (``GET /debug/flight/{worker}``) — the client fans out to every flight
    endpoint and filters on this field, so no instance-id mapping is needed.
    """

    def __init__(self, flight, *, worker: str = "") -> None:
        self.flight = flight
        self.worker = worker or f"pid-{os.getpid()}"

    async def generate(self, request: Any, context: Context) -> AsyncIterator[dict]:
        request = request or {}
        last = request.get("last")
        records = self.flight.snapshot(
            last=int(last) if last is not None else None,
            kind=request.get("kind"),
        )
        yield {"worker": self.worker, "records": records}


class ExplainQueryService(AsyncEngine[Any, dict]):
    """Answers ``{"t0"?, "t1"?}`` with this worker's attribution inputs.

    Returns the flight ring's STEP/COMPILE records (optionally windowed to
    ``[t0, t1]`` wall-clock seconds — the frontend passes the request's span
    bounds so the payload stays proportional to the request, not the ring)
    plus the engine's cumulative per-cause lost-time totals. The per-request
    join happens on the frontend (``attribution.build_explain``): flight
    records carry no request ids, so windowing is the only per-request cut a
    worker can make.
    """

    def __init__(self, core, *, worker: str = "") -> None:
        self.core = core
        self.worker = worker or f"pid-{os.getpid()}"

    async def generate(self, request: Any, context: Context) -> AsyncIterator[dict]:
        from dynamo_tpu.config import load_attrib_settings
        from dynamo_tpu.observability.flight import COMPILE, STEP

        request = request or {}
        t0 = request.get("t0")
        t1 = request.get("t1")

        def in_window(rec: dict) -> bool:
            ts = rec.get("ts") or 0.0
            return (t0 is None or ts >= float(t0)) and (t1 is None or ts <= float(t1))

        max_steps = load_attrib_settings().max_steps
        steps = [r for r in self.core.flight.snapshot(kind=STEP) if in_window(r)]
        compiles = [r for r in self.core.flight.snapshot(kind=COMPILE) if in_window(r)]
        yield {
            "worker": self.worker,
            "steps": steps[-max_steps:],
            "compiles": compiles,
            "lost_time_ms": {
                k: round(v, 3)
                for k, v in (getattr(self.core, "lost_time_ms", None) or {}).items()
            },
        }


class IncidentQueryService(AsyncEngine[Any, dict]):
    """Answers ``{"id"?: str}`` with this worker's incident bundles.

    Without an id: bundle summaries (the store's ``list()`` view). With an
    id: the full bundle, or ``{"found": False}`` when it isn't here — the
    frontend fans the id out to every worker and keeps the one that has it.
    """

    def __init__(self, store, *, worker: str = "") -> None:
        self.store = store
        self.worker = worker or f"pid-{os.getpid()}"

    async def generate(self, request: Any, context: Context) -> AsyncIterator[dict]:
        request = request or {}
        incident_id = request.get("id")
        if incident_id:
            bundle = self.store.get(str(incident_id))
            yield {"worker": self.worker, "found": bundle is not None, "bundle": bundle}
        else:
            yield {"worker": self.worker, "incidents": self.store.list()}


class ProfileCaptureService(AsyncEngine[Any, dict]):
    """Arms a bounded ``jax.profiler`` device trace on this worker.

    ``{"action": "status"}`` (or an empty request) reports availability and
    whether a trace is currently running. ``{"action": "capture",
    "duration_ms": N}`` traces the next N ms of device work (clamped to
    ``DYN_PROFILE_MAX_MS``) and returns the artifact directory plus a file
    summary. Single-flight is inherited from ``tracing.start_device_trace``
    — a second capture while one runs gets ``{"ok": False, "reason":
    "busy"}`` instead of queueing (profiles are operator actions; queueing
    them would silently serialize minutes of tracing). Refuses politely
    with ``reason: "profiler_unavailable"`` where ``jax.profiler`` cannot
    start a trace (e.g. stripped builds).
    """

    DEFAULT_DURATION_MS = 2000.0

    def __init__(self, *, worker: str = "") -> None:
        self.worker = worker or f"pid-{os.getpid()}"

    def _status(self) -> dict:
        from dynamo_tpu.tracing import (
            profile_artifact_dir,
            profile_max_ms,
            profiler_available,
            trace_running,
        )

        return {
            "worker": self.worker,
            "available": profiler_available(),
            "running": trace_running(),
            "artifact_dir": profile_artifact_dir(),
            "max_duration_ms": profile_max_ms(),
        }

    async def generate(self, request: Any, context: Context) -> AsyncIterator[dict]:
        from dynamo_tpu.tracing import (
            profile_artifact_dir,
            profile_for,
            profile_max_ms,
            profiler_available,
        )

        request = request or {}
        if request.get("action", "status") != "capture":
            yield self._status()
            return
        status = self._status()
        if not profiler_available():
            yield {**status, "ok": False, "reason": "profiler_unavailable"}
            return
        try:
            duration_ms = float(request.get("duration_ms") or self.DEFAULT_DURATION_MS)
        except (TypeError, ValueError):
            duration_ms = self.DEFAULT_DURATION_MS
        duration_ms = max(1.0, min(duration_ms, profile_max_ms()))
        log_dir = os.path.join(
            profile_artifact_dir(), f"{self.worker}-{int(time.time() * 1e3)}"
        )
        try:
            artifact = await profile_for(duration_ms / 1e3, log_dir)
        except Exception as exc:
            yield {
                **status, "ok": False, "reason": "capture_failed",
                "error": type(exc).__name__, "detail": str(exc)[:200],
            }
            return
        if artifact is None:
            yield {**status, "ok": False, "reason": "busy"}
            return
        files = []
        total_bytes = 0
        for root, _dirs, names in os.walk(artifact):
            for name in names:
                path = os.path.join(root, name)
                try:
                    total_bytes += os.path.getsize(path)
                except OSError:
                    continue
                files.append(os.path.relpath(path, artifact))
        yield {
            **status, "ok": True, "artifact": artifact,
            "duration_ms": duration_ms,
            "files": sorted(files)[:50], "file_count": len(files),
            "total_bytes": total_bytes,
        }


class WorkerTelemetryClient:
    """Frontend-side fan-out over every worker's telemetry endpoints.

    Discovery is a prefix scan per query (telemetry is off the request hot
    path; a live watch would be over-engineering): any instance record whose
    endpoint name matches is a target. Dead workers drop out with their
    lease like any other instance.
    """

    def __init__(self, runtime: DistributedRuntime, *, timeout: float = _FANOUT_TIMEOUT) -> None:
        self.runtime = runtime
        self.timeout = timeout
        #: Per-worker failed fan-out calls (dynamo_federation_scrape_failures_total).
        #: A failure here means the federated /metrics silently lost that
        #: worker's registry — which is exactly why it is counted.
        self.scrape_failures: dict[str, int] = {}
        #: The most recent failure, for the control tower: worker/error/ts.
        self.last_failure: dict[str, Any] | None = None

    async def _targets(self, endpoint: str) -> list[Instance]:
        records = await self.runtime.store.get_prefix(f"{INSTANCE_PREFIX}/")
        out = []
        for value in records.values():
            try:
                inst = Instance.from_bytes(value)
            except Exception:
                continue
            if inst.endpoint == endpoint:
                out.append(inst)
        return out

    async def _ask(self, inst: Instance, request: dict) -> dict | None:
        async def first() -> dict | None:
            stream = self.runtime.transport.generate(inst.address, request, Context())
            try:
                async for item in stream:
                    return item
                return None
            finally:
                await stream.aclose()

        try:
            return await asyncio.wait_for(first(), self.timeout)
        except Exception as exc:
            worker = f"{inst.instance_id:x}"
            self.scrape_failures[worker] = self.scrape_failures.get(worker, 0) + 1
            self.last_failure = {
                "worker": worker,
                "endpoint": inst.endpoint,
                "error": type(exc).__name__,
                "detail": str(exc)[:200],
                "ts": time.time(),
            }
            logger.warning("telemetry query to %s failed", worker, exc_info=True)
        return None

    async def collect_spans(self, *, request_id: str | None = None, trace_id: str | None = None) -> list[dict]:
        """The union of matching span docs across every live worker."""
        targets = await self._targets(DEBUG_TRACES_ENDPOINT)
        if not targets:
            return []
        results = await asyncio.gather(
            *(self._ask(t, {"request_id": request_id, "trace_id": trace_id}) for t in targets)
        )
        spans: list[dict] = []
        for inst, res in zip(targets, results):
            if res is None:
                continue
            for s in res.get("spans", []):
                s.setdefault("host", res.get("host", f"{inst.instance_id:x}"))
                spans.append(s)
        return spans

    async def collect_flight(
        self, *, worker: str | None = None, last: int | None = None, kind: str | None = None
    ) -> dict[str, list[dict]]:
        """Flight rings by worker id; ``worker`` filters to one (or ``"all"``/
        ``None`` for every worker)."""
        targets = await self._targets(FLIGHT_ENDPOINT)
        request: dict = {}
        if last is not None:
            request["last"] = last
        if kind is not None:
            request["kind"] = kind
        results = await asyncio.gather(*(self._ask(t, request) for t in targets))
        out: dict[str, list[dict]] = {}
        for inst, res in zip(targets, results):
            if res is None:
                continue
            wid = str(res.get("worker", f"{inst.instance_id:x}"))
            if worker not in (None, "all") and wid != worker:
                continue
            out[wid] = res.get("records", [])
        return out

    async def collect_explain(
        self, *, t0: float | None = None, t1: float | None = None
    ) -> list[dict]:
        """Every worker's windowed attribution inputs (steps + compiles)."""
        targets = await self._targets(DEBUG_EXPLAIN_ENDPOINT)
        request: dict = {}
        if t0 is not None:
            request["t0"] = t0
        if t1 is not None:
            request["t1"] = t1
        results = await asyncio.gather(*(self._ask(t, request) for t in targets))
        docs = []
        for inst, res in zip(targets, results):
            if res is None:
                continue
            res.setdefault("worker", f"{inst.instance_id:x}")
            docs.append(res)
        return docs

    async def collect_metrics_texts(self) -> list[bytes]:
        """Every worker's rendered registry (for /metrics federation)."""
        targets = await self._targets(METRICS_SCRAPE_ENDPOINT)
        results = await asyncio.gather(*(self._ask(t, {}) for t in targets))
        return [r["text"].encode() for r in results if r and "text" in r]

    async def collect_incidents(self) -> dict[str, list[dict]]:
        """Bundle summaries by worker id (the /debug/incidents listing)."""
        targets = await self._targets(DEBUG_INCIDENTS_ENDPOINT)
        results = await asyncio.gather(*(self._ask(t, {}) for t in targets))
        out: dict[str, list[dict]] = {}
        for inst, res in zip(targets, results):
            if res is None:
                continue
            wid = str(res.get("worker", f"{inst.instance_id:x}"))
            out[wid] = res.get("incidents", [])
        return out

    async def profile_status(self, worker: str | None = None) -> dict[str, dict]:
        """Profile-capture availability by worker id (GET /debug/profile)."""
        targets = await self._targets(PROFILE_ENDPOINT)
        results = await asyncio.gather(
            *(self._ask(t, {"action": "status"}) for t in targets)
        )
        out: dict[str, dict] = {}
        for inst, res in zip(targets, results):
            if res is None:
                continue
            wid = str(res.pop("worker", f"{inst.instance_id:x}"))
            if worker not in (None, "all") and wid != worker:
                continue
            out[wid] = res
        return out

    async def capture_profile(self, worker: str, duration_ms: float) -> dict | None:
        """Arm a device trace on one worker; returns its capture doc.

        The capture blocks for the trace window, so the fan-out timeout is
        stretched to cover the requested duration plus generous slack: on a
        busy worker the service coroutine may not even be scheduled for
        seconds (synchronous jit dispatches block the loop), and a timeout
        here cancels the trace mid-window.
        """
        targets = await self._targets(PROFILE_ENDPOINT)
        saved_timeout = self.timeout
        self.timeout = max(saved_timeout, duration_ms / 1e3 + 60.0)
        try:
            for inst in targets:
                status = await self._ask(inst, {"action": "status"})
                if status is None:
                    continue
                wid = str(status.get("worker", f"{inst.instance_id:x}"))
                if wid != worker:
                    continue
                return await self._ask(
                    inst, {"action": "capture", "duration_ms": duration_ms}
                )
            return None
        finally:
            self.timeout = saved_timeout

    async def fetch_incident(self, incident_id: str) -> dict | None:
        """The full bundle for one id, from whichever worker holds it."""
        targets = await self._targets(DEBUG_INCIDENTS_ENDPOINT)
        results = await asyncio.gather(
            *(self._ask(t, {"id": incident_id}) for t in targets)
        )
        for res in results:
            if res and res.get("found"):
                return res.get("bundle")
        return None


def assemble_timeline(request_id: str, spans: list[dict]) -> dict:
    """One ordered timeline from the union of span docs.

    Spans from different processes share a trace_id but not a monotonic
    clock, so ordering uses the wall-clock ``start_ts``; ``offset_ms`` is
    relative to the earliest span (queue wait → router decision → prefill →
    KV phases → first decode step read top to bottom). ``children`` indexes
    restore the parent/child structure where ids link up. A span whose
    parent was evicted from the ring (span buffers are bounded) still
    surfaces at top level, flagged ``parent_evicted: true`` — orphans must
    never silently vanish from a postmortem.
    """
    spans = sorted(spans, key=lambda s: (s.get("start_ts") or 0.0, s.get("duration_ms") or 0.0))
    t0 = spans[0].get("start_ts", 0.0) if spans else 0.0
    by_id = {s.get("span_id"): i for i, s in enumerate(spans) if s.get("span_id")}
    out_spans = []
    for i, s in enumerate(spans):
        doc = dict(s)
        doc["offset_ms"] = round(((s.get("start_ts") or t0) - t0) * 1e3, 3)
        doc["children"] = [
            j for j, c in enumerate(spans) if c.get("parent_id") and c["parent_id"] == s.get("span_id")
        ]
        doc["root"] = s.get("parent_id") not in by_id or s.get("parent_id") is None
        if s.get("parent_id") is not None and s.get("parent_id") not in by_id:
            doc["parent_evicted"] = True
        out_spans.append(doc)
    trace_ids = sorted({s["trace_id"] for s in spans if s.get("trace_id")})
    return {
        "request_id": request_id,
        "trace_ids": trace_ids,
        "span_count": len(out_spans),
        "duration_ms": round(
            max(
                (s["offset_ms"] + (s.get("duration_ms") or 0.0) for s in out_spans),
                default=0.0,
            ),
            3,
        ),
        "spans": out_spans,
    }
