"""Per-worker debug HTTP surface: GET /metrics + GET /debug/traces/{id}.

Workers normally expose telemetry only over the runtime transport
(``observability/service.py``), federated through the frontend. For direct
Prometheus scraping of a worker — or poking a worker without a frontend —
launch enables this tiny aiohttp server when ``DYN_WORKER_HTTP_PORT`` is set
(0 picks a free port; the chosen port is logged).
"""

from __future__ import annotations

import logging

from aiohttp import web

from dynamo_tpu.observability.metrics import EngineMetrics

logger = logging.getLogger(__name__)

WORKER_HTTP_ENV = "DYN_WORKER_HTTP_PORT"


class WorkerDebugServer:
    def __init__(
        self, metrics: EngineMetrics, *, flight=None, incidents=None
    ) -> None:
        self.metrics = metrics
        self.flight = flight  # this worker's FlightRecorder, if it has one
        self.incidents = incidents  # this worker's IncidentStore, if it has one
        self._runner: web.AppRunner | None = None
        self.port: int | None = None
        self.app = web.Application()
        self.app.add_routes(
            [
                web.get("/metrics", self.prometheus),
                web.get("/debug/traces/{request_id}", self.traces),
                web.get("/debug/flight", self.flight_dump),
                web.get("/debug/incidents", self.incidents_list),
                web.get("/debug/incidents/{incident_id}", self.incident_get),
            ]
        )

    async def prometheus(self, request: web.Request) -> web.Response:
        return web.Response(body=await self.metrics.render(), content_type="text/plain")

    async def traces(self, request: web.Request) -> web.Response:
        from dynamo_tpu.observability.service import assemble_timeline
        from dynamo_tpu.tracing import SPANS

        rid = request.match_info["request_id"]
        spans = SPANS.query(request_id=rid)
        if not spans:
            spans = SPANS.query(trace_id=rid)  # accept a trace_id too
        return web.json_response(assemble_timeline(rid, spans))

    async def flight_dump(self, request: web.Request) -> web.Response:
        if self.flight is None:
            return web.json_response({"error": "no flight recorder on this worker"}, status=404)
        last = request.query.get("last")
        records = self.flight.snapshot(
            last=int(last) if last else None, kind=request.query.get("kind")
        )
        return web.json_response({"records": records, "count": len(records)})

    async def incidents_list(self, request: web.Request) -> web.Response:
        if self.incidents is None:
            return web.json_response({"error": "no incident store on this worker"}, status=404)
        items = self.incidents.list()
        return web.json_response({"count": len(items), "incidents": items})

    async def incident_get(self, request: web.Request) -> web.Response:
        if self.incidents is None:
            return web.json_response({"error": "no incident store on this worker"}, status=404)
        incident_id = request.match_info["incident_id"]
        bundle = self.incidents.get(incident_id)
        if bundle is None:
            return web.json_response({"error": f"no incident {incident_id!r}"}, status=404)
        return web.json_response(bundle)

    async def start(self, host: str = "0.0.0.0", port: int = 0) -> int:
        self._runner = web.AppRunner(self.app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        self.port = self._runner.addresses[0][1] if self._runner.addresses else port
        logger.info("worker debug HTTP on %s:%d", host, self.port)
        return self.port

    async def close(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None
