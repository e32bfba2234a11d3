"""The OpenAI-compatible aiohttp service.

Routes (parity: reference `http/service/openai.rs`, `health.rs`,
`metrics.rs`, `clear_kv_blocks.rs`):

- POST /v1/chat/completions — streaming (SSE) and aggregated
- POST /v1/completions
- GET  /v1/models
- GET  /health, /live
- GET  /metrics — Prometheus text (frontend registry + federated worker
  EngineMetrics registries, when a telemetry client is wired)
- GET  /debug/traces/{request_id} — the assembled distributed timeline for
  one request (local spans + fan-out to every worker's span ring)
- POST /clear_kv_blocks — admin: drop prefix caches on all workers

Distributed tracing starts here: an incoming W3C ``traceparent`` header is
ingested (or a fresh trace minted), a root ``http_request`` span wraps the
request, and its context rides the per-request ``Context`` through every
pipeline stage and process hop.

Client disconnects cancel generation: the per-request Context is killed when
the response write fails or the request is torn down, and that propagates
through the pipeline to the engine scheduler.
"""

from __future__ import annotations

import asyncio
import logging
import sys
import time
from typing import Any, AsyncIterator, Awaitable, Callable

from aiohttp import web

from dynamo_tpu.frontend.metrics import FrontendMetrics
from dynamo_tpu.frontend.model_manager import ModelManager
from dynamo_tpu.frontend.openai_format import (
    SSE_DONE,
    ChatStream,
    CompletionStream,
    aggregate_chat,
    aggregate_completion,
    sse_encode,
)
from dynamo_tpu.protocols.common import BackendOutput, FinishReason
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.tracing import Span, TraceContext, record_span

logger = logging.getLogger(__name__)


def _error(status: int, message: str, etype: str = "invalid_request_error") -> web.Response:
    return web.json_response({"error": {"message": message, "type": etype}}, status=status)


#: The structured SSE event a client sees when the engine dies mid-stream —
#: OpenAI error shape, no traceback, followed by [DONE] and a clean close.
_ENGINE_ERROR_EVENT = {
    "error": {
        "message": "the engine failed while generating this response",
        "type": "engine_error",
        "code": "mid_stream_failure",
    }
}


class HttpService:
    def __init__(
        self,
        manager: ModelManager,
        *,
        metrics: FrontendMetrics | None = None,
        clear_kv_hook: Callable[[], Awaitable[int]] | None = None,
        telemetry: Any = None,
    ) -> None:
        self.manager = manager
        self.metrics = metrics or FrontendMetrics()
        self.clear_kv_hook = clear_kv_hook
        # WorkerTelemetryClient (observability/service.py): fans /metrics and
        # /debug/traces queries out to every live worker. None on frontends
        # with no runtime wired (unit tests) — both routes degrade to
        # frontend-local data.
        self.telemetry = telemetry
        self._runner: web.AppRunner | None = None
        self.app = web.Application()
        self.app.add_routes(
            [
                web.post("/v1/chat/completions", self.chat_completions),
                web.post("/v1/completions", self.completions),
                web.post("/v1/embeddings", self.embeddings),
                web.get("/v1/models", self.list_models),
                web.get("/health", self.health),
                web.get("/live", self.live),
                web.get("/metrics", self.prometheus),
                web.get("/debug/traces/{request_id}", self.debug_traces),
                web.get("/debug/explain/{request_id}", self.debug_explain),
                web.get("/debug/flight/{worker}", self.debug_flight),
                web.get("/debug/profile/{worker}", self.debug_profile_status),
                web.post("/debug/profile/{worker}", self.debug_profile_capture),
                web.get("/debug/incidents", self.debug_incidents),
                web.get("/debug/incidents/{incident_id}", self.debug_incident),
                web.get("/debug/federation", self.debug_federation),
                web.get("/debug/store", self.debug_store),
                web.post("/clear_kv_blocks", self.clear_kv_blocks),
            ]
        )

    # -- lifecycle ---------------------------------------------------------

    async def start(self, host: str = "0.0.0.0", port: int = 8080) -> int:
        self._runner = web.AppRunner(self.app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        actual = self._runner.addresses[0][1] if self._runner.addresses else port
        logger.info("HTTP frontend listening on %s:%d", host, actual)
        return actual

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    # -- OpenAI endpoints --------------------------------------------------

    async def chat_completions(self, request: web.Request) -> web.StreamResponse:
        return await self._serve_openai(request, kind="chat")

    async def completions(self, request: web.Request) -> web.StreamResponse:
        return await self._serve_openai(request, kind="completions")

    async def embeddings(self, request: web.Request) -> web.Response:
        """OpenAI /v1/embeddings: input = str | [str] | [int] | [[int]].

        Parity: `lib/llm/src/http/service/openai.rs:580`. Each input runs
        through the same preprocessor -> router -> worker pipeline as chat
        (annotated ``embed``); the worker answers with one vector.
        """
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        model = body.get("model")
        if not isinstance(model, str):
            return _error(400, "missing 'model'")
        entry = self.manager.get(model)
        if entry is None:
            return _error(404, f"model '{model}' not found", "model_not_found")
        raw = body.get("input")
        if isinstance(raw, str):
            inputs: list = [raw]
        elif isinstance(raw, list) and raw and all(isinstance(t, int) for t in raw):
            inputs = [raw]  # single pre-tokenized input
        elif isinstance(raw, list) and raw:
            inputs = raw
        else:
            return _error(400, "missing or empty 'input'")

        async def run_batch() -> tuple[list[list[float]], int]:
            # One pipeline request carries the whole input batch: the worker
            # encodes all rows in a single device dispatch (runner.embed).
            req_body = {"model": model, "prompt": inputs[0], "embed": True,
                        "embed_batch": inputs[1:]}
            vecs: list[list[float]] = []
            tokens = 0
            async for out in entry.pipeline.generate(req_body, Context()):
                out = out if isinstance(out, BackendOutput) else BackendOutput.from_dict(out)
                if out.embedding is not None:
                    vecs.append(out.embedding)
                    tokens += out.prompt_tokens or 0
                if out.finish_reason is not None:
                    break
            if len(vecs) != len(inputs):
                raise RuntimeError(f"worker returned {len(vecs)}/{len(inputs)} embeddings")
            return vecs, tokens

        with self.metrics.tracker(model, "embeddings") as tracker:
            try:
                vecs, total = await run_batch()
            except ValueError as exc:
                tracker.status = "invalid"
                return _error(400, str(exc))
            except Exception:
                logger.exception("embeddings failed (model=%s)", model)
                return _error(500, "internal error", "internal_error")
        return web.json_response(
            {
                "object": "list",
                "model": model,
                "data": [
                    {"object": "embedding", "index": i, "embedding": vec}
                    for i, vec in enumerate(vecs)
                ],
                "usage": {"prompt_tokens": total, "total_tokens": total},
            }
        )

    async def _serve_openai(self, request: web.Request, *, kind: str) -> web.StreamResponse:
        try:
            body = await request.json()
        except Exception:
            return _error(400, "invalid JSON body")
        model = body.get("model")
        if not isinstance(model, str):
            return _error(400, "missing 'model'")
        if kind == "chat" and not isinstance(body.get("messages"), list):
            return _error(400, "missing 'messages'")
        if kind == "completions" and "prompt" not in body:
            return _error(400, "missing 'prompt'")
        entry = self.manager.get(model)
        if (
            entry is None
            or (kind == "chat" and not entry.card.supports_chat)
            or (kind == "completions" and not entry.card.supports_completions)
        ):
            return _error(404, f"model '{model}' not found", "model_not_found")
        stream_mode = bool(body.get("stream", False))
        # OpenAI default: usage only when explicitly requested via stream_options.
        send_usage = bool((body.get("stream_options") or {}).get("include_usage", False))
        # Multi-tenant admission (dynamo_tpu/sched): tenant identity rides a
        # header (an API gateway stamps it; clients can't be trusted to);
        # priority is a plain body field. The preprocessor carries both into
        # PreprocessedRequest.
        tenant = request.headers.get("x-dynamo-tenant")
        if tenant:
            body["tenant_id"] = tenant
        else:
            # No gateway header: drop any client-supplied identity so a
            # client can't impersonate another tenant's quota (or hop to an
            # unconfigured tenant to dodge its own throttling).
            body.pop("tenant_id", None)
        ctx = Context(request_id=body.get("request_id"))
        # Trace ingress: continue the caller's W3C trace or mint a fresh one.
        # The root span's context rides ctx.trace through every pipeline
        # stage and process hop (GET /debug/traces/{ctx.id} reassembles it).
        incoming = TraceContext.from_traceparent(request.headers.get("traceparent"))
        root = Span("http_request", trace=incoming, request_id=ctx.id, model=model, endpoint=kind)
        root.__enter__()  # before its context is read: the root's start rides along
        ctx.trace = root.context.to_dict()

        try:
            with self.metrics.tracker(model, kind) as tracker:
                try:
                    backend_stream = self._backend_stream(entry.pipeline, body, ctx, tracker)
                    if stream_mode:
                        return await self._stream_response(
                            request, model, kind, ctx, backend_stream, send_usage,
                            parse_tools=kind == "chat" and bool(body.get("tools")),
                            tracker=tracker,
                        )
                    if kind == "chat":
                        payload = await aggregate_chat(
                            model, backend_stream, parse_tools=bool(body.get("tools"))
                        )
                    else:
                        payload = await aggregate_completion(model, backend_stream)
                    choices = payload.get("choices") or []
                    if choices and choices[0].get("finish_reason") == "error":
                        # Engine died under the aggregation: headers aren't
                        # out yet, so a real HTTP error is still possible.
                        tracker.status = "error"
                        return _error(
                            502, "the engine failed while generating this response", "engine_error"
                        )
                    return web.json_response(
                        payload, headers={"x-dynamo-trace-id": root.trace_id}
                    )
                except asyncio.CancelledError:
                    ctx.kill()
                    raise
                except ValueError as exc:  # request-shape errors from the preprocessor
                    tracker.status = "invalid"
                    ctx.kill()
                    return _error(400, str(exc))
                except Exception:
                    logger.exception("request failed (model=%s)", model)
                    ctx.kill()
                    return _error(500, "internal error", "internal_error")
        finally:
            root.__exit__(*sys.exc_info())

    async def _backend_stream(self, pipeline, body, ctx: Context, tracker) -> AsyncIterator[BackendOutput]:
        tracker.on_dispatch()
        async for item in pipeline.generate(body, ctx):
            out = item if isinstance(item, BackendOutput) else BackendOutput.from_dict(item)
            tracker.on_token()
            if out.admission_wait_ms is not None:
                tracker.on_admission_wait(out.admission_wait_ms / 1e3)
            if out.finish_reason is not None:
                tracker.on_usage(out.prompt_tokens, out.cumulative_tokens, out.cached_tokens)
            yield out

    async def _stream_response(
        self, request: web.Request, model: str, kind: str, ctx: Context,
        backend_stream: AsyncIterator[BackendOutput], send_usage: bool,
        *, parse_tools: bool = False, tracker=None,
    ) -> web.StreamResponse:
        headers = {
            "Content-Type": "text/event-stream",
            "Cache-Control": "no-cache",
            "Connection": "keep-alive",
        }
        # Surface the trace id to the client on the stream too: with it (or
        # the request id) /debug/traces and /debug/explain are reachable
        # without grepping worker logs.
        trace_id = (ctx.trace or {}).get("trace_id") if isinstance(ctx.trace, dict) else None
        if trace_id:
            headers["x-dynamo-trace-id"] = str(trace_id)
        resp = web.StreamResponse(headers=headers)
        await resp.prepare(request)
        fmt = ChatStream(model, send_usage=send_usage) if kind == "chat" else CompletionStream(model, send_usage=send_usage)
        jail = None
        if parse_tools:
            from dynamo_tpu.frontend.tool_calls import ToolCallStreamJail

            jail = ToolCallStreamJail()
        try:
            if kind == "chat":
                await resp.write(sse_encode(fmt.first()))
            async for out in backend_stream:
                if out.finish_reason is FinishReason.ERROR and not out.token_ids:
                    # Mid-stream engine death: emit a structured OpenAI-style
                    # error event (never a traceback) and end the stream.
                    if tracker is not None:
                        tracker.status = "error"
                    await resp.write(sse_encode(_ENGINE_ERROR_EVENT))
                    break
                if jail is None:
                    await resp.write(fmt.sse_delta(out))
                    if out.first_token_ts is not None:
                        # Engine's first token -> its SSE chunk written: the
                        # hop back, detokenizing, and this loop's own queue.
                        record_span(
                            "frontend_first_byte",
                            max(0.0, (time.time() - out.first_token_ts) * 1e3),
                            trace=TraceContext.from_dict(ctx.trace).under_root(),
                            start_ts=out.first_token_ts,
                            request_id=ctx.id,
                        )
                    continue
                # Tools declared: hold back potential tool-call markup; on
                # the final delta decide between text and tool_calls finish.
                safe = jail.push(out.text) if out.text else ""
                if out.finish_reason is None:
                    if safe:
                        await resp.write(sse_encode(fmt.text_chunk(safe)))
                    continue
                trailing, calls = jail.finish()
                if calls:
                    if safe:
                        await resp.write(sse_encode(fmt.text_chunk(safe)))
                    await resp.write(sse_encode(fmt.tool_calls_final(calls, out)))
                else:
                    out.text = safe + trailing
                    await resp.write(sse_encode(fmt.delta(out)))
            await resp.write(SSE_DONE)
        except (ConnectionResetError, asyncio.CancelledError):
            logger.info("client disconnected; cancelling %s", ctx.id)
            ctx.kill()
            raise
        except Exception:
            # Headers are already on the wire: a JSON 500 is impossible. End
            # the SSE stream with an error event instead of a silent cut.
            logger.exception("stream failed mid-flight (model=%s)", model)
            ctx.kill()
            if tracker is not None:
                tracker.status = "error"
            try:
                await resp.write(sse_encode(_ENGINE_ERROR_EVENT))
                await resp.write(SSE_DONE)
            except (ConnectionResetError, OSError):
                pass
        finally:
            aclose = getattr(backend_stream, "aclose", None)
            if aclose:
                await aclose()
        await resp.write_eof()
        return resp

    # -- service endpoints -------------------------------------------------

    async def list_models(self, request: web.Request) -> web.Response:
        return web.json_response(
            {
                "object": "list",
                "data": [
                    {"id": c.name, "object": "model", "created": 0, "owned_by": "dynamo-tpu"}
                    for c in self.manager.cards()
                ],
            }
        )

    async def health(self, request: web.Request) -> web.Response:
        models = self.manager.names()
        status = "healthy" if models else "no_models"
        return web.json_response({"status": status, "models": models})

    async def live(self, request: web.Request) -> web.Response:
        return web.json_response({"status": "live"})

    async def prometheus(self, request: web.Request) -> web.Response:
        self._sync_router_staleness()
        if self.telemetry is not None:
            from dynamo_tpu.observability.metrics import federate_text

            worker_parts: list[bytes] = []
            try:
                worker_parts = await self.telemetry.collect_metrics_texts()
            except Exception:
                logger.exception("worker metrics federation failed; serving frontend registry only")
            # Sync the scrape-failure counters *before* rendering the
            # frontend registry so a worker lost this scrape shows up in
            # this scrape's dynamo_federation_scrape_failures_total.
            self.metrics.sync_federation(self.telemetry.scrape_failures)
            parts = [self.metrics.render(), *worker_parts]
            return web.Response(body=federate_text(parts), content_type="text/plain")
        return web.Response(body=self.metrics.render(), content_type="text/plain")

    def _sync_router_staleness(self) -> None:
        """Fold every model's KvMetricsAggregator view into the staleness
        gauge (aggregators live in the model entries' aux lists)."""
        staleness: dict[int, float] = {}
        for name in self.manager.names():
            entry = self.manager.get(name)
            if entry is None:
                continue
            for a in entry.aux:
                fn = getattr(a, "staleness_seconds", None)
                if fn is not None:
                    staleness.update(fn())
        self.metrics.sync_staleness(staleness)

    async def debug_traces(self, request: web.Request) -> web.Response:
        """The assembled distributed timeline for one request id.

        Union of the frontend-local span ring and every worker's (via the
        telemetry fan-out), deduped by span_id; a second fan-out by trace_id
        catches spans a hop recorded under a different request id.
        """
        from dynamo_tpu.observability.service import assemble_timeline

        rid = request.match_info["request_id"]
        unique = await self._request_spans(rid)
        if not unique:
            return web.json_response(
                {"request_id": rid, "trace_ids": [], "span_count": 0, "spans": []}, status=404
            )
        return web.json_response(assemble_timeline(rid, unique))

    async def _request_spans(self, rid: str) -> list[dict]:
        """Deduped span-doc union for one request (local + worker fan-out +
        a trace-id follow-up for spans recorded under other request ids)."""
        from dynamo_tpu.tracing import SPANS

        spans = SPANS.query(request_id=rid)
        if self.telemetry is not None:
            try:
                spans += await self.telemetry.collect_spans(request_id=rid)
                for tid in sorted({s.get("trace_id") for s in spans if s.get("trace_id")}):
                    spans += SPANS.query(trace_id=tid)
                    spans += await self.telemetry.collect_spans(trace_id=tid)
            except Exception:
                logger.exception("trace fan-out failed; serving local spans only")
        seen: set[str] = set()
        unique = []
        for s in spans:
            sid = s.get("span_id")
            if sid and sid in seen:
                continue
            if sid:
                seen.add(sid)
            unique.append(s)
        return unique

    async def debug_explain(self, request: web.Request) -> web.Response:
        """One request's critical-path latency budget.

        Joins the request's span timeline (same union as ``/debug/traces``)
        with the serving worker's flight STEP/COMPILE records (``debug_explain``
        fan-out, windowed to the request's span bounds) into an ordered
        segment breakdown whose sum is checked against the measured E2E
        latency — the residual reported as ``unattributed``
        (``observability/attribution.py``).
        """
        from dynamo_tpu.config import load_attrib_settings
        from dynamo_tpu.observability.attribution import build_explain

        rid = request.match_info["request_id"]
        spans = await self._request_spans(rid)
        if not spans:
            return web.json_response(
                {"request_id": rid, "error": "no spans for this request id"}, status=404
            )
        step_docs: list[dict] = []
        if self.telemetry is not None:
            t0 = min((s.get("start_ts") or 0.0) for s in spans)
            t1 = max(
                (s.get("start_ts") or 0.0) + (s.get("duration_ms") or 0.0) / 1e3
                for s in spans
            )
            try:
                step_docs = await self.telemetry.collect_explain(t0=t0 - 1.0, t1=t1 + 1.0)
            except Exception:
                logger.exception("explain fan-out failed; serving span-only budget")
        doc = build_explain(
            rid, spans, step_docs,
            tolerance_frac=load_attrib_settings().tolerance_frac,
        )
        if doc is None:
            return web.json_response(
                {"request_id": rid, "error": "no anchor span (http_request/engine_request)"},
                status=404,
            )
        return web.json_response(doc)

    async def debug_flight(self, request: web.Request) -> web.Response:
        """One worker's engine flight ring (ordered per-step records).

        ``{worker}`` is the engine worker id (``all`` fans out to every
        worker); ``?last=N`` bounds the tail, ``?kind=step|compile|crash``
        filters by record kind.
        """
        if self.telemetry is None:
            return web.json_response(
                {"error": "no worker telemetry wired on this frontend"}, status=404
            )
        worker = request.match_info["worker"]
        last = request.query.get("last")
        try:
            rings = await self.telemetry.collect_flight(
                worker=worker,
                last=int(last) if last else None,
                kind=request.query.get("kind"),
            )
        except Exception:
            logger.exception("flight fan-out failed")
            return web.json_response({"error": "flight fan-out failed"}, status=502)
        if not rings:
            return web.json_response(
                {"error": f"no flight records for worker {worker!r}"}, status=404
            )
        return web.json_response(
            {
                "worker": worker,
                "workers": {
                    wid: {"count": len(recs), "records": recs} for wid, recs in rings.items()
                },
            }
        )

    async def debug_profile_status(self, request: web.Request) -> web.Response:
        """Profile-capture availability: is ``jax.profiler`` usable on the
        worker, is a trace currently running, and where artifacts land.
        ``{worker}`` = engine worker id, or ``all``."""
        if self.telemetry is None:
            return web.json_response(
                {"error": "no worker telemetry wired on this frontend"}, status=404
            )
        worker = request.match_info["worker"]
        try:
            workers = await self.telemetry.profile_status(worker=worker)
        except Exception:
            logger.exception("profile status fan-out failed")
            return web.json_response({"error": "profile status fan-out failed"}, status=502)
        if not workers:
            return web.json_response(
                {"error": f"no profile endpoint for worker {worker!r}"}, status=404
            )
        return web.json_response({"worker": worker, "workers": workers})

    async def debug_profile_capture(self, request: web.Request) -> web.Response:
        """Arm a bounded device trace on one worker:
        ``POST /debug/profile/{worker}?duration_ms=2000``.

        Blocks for the trace window and returns the artifact directory +
        file summary; ``409`` when another capture is already running on
        that worker (single-flight) and ``501`` when ``jax.profiler`` is
        unavailable there — a refusal, not an error, so automation can tell
        "try later" from "never works here"."""
        if self.telemetry is None:
            return web.json_response(
                {"error": "no worker telemetry wired on this frontend"}, status=404
            )
        worker = request.match_info["worker"]
        try:
            duration_ms = float(request.query.get("duration_ms", 2000.0))
        except ValueError:
            return web.json_response({"error": "duration_ms must be a number"}, status=400)
        try:
            doc = await self.telemetry.capture_profile(worker, duration_ms)
        except Exception:
            logger.exception("profile capture fan-out failed")
            return web.json_response({"error": "profile capture failed"}, status=502)
        if doc is None:
            return web.json_response(
                {"error": f"no profile endpoint for worker {worker!r}"}, status=404
            )
        if not doc.get("ok"):
            status = {"busy": 409, "profiler_unavailable": 501}.get(
                doc.get("reason", ""), 502
            )
            return web.json_response(doc, status=status)
        return web.json_response(doc)

    async def debug_incidents(self, request: web.Request) -> web.Response:
        """Fleet-wide incident bundle listing (frontend-local + every worker).

        Workers on one host may share the incident directory (run_local,
        fleetsim), so summaries are deduplicated by id; each summary's
        ``worker`` field names the process that captured it.
        """
        workers: dict[str, list[dict]] = {}
        if self.telemetry is not None:
            try:
                workers = await self.telemetry.collect_incidents()
            except Exception:
                logger.exception("incident fan-out failed")
                return web.json_response({"error": "incident fan-out failed"}, status=502)
        seen: dict[str, dict] = {}
        for items in workers.values():
            for item in items:
                seen.setdefault(item["id"], item)
        for item in self.metrics.incidents.store.list():
            seen.setdefault(item["id"], item)
        incidents = sorted(seen.values(), key=lambda i: i.get("ts") or 0.0)
        return web.json_response({"count": len(incidents), "incidents": incidents})

    async def debug_incident(self, request: web.Request) -> web.Response:
        """One full incident bundle by id, from whichever process holds it."""
        incident_id = request.match_info["incident_id"]
        bundle = self.metrics.incidents.store.get(incident_id)
        if bundle is None and self.telemetry is not None:
            try:
                bundle = await self.telemetry.fetch_incident(incident_id)
            except Exception:
                logger.exception("incident fetch fan-out failed")
                return web.json_response({"error": "incident fetch failed"}, status=502)
        if bundle is None:
            return web.json_response({"error": f"no incident {incident_id!r}"}, status=404)
        return web.json_response(bundle)

    async def debug_federation(self, request: web.Request) -> web.Response:
        """Telemetry fan-out health: per-worker failure counts + last failure."""
        if self.telemetry is None:
            return web.json_response({"failures": {}, "last_failure": None})
        return web.json_response(
            {
                "failures": dict(self.telemetry.scrape_failures),
                "last_failure": self.telemetry.last_failure,
            }
        )

    async def debug_store(self, request: web.Request) -> web.Response:
        """HA control-plane view from this process: the hosted store replica
        (role/epoch/seq/lag, if one lives in-process), the client-side
        failover ledger, and the router's index-resync counter. Process-local
        snapshots only — no store RPC, so it answers even mid-failover."""
        from dynamo_tpu.router.events import router_resync_snapshot
        from dynamo_tpu.runtime.replication import replica_snapshot
        from dynamo_tpu.runtime.store_server import store_client_snapshot

        return web.json_response(
            {
                "replica": replica_snapshot(),
                "client": store_client_snapshot(),
                "router": router_resync_snapshot(),
            }
        )

    async def clear_kv_blocks(self, request: web.Request) -> web.Response:
        if self.clear_kv_hook is None:
            return web.json_response({"cleared": 0, "detail": "no workers wired"}, status=200)
        cleared = await self.clear_kv_hook()
        return web.json_response({"cleared": cleared})
