"""Async engine service: the AsyncEngine facade over the synchronous core.

One background loop owns the EngineCore (single-writer — no locking):
it drains the intake queue, runs engine steps in a worker thread (so the
event loop keeps serving streams while XLA executes), and fans step outputs
out to per-request asyncio queues.

This is the stage that gets served on a runtime Endpoint
(``runtime.Endpoint.serve``); with KV events and metrics wired to the
runtime's event plane it is the full equivalent of one reference "worker"
process (vLLM subprocess + publisher side-cars, SURVEY.md §3 call stacks B/D).
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Any, AsyncIterator

from dynamo_tpu.engine.core import EngineCore
from dynamo_tpu.engine.sequence import Sequence
from dynamo_tpu.protocols.common import EngineOutput, PreprocessedRequest
from dynamo_tpu.protocols.kv import ForwardPassMetrics
from dynamo_tpu.runtime.engine import AsyncEngine, Context
from dynamo_tpu.runtime.faults import FAULTS
from dynamo_tpu.tracing import (
    INTAKE, NO_WORK, ROUTE, SUBMIT, Span, StepClock, record_span, trace_of,
)

logger = logging.getLogger(__name__)

_SENTINEL = object()


class JaxEngineService(AsyncEngine[Any, dict]):
    """Serves PreprocessedRequest (or its dict form) -> stream of EngineOutput dicts."""

    def __init__(self, core: EngineCore) -> None:
        self.core = core
        core.defer_offloads = True  # we flush after routing outputs (below)
        self.aux: list = []  # companion tasks (metrics publisher, ...) closed with us
        self._intake: asyncio.Queue = asyncio.Queue()
        self._streams: dict[int, asyncio.Queue] = {}
        self._loop_task: asyncio.Task | None = None
        self._wake = asyncio.Event()
        self._closed = False
        self._draining = False

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> "JaxEngineService":
        if self._closed:
            raise RuntimeError("engine service is closed")
        if self._loop_task is None:
            self._loop_task = asyncio.create_task(self._engine_loop(), name="jax-engine-loop")
        return self

    async def close(self) -> None:
        self._closed = True
        self._wake.set()
        for a in self.aux:
            await a.close()
        self.aux = []
        if self._loop_task is not None:
            self._loop_task.cancel()
            try:
                await self._loop_task
            except asyncio.CancelledError:
                pass
            self._loop_task = None
        # Cancelling the loop task does NOT stop a core.step() already
        # running in the executor thread — abort_all takes the core's
        # step_lock, so running it in the executor waits that step out
        # before touching the engine state it is mutating.
        await asyncio.get_running_loop().run_in_executor(None, self.core.abort_all)
        # In-flight streams would otherwise wait forever for a sentinel the
        # dead loop can never send (their consumers hang on shutdown/crash).
        self._drain_intake_failed()
        if self._streams:
            self._notify_streams_failed()

    def _drain_intake_failed(self) -> None:
        """Fail requests queued but never admitted by the (now dead) loop."""
        from dynamo_tpu.protocols.common import FinishReason

        drained = 0
        while True:
            try:
                _req, _ctx, out_q, _t_enq = self._intake.get_nowait()
            except asyncio.QueueEmpty:
                break
            out_q.put_nowait(EngineOutput(token_ids=[], finish_reason=FinishReason.ERROR))
            out_q.put_nowait(_SENTINEL)
            drained += 1
        if drained:
            flight = getattr(self.core, "flight", None)
            if flight is not None:
                from dynamo_tpu.observability.flight import CRASH

                flight.record(CRASH, where="intake_drain", drained=drained)

    async def drain(self, timeout: float = 30.0) -> bool:
        """Stop admitting new requests and wait for in-flight ones to finish.

        Returns True if everything finished before the deadline. The engine
        loop keeps stepping throughout — draining stops *admission*, not
        progress on work already admitted.
        """
        self._draining = True
        deadline = time.monotonic() + timeout
        while (self._streams or not self._intake.empty()) and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        return not self._streams and self._intake.empty()

    # -- engine loop -------------------------------------------------------

    async def _engine_loop(self) -> None:
        loop = asyncio.get_running_loop()
        # A step's outputs go to their streams when the step returns. The
        # streams' consumers (SSE encoding and writes) run on this loop's
        # thread and hold the GIL against the engine thread; the pipelined
        # loop returns the tokens it read after enqueueing its own program,
        # so they run under that program and cost the step nothing (PR 29).
        # The gap between two steps, in five parts: handoff (the step returned
        # in its thread -> this loop resumed; the core opens it), route,
        # intake, no_work, submit (-> the next step begins; the core ends it).
        clock = getattr(self.core, "clock", None) or StepClock()
        while not self._closed:
            # Drain intake without blocking.
            clock.mark(INTAKE)
            admitted = False
            while True:
                try:
                    request, context, out_q, t_enq = self._intake.get_nowait()
                except asyncio.QueueEmpty:
                    break
                # Intake-to-admission gap: how long the request sat waiting
                # for the engine loop (scheduler queue wait on the timeline).
                record_span(
                    "engine_queue_wait",
                    (time.perf_counter() - t_enq) * 1e3,
                    trace=trace_of(context),
                    request_id=context.id,
                )
                try:
                    seq = self.core.add_request(request, context)
                except Exception:
                    logger.exception("add_request failed; failing that request only")
                    from dynamo_tpu.protocols.common import FinishReason

                    out_q.put_nowait(EngineOutput(token_ids=[], finish_reason=FinishReason.ERROR))
                    out_q.put_nowait(_SENTINEL)
                    admitted = True
                    continue
                self._streams[seq.seq_id] = out_q
                if seq.is_finished:  # rejected at intake (too long / empty)
                    out_q.put_nowait(
                        EngineOutput(token_ids=[], finish_reason=seq.finish_reason, prompt_tokens=seq.num_prompt)
                    )
                    out_q.put_nowait(_SENTINEL)
                    del self._streams[seq.seq_id]
                admitted = True

            if not self.core.has_work:
                if not admitted:
                    clock.mark(NO_WORK)
                    self._wake.clear()
                    await self._wake.wait()
                continue

            # One engine step off-thread: the event loop stays responsive.
            # (If this task is cancelled mid-step, the executor thread keeps
            # running — close() serializes against it via core.step_lock.)
            try:
                if FAULTS.armed:
                    FAULTS.fire("engine.step")
                clock.mark(SUBMIT)
                outputs = await loop.run_in_executor(None, self.core.step)
                clock.mark(ROUTE)
            except Exception as exc:
                logger.exception("engine step failed; failing all in-flight streams")
                flight = getattr(self.core, "flight", None)
                if flight is not None:
                    try:
                        from dynamo_tpu.observability.flight import CRASH

                        flight.record(
                            CRASH, where="engine_loop",
                            error=type(exc).__name__, detail=str(exc)[:500],
                            streams=len(self._streams),
                        )
                        path = flight.dump_jsonl(reason="engine_step_failure")
                        logger.error("flight recorder dumped to %s", path)
                    except Exception:
                        logger.exception("flight recorder dump failed")
                # core.step's own except already captured a bundle for a
                # genuine step crash; the capture cooldown folds this
                # loop-level one into it, so pre-step injected faults
                # (FAULTS "engine.step") still produce exactly one bundle.
                incidents = getattr(self.core, "incidents", None)
                if incidents is not None:
                    incidents.capture("crash", {
                        "error": type(exc).__name__, "detail": str(exc)[:500],
                        "where": "engine_loop", "streams": len(self._streams),
                    })
                self._fail_all_streams()
                continue
            self._route(outputs)
            # Tier write-through happens after outputs are routed, so token
            # delivery latency never waits on device->host offload copies.
            if self.core.pending_offloads:
                try:
                    await loop.run_in_executor(None, self.core.flush_offloads)
                except Exception:
                    logger.exception("tier offload flush failed (non-fatal)")

    def _notify_streams_failed(self) -> None:
        from dynamo_tpu.protocols.common import FinishReason

        for q in self._streams.values():
            q.put_nowait(EngineOutput(token_ids=[], finish_reason=FinishReason.ERROR))
            q.put_nowait(_SENTINEL)
        self._streams.clear()

    def _fail_all_streams(self) -> None:
        self._notify_streams_failed()
        # Engine state may be inconsistent after a failed step: drop all work,
        # releasing every sequence's pages back to the allocator.
        self.core.abort_all()

    def _route(self, outputs: list[tuple[Sequence, EngineOutput]]) -> None:
        for seq, out in outputs:
            q = self._streams.get(seq.seq_id)
            if q is None:
                continue
            q.put_nowait(out)
            if out.finish_reason is not None:
                q.put_nowait(_SENTINEL)
                del self._streams[seq.seq_id]

    # -- AsyncEngine -------------------------------------------------------

    async def generate(self, request: Any, context: Context) -> AsyncIterator[dict]:
        if isinstance(request, dict):
            request = PreprocessedRequest.from_dict(request)
        if self._closed:
            # A dead engine must refuse loudly (the stream error feeds the
            # client's inhibit list), not queue into a loop that never runs.
            raise RuntimeError("engine service is closed")
        if self._draining:
            # Draining refuses the same way: the client breaker routes the
            # request to a replica while in-flight streams finish here.
            raise RuntimeError("engine service is draining")
        if request.annotations.get("embed"):
            # Embedding requests bypass the scheduler: the cache-free encoder
            # shares nothing with the paged decode state (runner.embed). The
            # request's whole input batch runs as ONE device dispatch; one
            # output per input streams back, the last carrying the finish.
            from dynamo_tpu.protocols.common import FinishReason

            inputs = request.annotations.get("embed_inputs") or [list(request.token_ids)]
            vecs = await asyncio.get_running_loop().run_in_executor(
                None, self.core.runner.embed, [list(ids) for ids in inputs]
            )
            for i, ids in enumerate(inputs):
                last = i == len(inputs) - 1
                yield EngineOutput(
                    token_ids=[], finish_reason=FinishReason.STOP if last else None,
                    prompt_tokens=len(ids), cached_tokens=0,
                    embedding=[float(x) for x in vecs[i]],
                ).to_dict()
            return
        trace = trace_of(context)
        if trace is not None and trace.root_ts:
            # The request's time before the engine (parse, preprocess, route,
            # the hop here), under the frontend's root span. Wall clock: the
            # frontend may be another process.
            record_span(
                "frontend_pre_engine",
                max(0.0, (time.time() - trace.root_ts) * 1e3),
                trace=trace.under_root(),
                start_ts=trace.root_ts,
                request_id=request.request_id,
            )
        await self.start()
        out_q: asyncio.Queue = asyncio.Queue()
        await self._intake.put((request, context, out_q, time.perf_counter()))
        self._wake.set()
        if self._closed:
            # close() may have run between the check above and the put: its
            # intake drain might have missed this entry, so unblock the
            # consumer directly (duplicate ERROR items are harmless).
            from dynamo_tpu.protocols.common import FinishReason

            out_q.put_nowait(EngineOutput(token_ids=[], finish_reason=FinishReason.ERROR))
            out_q.put_nowait(_SENTINEL)
        finished = False
        span = Span(
            "engine_request",
            trace=trace,
            request_id=request.request_id,
            prompt_tokens=len(request.token_ids),
        )
        span.__enter__()
        tokens_out = 0
        saw_finish = False
        try:
            while True:
                item = await out_q.get()
                if item is _SENTINEL:
                    finished = True
                    return
                if item.admission_wait_ms is not None:
                    # Arrival -> scheduler admission, measured by the core
                    # and attached to the first delta. As a span it joins
                    # the /debug/explain budget's pre-decode segments. The
                    # same delta says when admission was: the span starts
                    # there, and engine_prefill runs from there to now.
                    pf = item.prefill or {}
                    admitted = pf.get("admitted_mono")
                    record_span(
                        "engine_admission_wait",
                        item.admission_wait_ms,
                        trace=span.context,
                        start_mono=None if admitted is None else admitted - item.admission_wait_ms / 1e3,
                        request_id=request.request_id,
                    )
                    if admitted is not None and item.token_ids:
                        record_span(
                            "engine_prefill",
                            (time.perf_counter() - admitted) * 1e3,
                            trace=span.context,
                            start_mono=admitted,
                            request_id=request.request_id,
                            prompt_tokens=pf["prompt_tokens"],
                            cached_tokens=pf["cached_tokens"],
                            chunks=pf["chunks"],
                            steps=pf["steps"],
                        )
                if tokens_out == 0 and item.token_ids:
                    # TTFT as seen at the engine boundary: submit -> first
                    # token out of the step loop. Child of engine_request.
                    record_span(
                        "engine_first_token",
                        (time.perf_counter() - span.t0) * 1e3,
                        trace=span.context,
                        request_id=request.request_id,
                    )
                    item.first_token_ts = time.time()
                tokens_out += len(item.token_ids)
                saw_finish = saw_finish or item.finish_reason is not None
                yield item.to_dict()
        finally:
            span.fields["output_tokens"] = tokens_out
            # A consumer may stop at the finish item without draining the
            # sentinel — that's still a completed request for the span.
            span.fields["finished"] = finished or saw_finish
            span.__exit__(None, None, None)
            if not finished:
                # Consumer walked away (generator closed / task cancelled):
                # stop the sequence so it doesn't decode to max_tokens.
                context.stop_generating()
                self._wake.set()

    # -- introspection -----------------------------------------------------

    def metrics(self) -> ForwardPassMetrics:
        return self.core.metrics()
