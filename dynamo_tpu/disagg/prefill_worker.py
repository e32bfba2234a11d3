"""Prefill worker: claims queue tasks, prefills, ships KV to decode workers.

The reference's `examples/llm/components/prefill_worker.py` role. The local
engine runs an ordinary 1-token generation (prefill + first decode step);
its committed pages are then read out and streamed to the requesting decode
worker's transfer endpoint. The sampled token is discarded — the decode side
recomputes the sub-page tail locally and samples there, so the transferred
artifact is pure KV.

The worker claims up to ``max_concurrency`` queue tasks at once, but that
bound applies to the *compute* phase only: the moment a task's local prefill
generation completes, its compute slot is released and the KV ship continues
under a separate ``ship_concurrency`` bound (``DYN_PREFILL_SHIP_CONCURRENCY``,
default ``2 * max_concurrency``). Ship-of-request-A therefore overlaps
prefill-of-request-B even when ``max_concurrency`` is 1 — the wire rides
under the next prompt's compute instead of serializing behind it. The engine
additionally chunks each prompt under the mixed-step scheduler
(engine/core.py), so overlapping tasks interleave their prefill chunks.
"""

from __future__ import annotations

import asyncio
import logging
import os

from dynamo_tpu.disagg.queue import DistributedQueue
from dynamo_tpu.disagg.transfer import (
    collect_prefill_blocks,
    send_blocks,
    send_blocks_chunked,
    send_pull_offer,
)
from dynamo_tpu.engine.service import JaxEngineService
from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
from dynamo_tpu.runtime.component import DistributedRuntime
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.runtime.faults import FAULTS
from dynamo_tpu.tokens import compute_block_hashes

logger = logging.getLogger(__name__)

PREFILL_QUEUE = "prefill"


class PrefillWorker:
    def __init__(
        self,
        runtime: DistributedRuntime,
        service: JaxEngineService,
        *,
        queue_name: str = PREFILL_QUEUE,
        max_concurrency: int = 2,
        ship_concurrency: int | None = None,
    ) -> None:
        from dynamo_tpu.disagg.transfer import refuse_recurrent

        refuse_recurrent(service.core, "a prefill worker (its pages are shipped to a decode worker)")
        self.runtime = runtime
        self.service = service
        self.queue = DistributedQueue(runtime, queue_name)
        self._task: asyncio.Task | None = None
        # Compute-phase bound: held from claim until the local prefill
        # generation finishes (NOT until the ship completes — see _run_one).
        self._sem = asyncio.Semaphore(max(1, max_concurrency))
        if ship_concurrency is None:
            try:
                ship_concurrency = int(
                    os.environ.get("DYN_PREFILL_SHIP_CONCURRENCY", "")
                    or 2 * max(1, max_concurrency)
                )
            except ValueError:
                ship_concurrency = 2 * max(1, max_concurrency)
        # Ship-phase bound: caps in-flight KV transfers (each striped ship
        # holds host buffers for ~streams chunks) without tying up a compute
        # slot while bytes are on the wire.
        self._ship_sem = asyncio.Semaphore(max(1, ship_concurrency))
        self._inflight: set[asyncio.Task] = set()
        self.completed = 0

    async def start(self) -> "PrefillWorker":
        if self._task is None:
            self._task = asyncio.create_task(self._loop(), name="prefill-worker")
        return self

    async def _loop(self) -> None:
        while True:
            try:
                await self._sem.acquire()
                try:
                    claimed = await self.queue.claim(timeout=None)
                except BaseException:
                    self._sem.release()
                    raise
                if claimed is None:
                    self._sem.release()
                    continue
                t = asyncio.create_task(self._run_one(claimed), name="prefill-task")
                self._inflight.add(t)
                t.add_done_callback(self._inflight.discard)
            except asyncio.CancelledError:
                raise
            except Exception:
                logger.exception("prefill claim failed")
                await asyncio.sleep(0.2)

    async def _run_one(self, claimed: tuple) -> None:
        key, task = claimed
        # The compute slot frees as soon as the prefill generation is done
        # (callback invoked inside _prefill_and_ship) so the NEXT task's
        # prefill runs under THIS task's ship; the finally is the backstop
        # for failures before that point. Idempotent by construction.
        released = False

        def release_compute() -> None:
            nonlocal released
            if not released:
                released = True
                self._sem.release()

        try:
            await self._handle(task, release_compute)
            await self.queue.delete(key)
            self.completed += 1
        except asyncio.CancelledError:
            raise
        except Exception:
            # Release the claim so a *peer* reclaims the task immediately —
            # leaving it for our lease to expire would stall it a full TTL.
            logger.exception("prefill task failed; releasing claim for a peer to retry")
            try:
                await self.queue.release(key)
            except Exception:
                logger.exception("claim release failed; lease expiry will reclaim %s", key)
            await asyncio.sleep(0.2)
        finally:
            release_compute()

    async def _handle(self, task: dict, release_compute=lambda: None) -> None:
        import time

        from dynamo_tpu.tracing import Span, TraceContext, record_span

        token_ids = task["token_ids"]
        request_id = task["request_id"]
        # The decode side's remote_prefill span context rides the task dict;
        # everything this worker records links under it (one trace_id across
        # both processes). Untraced tasks get local root spans.
        trace = TraceContext.from_dict(task.get("trace"))
        t_enq = task.get("t_enqueue")
        if t_enq is not None:
            # Wall-clock gap (cross-process; clocks assumed NTP-close): how
            # long the task sat in the distributed queue before our claim.
            record_span(
                "prefill_queue_wait", max(0.0, (time.time() - float(t_enq)) * 1e3),
                trace=trace, request_id=request_id,
            )
        exec_span = Span("prefill_exec", trace=trace, request_id=request_id, tokens=len(token_ids))
        with exec_span:
            if FAULTS.armed:
                FAULTS.fire("prefill.exec")
            await self._prefill_and_ship(task, exec_span.context, release_compute)

    async def _prefill_and_ship(self, task: dict, trace, release_compute=lambda: None) -> None:
        token_ids = task["token_ids"]
        request_id = task["request_id"]
        page_size = self.service.core.config.page_size
        salt = self.service.core.config.salt
        # Ordinary 1-token generation: prefill fills + commits the prompt's
        # full pages into this worker's prefix cache.
        req = PreprocessedRequest(
            token_ids=token_ids,
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=1, ignore_eos=True),
            request_id=request_id,
        )
        async for _ in self.service.generate(req, Context(request_id=request_id, trace=trace.to_dict())):
            pass
        # Compute done: free the slot so the next claimed task prefills while
        # this one's KV goes out under the ship bound.
        release_compute()
        hashes = compute_block_hashes(token_ids, page_size, salt=salt)
        async with self._ship_sem:
            await self._ship(task, trace, hashes)

    async def _ship(self, task: dict, trace, hashes: list[int]) -> None:
        token_ids = task["token_ids"]
        request_id = task["request_id"]

        # Co-located decode worker with matching cache geometry: move the
        # pages over the device path (gather -> device_put -> scatter; ICI
        # when chips differ). The TCP stream below is the cross-host (DCN)
        # fallback, also taken if the device path fails.
        from dynamo_tpu.disagg.device_transfer import REGISTRY, cache_compatible

        peer = REGISTRY.lookup(task["transfer_address"])
        if peer is not None and cache_compatible(self.service.core.runner, peer.core.runner):
            try:
                injected = await peer.inject_from(self.service.core, hashes, request_id)
            except Exception:
                logger.exception(
                    "prefill %s: device-path transfer failed, falling back to TCP", request_id
                )
            else:
                logger.info(
                    "prefill %s: %d tokens -> %d blocks via device path (%s)",
                    request_id, len(token_ids), injected, peer.stats(),
                )
                return

        # Cross-process device path: offer the chain for a transfer-engine
        # pull (jax.experimental.transfer — ICI/DCN, no host bounce). The
        # receiver's response tells us whether it could pull; any failure
        # falls through to the packed-bytes TCP stream below.
        try:
            result = await send_pull_offer(
                self.runtime.transport, task["transfer_address"], request_id,
                self.service.core, hashes,
            )
        except Exception:
            logger.exception("prefill %s: pull offer failed, falling back to TCP", request_id)
            result = None
        if result is not None:
            logger.info(
                "prefill %s: %d tokens -> %s blocks via cross-process device pull (%s)",
                request_id, len(token_ids), result.get("injected"), result.get("stats"),
            )
            return

        # Chunked TCP stream (wire v3 striped when the transport has a duplex
        # data plane, v2 single-stream otherwise): gather, pack and wire
        # pipelined per chunk, runner lock released between chunks. The
        # monolithic v1 collect-then-send below is the last-resort fallback.
        try:
            result = await send_blocks_chunked(
                self.runtime.transport, task["transfer_address"], request_id,
                self.service.core, hashes, trace=trace,
            )
        except Exception:
            logger.exception(
                "prefill %s: chunked stream failed, falling back to monolithic TCP", request_id
            )
        else:
            if result.get("total", 0) == 0:
                logger.warning("prefill %s produced no transferable blocks", request_id)
            logger.info(
                "prefill %s: %d tokens -> %s blocks streamed via wire %s x%s (%s injected, phases %s)",
                request_id, len(token_ids), result.get("total"),
                result.get("protocol", "v2"), result.get("streams", 1),
                result.get("injected"), result.get("phases"),
            )
            return

        loop = asyncio.get_running_loop()
        blocks = await loop.run_in_executor(None, collect_prefill_blocks, self.service.core, hashes)
        if not blocks:
            logger.warning("prefill %s produced no transferable blocks", request_id)
        result = await send_blocks(
            self.runtime.transport, task["transfer_address"], request_id, blocks,
            trace=trace, core=self.service.core,
        )
        logger.info(
            "prefill %s: %d tokens -> %d blocks shipped (%s injected)",
            request_id, len(token_ids), len(blocks), result.get("injected"),
        )

    async def drain(self, timeout: float = 30.0) -> bool:
        """Stop claiming new tasks and wait for in-flight prefills to finish
        (under ``timeout``). Returns True if everything completed."""
        if self._task is not None:
            self._task.cancel()
            self._task = None
        if self._inflight:
            _done, pending = await asyncio.wait(list(self._inflight), timeout=timeout)
            return not pending
        return True

    async def close(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None
        for t in list(self._inflight):
            t.cancel()
        self._inflight.clear()
        await self.queue.close()
