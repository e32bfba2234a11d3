"""KV block transfer: the prefill->decode migration path.

Decode workers serve a ``kv_transfer`` endpoint (`KvTransferService`).
A transfer request is a stream of block payloads — each a hash-chained,
complete page of KV for all layers — which the service writes into freshly
allocated pages and *commits to the local prefix cache*. From that moment
the blocks are indistinguishable from locally-computed cache: admission
matches them, KV events announce them, eviction can offload them to tiers.

Wire format per block (msgpack-native, no base64):
  {"hash": int, "parent": int|None, "tokens": [int], "k": bytes, "v": bytes,
   "shape": [L, ps, kv, hd], "dtype": str}

Two framings carry those blocks (docs/KV_TRANSFER_WIRE_V2.md):

- v1 (monolithic): one ``{"request_id", "blocks": [...]}`` message with the
  whole chain — collect-then-send, retained as the last-resort fallback.
- v2 (streaming): a sequence of ``{"request_id", "seq", "blocks", "last"}``
  chunk messages. The sender (:func:`send_blocks_chunked`) pipelines them:
  chunk N+1's device gather + D2H copy is dispatched (``read_pages_async``)
  before chunk N is packed and sent, so gather, pack and wire overlap and
  the runner lock releases between chunks. The receiver scatters each chunk
  with one batched ``write_pages``, commits it incrementally (every prefix
  of the hash chain is a valid cache state) while holding refcounts so a
  later chunk's allocations can't evict the chain, and rolls back staging
  on mid-stream failure or sender death.
- v3 (striped): the same chunks split round-robin across a pool of
  ``DYN_KV_WIRE_STREAMS`` persistent duplex connections, each chunk a raw
  blob frame (msgpack header + raw k/v bytes, no per-block msgpack copies).
  The receiver reassembles out-of-order arrivals under a host-staging byte
  budget (``DYN_KV_WIRE_INFLIGHT``) and commits strictly in seq order, so
  v2's incremental commit/rollback and per-chunk crc-retry semantics carry
  over exactly. Falls back to v2 when the transport or the receiver has no
  duplex data plane.

Completion notifications resolve per-request futures so the disagg operator
holding the original request knows when injection is done.

Parity: replaces the reference's NIXL RDMA block writes
(`block_manager/block/transfer/nixl.rs`, vLLM patch in SURVEY.md §3C) with a
receiver-driven stream over the runtime's data plane — the DCN path. Workers
sharing a host/slice can short-circuit with device-to-device copies; that
fast path rides the same interface.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import os
import threading
import time
import zlib
from typing import Any, AsyncIterator

import numpy as np

from dynamo_tpu.engine.allocator import OutOfPagesError
from dynamo_tpu.engine.core import EngineCore
from dynamo_tpu.observability.metrics import observe_kv_phase
from dynamo_tpu.runtime.engine import AsyncEngine, Context
from dynamo_tpu.runtime.faults import FAULTS, corrupt_bytes
from dynamo_tpu.runtime.transport import DuplexUnsupportedError, Transport
from dynamo_tpu.tracing import TraceContext, record_span

logger = logging.getLogger(__name__)

KV_TRANSFER_ENDPOINT = "kv_transfer"

#: Pages per streamed chunk — the same bounded-lock-hold sizing as
#: ``device_transfer.DeviceKvTransfer.CHUNK_PAGES``: each chunk's gather
#: holds the sender's io_lock for one dispatch only, and each chunk is one
#: compiled pow2 shape, so a long chain costs a handful of programs and the
#: engines' decode loops interleave with an in-flight transfer.
#: Overridable end-to-end with ``DYN_KV_CHUNK_PAGES``.
CHUNK_PAGES = 64


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def default_chunk_pages() -> int:
    """Pages per streamed chunk; ``DYN_KV_CHUNK_PAGES`` overrides."""
    return max(1, _env_int("DYN_KV_CHUNK_PAGES", CHUNK_PAGES))


def default_wire_streams() -> int:
    """Striped data-plane connections per transfer (wire v3).

    ``DYN_KV_WIRE_STREAMS`` overrides; 0 pins the legacy single-stream v2
    protocol (per-chunk request/response round trips)."""
    return max(0, _env_int("DYN_KV_WIRE_STREAMS", 4))


def staging_budget_bytes() -> int:
    """Receiver-side host bytes allowed in out-of-order reassembly staging
    across ALL in-flight sessions; ``DYN_KV_WIRE_INFLIGHT`` overrides.
    In-order chunks are always admitted, so the budget bounds memory without
    ever blocking stream progress."""
    return max(1, _env_int("DYN_KV_WIRE_INFLIGHT", 256 * 1024 * 1024))


class _PhaseClock:
    """Busy-interval union across parallel streams.

    ``total`` accumulates wall time during which *at least one* stream was
    inside the phase — per-stream-attributed wall time, never a sum over
    concurrent streams. This keeps the overlap-is-real invariant (phase sums
    exceeding end-to-end time measure genuine overlap) meaningful for the
    striped sender, where four stripes on the wire at once must count as one
    second per second. Thread-safe: pack runs on executor threads."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._busy = 0
        self._t0 = 0.0
        self.total = 0.0

    def enter(self) -> None:
        with self._lock:
            if self._busy == 0:
                self._t0 = time.perf_counter()
            self._busy += 1

    def exit(self) -> None:
        with self._lock:
            self._busy -= 1
            if self._busy == 0:
                self.total += time.perf_counter() - self._t0


@dataclasses.dataclass
class _StreamSession:
    """Receiver-side state of one in-flight chunk stream (wire v2 or v3).

    ``pinned`` holds refcounts on every block of the chain ingested so far
    (cache hits AND incrementally-committed chunks): a later chunk's
    allocations must not be able to evict the chain prefix mid-stream. The
    refcounts drop when the stream ends — on the ``last`` chunk, an abort,
    an error, or the abandoned-stream sweep.

    Wire v3 adds out-of-order reassembly: stripes deliver chunks in any
    order, ``staging`` parks arrivals ahead of ``next_seq`` (bounded by the
    service-wide staging budget), and a per-session pump task commits them
    strictly in seq order — so the v2 invariant that every committed prefix
    is a valid cache state is untouched. Acks are deferred until commit;
    ``acks``/``wake`` hand them back to the stripe handler that parked.
    """

    next_seq: int = 0
    pinned: list[int] = dataclasses.field(default_factory=list)
    injected: int = 0
    total_blocks: int = 0
    #: Pool exhaustion truncated the chain: later chunks are acknowledged
    #: but not ingested (their parents are missing — committing them would
    #: publish unreachable blocks).
    truncated: bool = False
    t_last: float = dataclasses.field(default_factory=time.monotonic)
    # -- wire v3 (striped) state ------------------------------------------
    sid: str = ""  # sender-chosen stream id: stripes of one transfer attach
    stripes: int = 1
    total_chunks: int | None = None  # None = v2 session (total from "last")
    conns: int = 0  # open stripe connections feeding this session
    dead: bool = False
    bytes: int = 0
    staging: dict[int, tuple[list[dict], int]] = dataclasses.field(default_factory=dict)
    staged_bytes: int = 0
    acks: dict[int, dict] = dataclasses.field(default_factory=dict)
    wake: asyncio.Event = dataclasses.field(default_factory=asyncio.Event)
    pump: asyncio.Task | None = None
    #: Sender's trace context (from the stream_open request): v3 meta blocks
    #: don't carry per-block trace dicts, so receiver-side spans link here.
    trace: dict | None = None

    def pulse(self) -> None:
        """Wake everything parked on this session (generation-event idiom:
        waiters grab ``wake`` before re-checking their predicate)."""
        ev = self.wake
        self.wake = asyncio.Event()
        ev.set()


def pack_block(block_hash: int, parent_hash: int | None, tokens: list[int], k: np.ndarray, v: np.ndarray) -> dict:
    kb = np.ascontiguousarray(k).tobytes()
    vb = np.ascontiguousarray(v).tobytes()
    return {
        "hash": block_hash,
        "parent": parent_hash,
        "tokens": list(tokens),
        "k": kb,
        "v": vb,
        "shape": list(k.shape),
        "dtype": str(k.dtype),
        # End-to-end payload integrity: verified receiver-side before the
        # scatter (msgpack/TCP don't checksum application payloads for us).
        "crc": zlib.crc32(vb, zlib.crc32(kb)),
    }


def block_crc_ok(blk: dict) -> bool:
    """Verify a packed block's crc32. Blocks without one (older senders)
    pass — the check is opt-in by wire format, not a protocol break."""
    crc = blk.get("crc")
    if crc is None:
        return True
    return zlib.crc32(blk["v"], zlib.crc32(blk["k"])) == crc


def unpack_payload(msg: dict) -> tuple[np.ndarray, np.ndarray]:
    shape = tuple(msg["shape"])
    dtype = np.dtype(msg["dtype"])
    k = np.frombuffer(msg["k"], dtype=dtype).reshape(shape)
    v = np.frombuffer(msg["v"], dtype=dtype).reshape(shape)
    return k, v


def pack_chunk_blob(
    hashes: list[int], parents: list[int | None], payloads, clock: _PhaseClock | None = None
) -> tuple[list[dict], list[memoryview], int]:
    """Wire v3 framing: per-block *metadata* only (msgpack head) plus the raw
    k/v buffers as zero-copy memoryviews for the blob body — no ``tobytes``
    and no per-block msgpack of payload bytes (that was v2's pack_s)."""
    if clock is not None:
        clock.enter()
    try:
        meta: list[dict] = []
        bufs: list[memoryview] = []
        nbytes = 0
        for i, (k, v) in enumerate(payloads):
            k = np.ascontiguousarray(k)
            v = np.ascontiguousarray(v)
            shape, dtype = list(k.shape), str(k.dtype)
            # Byte-view before memoryview: extension dtypes (bfloat16 et al)
            # have no buffer-protocol format char, but their bytes do.
            kb = memoryview(k.view(np.uint8).reshape(-1))
            vb = memoryview(v.view(np.uint8).reshape(-1))
            meta.append({
                "hash": hashes[i],
                "parent": parents[i],
                "tokens": [],
                "shape": shape,
                "dtype": dtype,
                "k_len": kb.nbytes,
                "v_len": vb.nbytes,
                "crc": zlib.crc32(vb, zlib.crc32(kb)),
            })
            bufs.extend((kb, vb))
            nbytes += kb.nbytes + vb.nbytes
        return meta, bufs, nbytes
    finally:
        if clock is not None:
            clock.exit()


def blob_to_blocks(meta: list[dict], blob) -> list[dict]:
    """Slice a chunk's blob body back into v2-shaped block dicts (memoryview
    k/v, so crc verify / unpack / scatter reuse the v2 receiver unchanged)."""
    mv = memoryview(blob)
    off = 0
    out: list[dict] = []
    for m in meta:
        blk = dict(m)
        blk["k"] = mv[off:off + m["k_len"]]
        off += m["k_len"]
        blk["v"] = mv[off:off + m["v_len"]]
        off += m["v_len"]
        out.append(blk)
    if off != len(mv):
        raise ValueError(f"blob length mismatch: meta declares {off}, body has {len(mv)}")
    return out


def refuse_recurrent(core, what: str) -> None:
    """Page transfer moves pages, and a model with recurrent layers keeps the
    rest of a sequence in a state slot that no page holds: refused by name
    (snapshots of the state at page boundaries are not built). So is a model
    with a page pool per layer kind, whose sequence holds pages of two id spaces."""
    if getattr(core, "state_slots", None) is not None:
        raise NotImplementedError(
            f"{core.runner.cfg.name}: {what} is not served for a model with recurrent layers: a sequence's state "
            "lives in a slot beside its pages, and page transfer would move the pages alone")
    if getattr(core, "window_allocator", None) is not None:
        raise NotImplementedError(
            f"{core.runner.cfg.name}: {what} is not served for a model with a page pool per layer kind (window and "
            "full layers mixed): a transfer moves a page across every layer by one id, and such a model's ids name "
            "pages of one kind's pool")


class KvTransferService(AsyncEngine[Any, dict]):
    """Served by decode workers: ingests KV blocks into the local cache.

    Two ingestion paths share this service: the TCP stream below (DCN
    fallback, host-bounced) and :meth:`inject_from` (device path — pages
    move src-device -> dst-device through ``disagg/device_transfer.py``
    without touching the host). Both record bytes/seconds; ``stats()``
    reports cumulative bandwidth, a tracked metric (BASELINE.md).
    """

    #: Staged pull state older than this is assumed abandoned (sender died
    #: between phases) and rolled back on the next service interaction.
    PENDING_PULL_MAX_AGE = 120.0

    def __init__(self, core: EngineCore) -> None:
        refuse_recurrent(core, "the KV transfer service")
        self.core = core
        self._completions: dict[str, asyncio.Event] = {}
        # request_id -> (pinned, staged, parents, t_monotonic): pages staged
        # by a pull_query, awaiting the matching pull (two-phase protocol).
        self._pending_pulls: dict[str, tuple[list[int], list, list, float]] = {}
        # request_id -> in-flight chunk stream (wire protocol v2 or v3).
        self._streams: dict[str, _StreamSession] = {}
        self._sweeper: asyncio.Task | None = None
        self.blocks_received = 0
        self.bytes_received = 0
        self.transfer_seconds = 0.0
        self.scatter_seconds = 0.0
        self.device_path_blocks = 0
        self.crc_failures = 0
        self.rollbacks = 0
        # Which path served each completed transfer (ISSUE 8 tentpole #4):
        # device_colocated / device_pull / host_striped / host_chunked /
        # host_monolithic -> {"transfers", "bytes"}.
        self.path_stats: dict[str, dict[str, int]] = {}
        # Wire v3: service-wide out-of-order staging budget + accounting.
        self._staging_budget = staging_budget_bytes()
        self._staged_bytes = 0
        self._wire_conns = 0  # open striped data-plane connections
        self._wake = asyncio.Event()  # pulsed when staging bytes are freed

    def _record_path(self, path: str, nbytes: int) -> None:
        d = self.path_stats.setdefault(path, {"transfers": 0, "bytes": 0})
        d["transfers"] += 1
        d["bytes"] += nbytes

    def _pulse_budget(self) -> None:
        ev = self._wake
        self._wake = asyncio.Event()
        ev.set()

    def start_sweeper(self, interval: float | None = None) -> "KvTransferService":
        """Run :meth:`_sweep_pending_pulls` on a timer, so staging abandoned
        by a dead sender is reclaimed even when no further transfer traffic
        arrives (the in-band sweep in :meth:`generate` only fires on
        interaction — ADVICE r4). Returns self so callers can register it
        for ``close()``."""
        interval = interval or self.PENDING_PULL_MAX_AGE / 4

        async def _loop() -> None:
            while True:
                await asyncio.sleep(interval)
                try:
                    self._sweep_pending_pulls()
                except Exception:
                    # A sweep failure must not kill the task (or surface as a
                    # stale exception out of close()) — the next tick retries.
                    logger.exception("pending-pull sweep failed")

        if self._sweeper is None:
            self._sweeper = asyncio.create_task(_loop(), name="kv-transfer-sweeper")
        return self

    async def close(self) -> None:
        if self._sweeper is not None:
            self._sweeper.cancel()
            try:
                await self._sweeper
            except asyncio.CancelledError:
                pass
            self._sweeper = None

    def stats(self) -> dict:
        gbps = (self.bytes_received / 1e9) / self.transfer_seconds if self.transfer_seconds else 0.0
        return {
            "blocks": self.blocks_received,
            "device_path_blocks": self.device_path_blocks,
            "bytes": self.bytes_received,
            "seconds": round(self.transfer_seconds, 6),
            "scatter_s": round(self.scatter_seconds, 6),
            "streams_in_flight": len(self._streams),
            "gbytes_per_sec": round(gbps, 6),
            "crc_failures": self.crc_failures,
            "rollbacks": self.rollbacks,
            "wire_conns": self._wire_conns,
            "staged_bytes": self._staged_bytes,
            "paths": {p: dict(d) for p, d in self.path_stats.items()},
        }

    # -- staging (shared by the TCP and device ingestion paths) ------------

    def _stage_chain(self, items) -> tuple[list[int], list[tuple[int, int, Any]]]:
        """Pin already-present blocks; allocate a destination page per miss.

        ``items``: (block_hash, payload) in chain order; stops at pool
        exhaustion. Returns ``(pinned_hits, staged)`` with staged =
        ``[(dst_pid, block_hash, payload), ...]``. Hits are *pinned*
        (refcount++) so the allocations here can't evict them mid-chain —
        the caller must release them. Staged pages are uncommitted
        (refcount 1): finish with :meth:`_commit_staged` or roll back with
        :meth:`_release_staged`.
        """
        alloc = self.core.allocator
        pinned: list[int] = []
        staged: list[tuple[int, int, Any]] = []
        for h, payload in items:
            hit = alloc.acquire_cached(h)  # already have it (races are benign)
            if hit is not None:
                pinned.append(hit)
                continue
            try:
                [pid] = alloc.allocate(1)
            except OutOfPagesError:
                logger.warning("kv injection out of pages after %d blocks", len(staged))
                break
            staged.append((pid, h, payload))
        return pinned, staged

    def _commit_staged(self, entries) -> None:
        """``entries``: (dst_pid, hash, parent_hash, tokens) — publish each
        written page to the prefix cache and drop the staging refcount."""
        alloc = self.core.allocator
        for pid, h, parent, tokens in entries:
            alloc.commit(pid, h, parent, tokens)
            alloc.release([pid])  # refcount 0: lives as prefix cache
            self.blocks_received += 1

    def _release_staged(self, staged) -> None:
        # Uncommitted pages: release returns them to the free list instead
        # of stranding them at refcount 1 forever.
        self.core.allocator.release([pid for pid, _h, _p in staged])

    async def inject_from(self, src_core: EngineCore, block_hashes: list[int], request_id: str = "") -> int:
        """Device-path injection: pull the hash chain's pages straight from a
        co-located engine's cache over the device interconnect.

        Returns the number of chain blocks now present at the destination
        (already-cached hits + freshly transferred). On a transfer failure
        the staged destination pages are released and the error propagates —
        the caller falls back to the TCP path.
        """
        from dynamo_tpu.disagg.device_transfer import DeviceKvTransfer

        src_alloc = src_core.allocator
        src_pages = src_alloc.match_prefix(block_hashes)  # acquires refcounts
        pinned: list[int] = []
        staged: list[tuple[int, int, Any]] = []  # payload = source page id
        try:
            pinned, staged = self._stage_chain(
                (block_hashes[i], src_pid) for i, src_pid in enumerate(src_pages)
            )
            if staged:
                xfer = DeviceKvTransfer()
                loop = asyncio.get_running_loop()
                try:
                    await loop.run_in_executor(
                        None, xfer.transfer,
                        src_core.runner, [src_pid for _pid, _h, src_pid in staged],
                        self.core.runner, [pid for pid, _h, _s in staged],
                    )
                except Exception:
                    self._release_staged(staged)
                    staged = []
                    raise
                self._commit_staged(
                    (pid, h, src_alloc.page_parent_hash(src_pid), ())
                    for pid, h, src_pid in staged
                )
                self.transfer_seconds += xfer.stats.seconds
                self.bytes_received += xfer.stats.bytes
                self.device_path_blocks += len(staged)
                self._record_path("device_colocated", xfer.stats.bytes)
        finally:
            self.core.allocator.release(pinned)
            src_alloc.release(src_pages)
        ev = self._completions.get(request_id)
        if ev is not None:
            ev.set()
        return len(pinned) + len(staged)

    def _abort_pull(self, request_id: str) -> None:
        """Roll back pages staged by a pull_query whose pull never arrived."""
        pending = self._pending_pulls.pop(request_id, None)
        if pending is None:
            return
        pinned, staged, _parents, _t0 = pending
        self._release_staged(staged)
        self.core.allocator.release(pinned)

    def _sweep_pending_pulls(self) -> None:
        now = time.monotonic()
        for rid in [
            rid for rid, (_p, _s, _pa, t0) in self._pending_pulls.items()
            if now - t0 > self.PENDING_PULL_MAX_AGE
        ]:
            logger.warning("abandoned pull staging for %s rolled back", rid)
            self._abort_pull(rid)
        for rid in [
            rid for rid, sess in self._streams.items()
            if now - sess.t_last > self.PENDING_PULL_MAX_AGE
        ]:
            logger.warning("abandoned chunk stream for %s rolled back", rid)
            self._abort_stream(rid)

    # -- wire protocol v2: streaming chunk ingestion -----------------------

    def _abort_stream(self, request_id: str) -> None:
        """Drop a chunk stream's session and its chain refcounts.

        Blocks committed by earlier chunks STAY in the prefix cache — an
        incremental commit only ever publishes a valid, chain-consistent
        prefix — but releasing the pins makes them ordinary evictable cache
        again, so a dead sender reclaims to a clean allocator state.
        """
        sess = self._streams.pop(request_id, None)
        if sess is None:
            return
        self.rollbacks += 1
        self.core.allocator.release(sess.pinned)
        # Wire v3: drop out-of-order staging, return its budget share, and
        # wake every stripe handler parked on a deferred ack or the pump.
        sess.dead = True
        if sess.staged_bytes:
            self._staged_bytes -= sess.staged_bytes
            sess.staging.clear()
            sess.staged_bytes = 0
        self._pulse_budget()
        sess.pulse()

    async def _ingest_chunk(self, request_id: str, request: dict) -> dict:
        """One v2 chunk: stage, scatter (one batched ``write_pages``), and
        commit incrementally, keeping the whole chain pinned until ``last``.

        Any failure rolls the stream back (:meth:`_abort_stream`): the
        uncommitted staged pages return to the free pool and the response's
        ``stream_error`` tells the sender to fall back to the monolithic
        path. Out-of-order or unknown ``seq`` is a protocol error and also
        aborts — a reconnecting sender restarts at seq 0, which replaces
        any stale session for the same request id.
        """
        if FAULTS.armed:
            FAULTS.fire("kv.chunk.recv")
        seq = int(request.get("seq", 0))
        last = bool(request.get("last"))
        blocks = request.get("blocks", [])
        sess = self._streams.get(request_id)
        if seq == 0:
            if sess is not None:
                if sess.next_seq == 0 and not sess.pinned:
                    # crc-retry of the very first chunk: the session never
                    # ingested anything, so replacing it is not a rollback.
                    self._streams.pop(request_id, None)
                else:
                    self._abort_stream(request_id)
            sess = _StreamSession()
            self._streams[request_id] = sess
        if sess is None or seq != sess.next_seq:
            self._abort_stream(request_id)
            return {
                "request_id": request_id, "seq": seq,
                "stream_error": f"unexpected seq {seq}"
                + (f" (want {sess.next_seq})" if sess else " (no session)"),
            }
        bad = sum(1 for blk in blocks if not block_crc_ok(blk))
        if bad:
            # Corruption is retryable, not fatal: the session is untouched
            # (next_seq unchanged) so the sender can re-send this exact seq.
            self.crc_failures += bad
            sess.t_last = time.monotonic()
            logger.warning(
                "kv chunk crc mismatch (req=%s seq=%d, %d/%d blocks); asking sender to retry",
                request_id, seq, bad, len(blocks),
            )
            return {"request_id": request_id, "seq": seq, "crc_error": True, "bad_blocks": bad}
        t0 = time.perf_counter()
        staged: list[tuple[int, int, Any]] = []
        try:
            sess.total_blocks += len(blocks)
            if not sess.truncated and blocks:
                pinned, staged = self._stage_chain((blk["hash"], blk) for blk in blocks)
                sess.pinned.extend(pinned)
                if len(pinned) + len(staged) < len(blocks):
                    sess.truncated = True  # pool exhausted: drop the tail
                if staged:
                    payloads = [unpack_payload(blk) for _pid, _h, blk in staged]
                    t_sc = time.perf_counter()
                    await asyncio.get_running_loop().run_in_executor(
                        None, self.core.runner.write_pages,
                        [pid for pid, _h, _b in staged],
                        [k for k, _ in payloads], [v for _, v in payloads],
                    )
                    dt_sc = time.perf_counter() - t_sc
                    self.scatter_seconds += dt_sc
                    observe_kv_phase("scatter", dt_sc, core=self.core)
                    # Receiver-side phase span, linked into the sender's
                    # trace when the chunk carries one.
                    record_span(
                        "kv_scatter", dt_sc * 1e3,
                        trace=TraceContext.from_dict(request.get("trace")),
                        request_id=request_id, seq=seq, blocks=len(staged),
                    )
                    alloc = self.core.allocator
                    for pid, h, blk in staged:
                        # Incremental commit: publish, but KEEP the staging
                        # refcount as the session's pin (released at stream
                        # end) so later chunks can't evict the chain prefix.
                        alloc.commit(pid, h, blk.get("parent"), tuple(blk.get("tokens", ())))
                        sess.pinned.append(pid)
                        self.blocks_received += 1
                    chunk_bytes = sum(k.nbytes + v.nbytes for k, v in payloads)
                    self.bytes_received += chunk_bytes
                    sess.bytes += chunk_bytes
                sess.injected += len(pinned) + len(staged)
            self.transfer_seconds += time.perf_counter() - t0
        except Exception:
            self._release_staged(staged)
            self._abort_stream(request_id)
            logger.exception(
                "kv chunk ingestion failed (req=%s seq=%d); stream rolled back",
                request_id, seq,
            )
            return {"request_id": request_id, "seq": seq, "stream_error": "ingestion failed"}
        sess.next_seq = seq + 1
        sess.t_last = time.monotonic()
        summary = {"request_id": request_id, "seq": seq, "injected": sess.injected, "last": last}
        if last:
            self._streams.pop(request_id, None)
            self.core.allocator.release(sess.pinned)
            self._record_path("host_chunked", sess.bytes)
            summary["total"] = sess.total_blocks
            summary["stats"] = self.stats()
            ev = self._completions.get(request_id)
            if ev is not None:
                ev.set()
        return summary

    # -- wire protocol v3: striped duplex ingestion ------------------------

    def _attach_striped(self, request_id: str, request: dict) -> _StreamSession | None:
        """Attach a stripe connection to its session, creating it on first
        arrival. Stripes of one transfer share a sender-chosen ``sid``; a
        different sid means a retry/new attempt and replaces any stale
        session (rolling it back iff it had ingested anything, mirroring the
        v2 seq-0 rule)."""
        sid = str(request.get("sid", ""))
        total = int(request.get("total_chunks", 0))
        if not sid or total <= 0:
            return None
        sess = self._streams.get(request_id)
        if sess is not None and sess.sid == sid and not sess.dead:
            return sess
        if sess is not None:
            if sess.next_seq == 0 and not sess.pinned:
                self._streams.pop(request_id, None)
                sess.dead = True
                sess.pulse()
            else:
                self._abort_stream(request_id)
        sess = _StreamSession(
            sid=sid, stripes=int(request.get("stripes", 1)), total_chunks=total,
            trace=request.get("trace"),
        )
        self._streams[request_id] = sess
        sess.pump = asyncio.create_task(
            self._striped_pump(request_id, sess), name=f"kv-stripe-pump-{request_id}"
        )
        return sess

    async def _striped_pump(self, request_id: str, sess: _StreamSession) -> None:
        """Per-session reassembly pump: commits staged chunks strictly in seq
        order, so the incremental-commit invariant (every committed prefix is
        a valid cache state) is exactly v2's. Each commit publishes its ack
        into ``sess.acks`` and pulses the stripe handler that parked on it."""
        total = sess.total_chunks or 0
        try:
            while not sess.dead and sess.next_seq < total:
                # Grab the generation event BEFORE checking state: a pulse
                # between check and wait replaces the event, and waiting on
                # the replacement would miss it.
                ev = sess.wake
                entry = sess.staging.pop(sess.next_seq, None)
                if entry is None:
                    await ev.wait()
                    continue
                blocks, nbytes = entry
                sess.staged_bytes -= nbytes
                self._staged_bytes -= nbytes
                self._pulse_budget()
                seq = sess.next_seq
                ack = await self._commit_striped_chunk(request_id, sess, seq, blocks, nbytes)
                sess.acks[seq] = ack
                sess.pulse()
                # The commit advanced the cursor: stripes parked on the
                # budget whose seq is now <= next_seq must re-check (their
                # admission no longer needs budget headroom).
                self._pulse_budget()
                if ack.get("stream_error"):
                    return
        except asyncio.CancelledError:
            raise
        except Exception:
            logger.exception("kv stripe pump failed (req=%s); stream rolled back", request_id)
            if self._streams.get(request_id) is sess:
                self._abort_stream(request_id)

    async def _commit_striped_chunk(
        self, request_id: str, sess: _StreamSession, seq: int, blocks: list[dict], nbytes: int
    ) -> dict:
        """Scatter + incrementally commit one in-seq-order chunk — the v2
        ``_ingest_chunk`` body on v3-framed blocks. Returns the chunk's ack;
        the final chunk's ack carries the stream summary."""
        total = sess.total_chunks or 0
        t0 = time.perf_counter()
        staged: list[tuple[int, int, Any]] = []
        try:
            sess.total_blocks += len(blocks)
            if not sess.truncated and blocks:
                pinned, staged = self._stage_chain((blk["hash"], blk) for blk in blocks)
                sess.pinned.extend(pinned)
                if len(pinned) + len(staged) < len(blocks):
                    sess.truncated = True  # pool exhausted: drop the tail
                if staged:
                    payloads = [unpack_payload(blk) for _pid, _h, blk in staged]
                    t_sc = time.perf_counter()
                    await asyncio.get_running_loop().run_in_executor(
                        None, self.core.runner.write_pages,
                        [pid for pid, _h, _b in staged],
                        [k for k, _ in payloads], [v for _, v in payloads],
                    )
                    dt_sc = time.perf_counter() - t_sc
                    self.scatter_seconds += dt_sc
                    observe_kv_phase("scatter", dt_sc, core=self.core)
                    record_span(
                        "kv_scatter", dt_sc * 1e3,
                        trace=TraceContext.from_dict(sess.trace),
                        request_id=request_id, seq=seq, blocks=len(staged),
                    )
                    alloc = self.core.allocator
                    for pid, h, blk in staged:
                        alloc.commit(pid, h, blk.get("parent"), tuple(blk.get("tokens", ())))
                        sess.pinned.append(pid)
                        self.blocks_received += 1
                    chunk_bytes = sum(k.nbytes + v.nbytes for k, v in payloads)
                    self.bytes_received += chunk_bytes
                    sess.bytes += chunk_bytes
                sess.injected += len(pinned) + len(staged)
            self.transfer_seconds += time.perf_counter() - t0
        except Exception:
            self._release_staged(staged)
            if self._streams.get(request_id) is sess:
                self._abort_stream(request_id)
            logger.exception(
                "kv striped chunk ingestion failed (req=%s seq=%d); stream rolled back",
                request_id, seq,
            )
            return {"request_id": request_id, "seq": seq, "stream_error": "ingestion failed"}
        sess.next_seq = seq + 1
        sess.t_last = time.monotonic()
        ack = {"request_id": request_id, "seq": seq, "injected": sess.injected,
               "last": seq == total - 1}
        if seq == total - 1:
            self._streams.pop(request_id, None)
            self.core.allocator.release(sess.pinned)
            self._record_path("host_striped", sess.bytes)
            ack["total"] = sess.total_blocks
            ack["stats"] = self.stats()
            ev = self._completions.get(request_id)
            if ev is not None:
                ev.set()
        return ack

    async def _ingest_striped_chunk(self, request_id: str, sess: _StreamSession, msg: dict) -> dict:
        """One stripe arrival: crc-verify, admit (staging out-of-order chunks
        under the service-wide byte budget; in-seq chunks are always admitted
        so the stream can't deadlock on its own backpressure), then park
        until the pump commits this seq and hands back the ack.

        crc failure responds immediately without touching the session — the
        sender retries the same seq on the same stripe, exactly v2's
        retry-before-rollback contract, now per stripe."""
        if FAULTS.armed:
            FAULTS.fire("kv.chunk.recv")  # fires per stripe per chunk
        seq = int(msg.get("seq", -1))
        total = sess.total_chunks or 0
        blocks = blob_to_blocks(msg.get("blocks", []), msg.get("blob", b""))
        bad = sum(1 for blk in blocks if not block_crc_ok(blk))
        if bad:
            self.crc_failures += bad
            sess.t_last = time.monotonic()
            logger.warning(
                "kv chunk crc mismatch (req=%s seq=%d, %d/%d blocks); asking sender to retry",
                request_id, seq, bad, len(blocks),
            )
            return {"request_id": request_id, "seq": seq, "crc_error": True, "bad_blocks": bad}
        nbytes = sum(len(blk["k"]) + len(blk["v"]) for blk in blocks)
        # Budget backpressure applies only to chunks AHEAD of the commit
        # cursor; the cursor chunk always proceeds, which also drains staging.
        while True:
            ev = self._wake
            if (sess.dead or seq <= sess.next_seq
                    or self._staged_bytes + nbytes <= self._staging_budget):
                break
            await ev.wait()
        if sess.dead or self._streams.get(request_id) is not sess:
            return {"request_id": request_id, "seq": seq, "stream_error": "no session"}
        if seq < sess.next_seq or seq >= total or seq in sess.staging or seq in sess.acks:
            self._abort_stream(request_id)
            return {
                "request_id": request_id, "seq": seq,
                "stream_error": f"unexpected seq {seq} (want {sess.next_seq})",
            }
        sess.staging[seq] = (blocks, nbytes)
        sess.staged_bytes += nbytes
        self._staged_bytes += nbytes
        sess.t_last = time.monotonic()
        sess.pulse()  # wake the pump
        while True:
            ev = sess.wake
            if sess.dead or seq in sess.acks:
                break
            await ev.wait()
        ack = sess.acks.pop(seq, None)
        if ack is None:
            return {"request_id": request_id, "seq": seq, "stream_error": "stream aborted"}
        return ack

    async def duplex(self, request: Any, inbound: AsyncIterator[dict], context: Context) -> AsyncIterator[dict]:
        """Wire v3 data plane: one duplex connection per stripe.

        The opening request is ``{"request_id", "stream_open": true, "sid",
        "stripe", "stripes", "total_chunks"}``; every inbound message is one
        chunk — msgpack head ``{"seq", "blocks": [meta...], "last"}`` plus
        the raw k/v blob — and gets exactly one ack, deferred until the
        chunk commits. When the last stripe connection drops while the
        session is incomplete, the sender died: roll back immediately
        instead of waiting for the abandoned-stream sweep."""
        request_id = str(request.get("request_id", ""))
        sess = self._attach_striped(request_id, request) if request.get("stream_open") else None
        if sess is None:
            yield {"request_id": request_id,
                   "stream_error": "expected stream_open with sid/total_chunks"}
            return
        sess.conns += 1
        self._wire_conns += 1
        try:
            async for msg in inbound:
                try:
                    resp = await self._ingest_striped_chunk(request_id, sess, msg)
                except Exception:
                    logger.exception(
                        "kv striped ingest failed (req=%s); stream rolled back", request_id
                    )
                    if self._streams.get(request_id) is sess:
                        self._abort_stream(request_id)
                    resp = {"request_id": request_id, "seq": msg.get("seq"),
                            "stream_error": "ingestion failed"}
                yield resp
                if resp.get("stream_error"):
                    return
        finally:
            sess.conns -= 1
            self._wire_conns -= 1
            if sess.conns == 0 and self._streams.get(request_id) is sess:
                logger.warning(
                    "kv stripe connections for %s all closed mid-stream; rolling back",
                    request_id,
                )
                self._abort_stream(request_id)

    async def _handle_pull_query(self, request_id: str, query: dict) -> dict:
        """Phase 1 of the two-phase device-path pull: report which chain
        blocks are missing locally, staging destination pages for them.

        The sender gathers and offers ONLY the missed pages afterwards — a
        fully-cached chain completes right here with zero gather work and
        zero transfer-server staging on either side (the un-pulled-offer
        device-memory leak class, ADVICE r3)."""
        import time

        from dynamo_tpu.disagg.pull_transport import device_pull_supported

        if not device_pull_supported():
            return {"request_id": request_id, "injected": 0, "pull_unsupported": True}
        self._abort_pull(request_id)  # a re-query replaces stale staging
        hashes = list(query["hashes"])
        parents = list(query["parents"])
        pinned, staged = self._stage_chain((h, i) for i, h in enumerate(hashes))
        if not staged:
            # Warm cache: the whole chain is already here.
            self.core.allocator.release(pinned)
            ev = self._completions.get(request_id)
            if ev is not None:
                ev.set()
            return {
                "request_id": request_id,
                "injected": len(pinned),
                "total": len(hashes),
                "miss": [],
                "pull": True,
                "stats": self.stats(),
            }
        self._pending_pulls[request_id] = (pinned, staged, parents, time.monotonic())
        return {
            "request_id": request_id,
            "miss": [i for _pid, _h, i in staged],
            "hits": len(pinned),
            "pull": True,
        }

    async def _ingest_pull(self, request_id: str, pull: dict) -> dict:
        """Phase 2: pull the sender's staged miss-page stack through the
        transfer engine (``disagg/pull_transport.py``) and scatter it into
        the pages staged by :meth:`_handle_pull_query`.

        Returns the summary dict; ``pull_failed`` tells the sender to fall
        back to the packed-bytes TCP path (its offer stays un-pulled, so it
        must drain it — ``finish_offer(consumed=False)``)."""
        import time

        import jax
        import numpy as np

        from dynamo_tpu.disagg.pull_transport import get_transport

        pending = self._pending_pulls.pop(request_id, None)
        if pending is None:
            logger.warning("pull for %s without a pending pull_query", request_id)
            return {"request_id": request_id, "injected": 0, "pull_failed": True}
        pinned, staged, parents, _t0 = pending
        t0 = time.perf_counter()
        wire_pulled = False  # whether the transfer-engine pull itself completed
        try:
            runner = self.core.runner
            sharding = runner.k_cache.sharding
            k_sds = jax.ShapeDtypeStruct(
                tuple(pull["k_shape"]), np.dtype(pull["k_dtype"]), sharding=sharding
            )
            v_sds = jax.ShapeDtypeStruct(
                tuple(pull["v_shape"]), np.dtype(pull["v_dtype"]), sharding=sharding
            )
            transport = get_transport()
            try:
                k, v = await asyncio.get_running_loop().run_in_executor(
                    None, transport.pull, pull["address"], pull["uuid"], [k_sds, v_sds]
                )
                wire_pulled = True
                # The stack holds exactly the missed pages (staged order),
                # padded to a power of two; slice off the pad device-side.
                n = len(staged)
                await asyncio.get_running_loop().run_in_executor(
                    None, self.core.runner.write_pages,
                    [pid for pid, _h, _i in staged], k[:, :n], v[:, :n],
                )
            except Exception:
                self._release_staged(staged)
                logger.exception("device pull ingestion failed; sender will fall back")
                # "pulled" tells the sender whether its offer was consumed:
                # a consumed one-shot offer must NOT be drained again (a
                # second pull of the same uuid can block forever).
                return {
                    "request_id": request_id, "injected": 0,
                    "pull_failed": True, "pulled": wire_pulled,
                }
            self._commit_staged(
                (pid, h, parents[i], ()) for pid, h, i in staged
            )
            pulled_bytes = (
                int(np.prod(pull["k_shape"])) * np.dtype(pull["k_dtype"]).itemsize
                + int(np.prod(pull["v_shape"])) * np.dtype(pull["v_dtype"]).itemsize
            )
            self.bytes_received += pulled_bytes
            self.transfer_seconds += time.perf_counter() - t0
            self.device_path_blocks += len(staged)
            self._record_path("device_pull", pulled_bytes)
        finally:
            self.core.allocator.release(pinned)
        ev = self._completions.get(request_id)
        if ev is not None:
            ev.set()
        return {
            "request_id": request_id,
            "injected": len(pinned) + len(staged),
            "total": pull.get("total", len(pinned) + len(staged)),
            "pull": True,
            "stats": self.stats(),
        }

    def expect(self, request_id: str) -> asyncio.Event:
        """Register interest in a transfer's completion (disagg operator)."""
        ev = self._completions.setdefault(request_id, asyncio.Event())
        return ev

    def forget(self, request_id: str) -> None:
        self._completions.pop(request_id, None)

    async def generate(self, request: Any, context: Context) -> AsyncIterator[dict]:
        """Request forms:

        - ``{"request_id", "seq", "blocks", "last"}`` — wire protocol v2:
          one chunk of a pipelined stream (:meth:`_ingest_chunk`);
        - ``{"request_id", "stream_abort": true}`` — sender abandoned a v2
          stream mid-flight; roll back its session;
        - ``{"request_id", "blocks": [packed blocks...]}`` — v1 monolithic
          packed-bytes message (last-resort fallback);
        - ``{"request_id", "pull_query": {hashes, parents}}`` — phase 1 of
          the device-path pull (:meth:`_handle_pull_query`);
        - ``{"request_id", "pull": descriptor}`` — phase 2
          (:meth:`_ingest_pull`);
        - ``{"request_id", "pull_abort": true}`` — sender abandoned a
          staged pull (falls back to packed bytes); roll back staging.

        Responds with one summary item per message. On the v1 path the whole
        chain is staged (allocate + unpack) then written as one batched
        scatter and committed; a failure anywhere releases the staged pages,
        so the cache keeps only previously-present blocks — still a valid,
        chain-consistent prefix.
        """
        request_id = request.get("request_id", "")
        # Reclaim staging abandoned by dead senders on EVERY interaction,
        # not just pull queries — otherwise packed-bytes-only traffic never
        # frees it.
        self._sweep_pending_pulls()
        if "seq" in request:
            yield await self._ingest_chunk(request_id, request)
            return
        if request.get("stream_abort"):
            self._abort_stream(request_id)
            yield {"request_id": request_id, "aborted": True}
            return
        if request.get("pull_query") is not None:
            yield await self._handle_pull_query(request_id, request["pull_query"])
            return
        if request.get("pull") is not None:
            yield await self._ingest_pull(request_id, request["pull"])
            return
        if request.get("pull_abort"):
            self._abort_pull(request_id)
            yield {"request_id": request_id, "aborted": True}
            return
        # Packed-bytes path: supersedes any staged pull or stream for this
        # request.
        self._abort_pull(request_id)
        self._abort_stream(request_id)
        blocks = request.get("blocks", [])
        first_bad = next((i for i, blk in enumerate(blocks) if not block_crc_ok(blk)), None)
        if first_bad is not None:
            # v1 has no per-chunk retry protocol: truncate at the first
            # corrupt block (every prefix of the hash chain is a valid cache
            # state; committing past a gap would publish unreachable blocks).
            self.crc_failures += 1
            logger.warning(
                "v1 kv payload crc mismatch at block %d/%d (req=%s); chain truncated",
                first_bad, len(blocks), request_id,
            )
            blocks = blocks[:first_bad]
        injected = 0
        t0 = time.perf_counter()
        pinned: list[int] = []
        staged: list[tuple[int, int, Any]] = []  # payload = packed block dict
        try:
            pinned, staged = self._stage_chain((blk["hash"], blk) for blk in blocks)
            injected += len(pinned)
            if staged:
                payloads = [unpack_payload(blk) for _pid, _h, blk in staged]
                # One stacked transfer + one scatter for the whole chain,
                # instead of a dispatch round-trip per page.
                t_sc = time.perf_counter()
                await asyncio.get_running_loop().run_in_executor(
                    None, self.core.runner.write_pages,
                    [pid for pid, _h, _b in staged],
                    [k for k, _ in payloads], [v for _, v in payloads],
                )
                dt_sc = time.perf_counter() - t_sc
                self.scatter_seconds += dt_sc
                observe_kv_phase("scatter", dt_sc, core=self.core)
                record_span(
                    "kv_scatter", dt_sc * 1e3,
                    trace=TraceContext.from_dict(request.get("trace")),
                    request_id=request_id, blocks=len(staged), protocol="v1",
                )
                self._commit_staged(
                    (pid, h, blk.get("parent"), tuple(blk.get("tokens", ())))
                    for pid, h, blk in staged
                )
                injected += len(staged)
                v1_bytes = sum(k.nbytes + v.nbytes for k, v in payloads)
                self.bytes_received += v1_bytes
                self.transfer_seconds += time.perf_counter() - t0
                self._record_path("host_monolithic", v1_bytes)
        except Exception:
            self._release_staged(staged)
            logger.exception("kv injection failed; dropped %d staged blocks", len(staged))
        finally:
            self.core.allocator.release(pinned)
        ev = self._completions.get(request_id)
        if ev is not None:
            ev.set()
        yield {"request_id": request_id, "injected": injected, "total": len(blocks), "stats": self.stats()}


async def send_blocks(
    transport: Transport,
    address: str,
    request_id: str,
    blocks: list[dict],
    *,
    context: Context | None = None,
    trace: TraceContext | None = None,
    core: EngineCore | None = None,
) -> dict:
    """Sender-side: ship packed blocks to a decode worker's transfer endpoint.

    ``core`` (when the caller has one) routes the wire-phase observation to
    that engine's metrics registry instead of the process-global fallback.
    """
    context = context or Context()
    msg: dict = {"request_id": request_id, "blocks": blocks}
    if trace is not None:
        msg["trace"] = trace.to_dict()
    t0 = time.perf_counter()
    result: dict = {}
    async for item in transport.generate(address, msg, context):
        result = item
    dt = time.perf_counter() - t0
    observe_kv_phase("wire", dt, core=core)
    record_span("kv_wire", dt * 1e3, trace=trace, request_id=request_id, blocks=len(blocks), protocol="v1")
    return result


async def send_blocks_chunked(
    transport: Transport,
    address: str,
    request_id: str,
    core: EngineCore,
    block_hashes: list[int],
    *,
    chunk_pages: int | None = None,
    streams: int | None = None,
    context: Context | None = None,
    trace: TraceContext | None = None,
) -> dict:
    """Pipelined chunked transfer of a committed hash chain (wire v2/v3).

    With ``streams >= 1`` (default: ``DYN_KV_WIRE_STREAMS``) and a transport
    that has a duplex data plane, the chunks are striped round-robin across
    that many persistent connections as raw blob frames
    (:func:`_send_blocks_striped`); when the transport or receiver lacks
    duplex support — or ``streams == 0`` pins the legacy protocol — the
    single-stream v2 loop below runs instead.

    The chain's pages are shipped in ``chunk_pages`` chunks (default:
    ``DYN_KV_CHUNK_PAGES``) with the three phases double-buffered: chunk
    N+1's batched gather + device->host DMA is dispatched
    (``read_pages_async``, lock held for the dispatch only) BEFORE chunk N
    is packed and sent, so the D2H copy rides under chunk N's pack + TCP
    round trip and the sender's decode loop interleaves between chunks. The
    receiver scatters and commits each chunk incrementally
    (:meth:`KvTransferService._ingest_chunk` /
    :meth:`KvTransferService.duplex`).

    Returns the receiver's final summary, augmented with ``bytes`` and
    per-phase wall times ``phases = {gather_s, pack_s, wire_s}`` (phase sums
    exceed the end-to-end time exactly when the overlap is real — that is
    the number the kv_wire bench tracks). On the striped path each phase is
    per-stream-attributed wall time (busy-interval union across stripes,
    :class:`_PhaseClock`), never a sum over concurrent streams, so the
    invariant survives striping. Raises on a mid-stream failure after
    telling the receiver to roll back; callers fall back to the v1
    monolithic path.
    """
    chunk_pages = default_chunk_pages() if chunk_pages is None else chunk_pages
    streams = default_wire_streams() if streams is None else streams
    if streams >= 1:
        try:
            return await _send_blocks_striped(
                transport, address, request_id, core, block_hashes,
                chunk_pages=chunk_pages, streams=streams, context=context, trace=trace,
            )
        except DuplexUnsupportedError:
            logger.debug("kv wire v3 unavailable for %s; using v2", address)
    context = context or Context()
    loop = asyncio.get_running_loop()
    allocator = core.allocator
    runner = core.runner
    # Hold the chain's refcounts for the whole stream: the gather of chunk
    # N+1 is in flight while chunk N is on the wire, and eviction must not
    # reuse any of these pages until the last chunk is packed.
    pages = await loop.run_in_executor(None, allocator.match_prefix, block_hashes)
    phases = {"gather_s": 0.0, "pack_s": 0.0, "wire_s": 0.0}
    total_bytes = 0
    crc_retries = 0
    streaming = False  # any chunk reached the receiver (it may hold session state)
    try:
        if not pages:
            return {"request_id": request_id, "injected": 0, "total": 0, "phases": phases, "bytes": 0}
        hashes = list(block_hashes[: len(pages)])
        parents = [allocator.page_parent_hash(pid) for pid in pages]
        chunks = [
            (pages[off : off + chunk_pages], hashes[off : off + chunk_pages],
             parents[off : off + chunk_pages])
            for off in range(0, len(pages), chunk_pages)
        ]

        def _dispatch(pids: list[int]):
            return time.perf_counter(), runner.read_pages_async(pids)

        t_dispatch, inflight = await loop.run_in_executor(None, _dispatch, chunks[0][0])
        result: dict = {}
        for i, (_pids, chunk_hashes, chunk_parents) in enumerate(chunks):
            payloads = await loop.run_in_executor(None, inflight.wait)
            phases["gather_s"] += time.perf_counter() - t_dispatch
            if i + 1 < len(chunks):
                # Double buffer: next chunk's gather + D2H DMA starts now and
                # runs under THIS chunk's pack + wire.
                t_dispatch, inflight = await loop.run_in_executor(None, _dispatch, chunks[i + 1][0])
            t_pack = time.perf_counter()
            blocks = await loop.run_in_executor(
                None,
                lambda: [
                    pack_block(chunk_hashes[j], chunk_parents[j], [], k, v)
                    for j, (k, v) in enumerate(payloads)
                ],
            )
            phases["pack_s"] += time.perf_counter() - t_pack
            total_bytes += sum(len(b["k"]) + len(b["v"]) for b in blocks)
            wire_blocks = blocks
            if FAULTS.armed:
                if FAULTS.fire("kv.chunk.send") == "corrupt" and wire_blocks:
                    corrupted = dict(wire_blocks[0])
                    corrupted["k"] = corrupt_bytes(corrupted["k"])
                    wire_blocks = [corrupted, *wire_blocks[1:]]
            t_wire = time.perf_counter()
            streaming = True
            msg = {
                "request_id": request_id, "seq": i, "blocks": wire_blocks,
                "last": i == len(chunks) - 1,
            }
            if trace is not None:
                # The receiver's scatter spans link under the sender's span.
                msg["trace"] = trace.to_dict()
            resp = await _round_trip(transport, address, msg)
            if resp.get("crc_error"):
                # The receiver rejected the chunk but kept the session at
                # this seq: one transfer-level retry with freshly-packed
                # blocks (the clean copies, whatever got mangled in flight)
                # before giving up on the stream.
                logger.warning(
                    "kv chunk %d of %s failed crc at receiver; retrying once",
                    i, request_id,
                )
                crc_retries += 1
                msg["blocks"] = blocks
                resp = await _round_trip(transport, address, msg)
                if resp.get("crc_error"):
                    raise RuntimeError(f"kv chunk {i} failed crc after retry")
            phases["wire_s"] += time.perf_counter() - t_wire
            if resp.get("stream_error"):
                # The receiver already rolled the stream back.
                streaming = False
                raise RuntimeError(f"kv chunk stream rejected: {resp['stream_error']}")
            result = resp
        streaming = False
        result["phases"] = {k: round(v, 6) for k, v in phases.items()}
        result["bytes"] = total_bytes
        result["crc_retries"] = crc_retries
        # Sender-side phase telemetry: one span per phase (cumulative over
        # the stream) + histogram observations for the metrics plane.
        for phase, secs in (("gather", phases["gather_s"]), ("pack", phases["pack_s"]), ("wire", phases["wire_s"])):
            observe_kv_phase(phase, secs, core=core)
            record_span(
                f"kv_{phase}", secs * 1e3, trace=trace,
                request_id=request_id, chunks=len(chunks), bytes=total_bytes,
            )
        return result
    finally:
        if streaming:
            # Mid-stream failure on our side (or transport death): best-effort
            # tell the receiver to roll back its session before we fall back.
            try:
                await _round_trip(transport, address, {"request_id": request_id, "stream_abort": True})
            except Exception:
                logger.warning("stream abort for %s not delivered", request_id)
        await loop.run_in_executor(None, allocator.release, pages)


async def _send_blocks_striped(
    transport: Transport,
    address: str,
    request_id: str,
    core: EngineCore,
    block_hashes: list[int],
    *,
    chunk_pages: int,
    streams: int,
    context: Context | None = None,
    trace: TraceContext | None = None,
) -> dict:
    """Wire v3 sender: stripe the chunk sequence across ``streams`` duplex
    connections, each chunk one raw blob frame.

    One producer coroutine runs the v2 double-buffered gather (chunk N+1's
    device gather + D2H dispatched before chunk N is consumed) and feeds
    bounded per-stripe queues round-robin; each stripe task packs its chunk
    (metadata msgpack + zero-copy memoryview body), sends, and waits for the
    ack — which the receiver defers until the chunk *commits*, so at most
    ``streams`` chunks are un-acked and flow control falls out of the
    protocol. A ``crc_error`` ack retries that seq once on the same stripe
    with the clean buffers (v2's retry-before-rollback, per stripe); any
    stripe failure cancels the rest, tells the receiver to roll back, and
    raises so the caller can fall back.

    Raises :class:`DuplexUnsupportedError` (before any stream state exists)
    when the transport or receiver has no duplex plane — the caller then
    runs the v2 protocol.
    """
    open_duplex = getattr(transport, "open_duplex", None)
    if open_duplex is None:
        raise DuplexUnsupportedError("transport has no duplex data plane")
    context = context or Context()
    loop = asyncio.get_running_loop()
    allocator = core.allocator
    runner = core.runner
    pages = await loop.run_in_executor(None, allocator.match_prefix, block_hashes)
    pack_clock = _PhaseClock()
    wire_clock = _PhaseClock()
    gather_s = 0.0
    total_bytes = 0
    crc_retries = 0
    opened: list[Any] = []
    streaming = False
    try:
        if not pages:
            return {"request_id": request_id, "injected": 0, "total": 0,
                    "phases": {"gather_s": 0.0, "pack_s": 0.0, "wire_s": 0.0}, "bytes": 0}
        hashes = list(block_hashes[: len(pages)])
        parents = [allocator.page_parent_hash(pid) for pid in pages]
        chunks = [
            (pages[off: off + chunk_pages], hashes[off: off + chunk_pages],
             parents[off: off + chunk_pages])
            for off in range(0, len(pages), chunk_pages)
        ]
        n = len(chunks)
        n_stripes = max(1, min(streams, n))
        sid = os.urandom(8).hex()
        for s in range(n_stripes):
            req = {"request_id": request_id, "stream_open": True, "sid": sid,
                   "stripe": s, "stripes": n_stripes, "total_chunks": n}
            if trace is not None:
                req["trace"] = trace.to_dict()
            # The first open raises DuplexUnsupportedError on a v2-only
            # receiver — before any session state exists on either side.
            opened.append(await open_duplex(address, req, context))
        streaming = True
        queues: list[asyncio.Queue] = [asyncio.Queue(maxsize=2) for _ in range(n_stripes)]
        summary: dict = {}

        def _dispatch(pids: list[int]):
            return time.perf_counter(), runner.read_pages_async(pids)

        async def producer() -> None:
            nonlocal gather_s
            t_dispatch, inflight = await loop.run_in_executor(None, _dispatch, chunks[0][0])
            for i in range(n):
                payloads = await loop.run_in_executor(None, inflight.wait)
                gather_s += time.perf_counter() - t_dispatch
                if i + 1 < n:
                    t_dispatch, inflight = await loop.run_in_executor(
                        None, _dispatch, chunks[i + 1][0])
                await queues[i % n_stripes].put((i, payloads))
            for q in queues:
                await q.put(None)

        async def stripe(s: int) -> None:
            nonlocal summary, total_bytes, crc_retries
            st = opened[s]
            while True:
                item = await queues[s].get()
                if item is None:
                    return
                i, payloads = item
                _pids, chunk_hashes, chunk_parents = chunks[i]
                meta, bufs, nbytes = await loop.run_in_executor(
                    None, pack_chunk_blob, chunk_hashes, chunk_parents, payloads, pack_clock,
                )
                total_bytes += nbytes
                msg = {"request_id": request_id, "seq": i, "blocks": meta,
                       "last": i == n - 1}
                if trace is not None:
                    msg["trace"] = trace.to_dict()
                wire_bufs = bufs
                if FAULTS.armed:
                    # Same drill as v2, now per stripe: corrupt the first
                    # block's k-bytes of whichever chunk this stripe carries.
                    if FAULTS.fire("kv.chunk.send") == "corrupt" and wire_bufs:
                        wire_bufs = [corrupt_bytes(bytes(wire_bufs[0])), *wire_bufs[1:]]
                wire_clock.enter()
                try:
                    await st.send(msg, blobs=wire_bufs)
                    resp = await st.recv()
                finally:
                    wire_clock.exit()
                if resp is None:
                    raise RuntimeError(f"kv stripe {s} closed mid-stream")
                if resp.get("crc_error"):
                    logger.warning(
                        "kv chunk %d of %s failed crc at receiver; retrying once",
                        i, request_id,
                    )
                    crc_retries += 1
                    wire_clock.enter()
                    try:
                        await st.send(msg, blobs=bufs)  # clean copies
                        resp = await st.recv()
                    finally:
                        wire_clock.exit()
                    if resp is None:
                        raise RuntimeError(f"kv stripe {s} closed mid-stream")
                    if resp.get("crc_error"):
                        raise RuntimeError(f"kv chunk {i} failed crc after retry")
                if resp.get("stream_error"):
                    raise RuntimeError(f"kv chunk stream rejected: {resp['stream_error']}")
                if resp.get("last"):
                    summary = resp

        tasks = [asyncio.create_task(producer(), name=f"kv-stripe-producer-{request_id}")]
        tasks += [
            asyncio.create_task(stripe(s), name=f"kv-stripe-{s}-{request_id}")
            for s in range(n_stripes)
        ]
        try:
            await asyncio.gather(*tasks)
        except BaseException:
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            raise
        streaming = False
        phases = {"gather_s": gather_s, "pack_s": pack_clock.total, "wire_s": wire_clock.total}
        result = dict(summary) if summary else {"request_id": request_id, "injected": 0}
        result["phases"] = {k: round(v, 6) for k, v in phases.items()}
        result["bytes"] = total_bytes
        result["crc_retries"] = crc_retries
        result["protocol"] = "v3"
        result["streams"] = n_stripes
        for phase, secs in (("gather", phases["gather_s"]), ("pack", phases["pack_s"]),
                            ("wire", phases["wire_s"])):
            observe_kv_phase(phase, secs, core=core)
            record_span(
                f"kv_{phase}", secs * 1e3, trace=trace,
                request_id=request_id, chunks=n, bytes=total_bytes, streams=n_stripes,
            )
        return result
    finally:
        if streaming:
            # Mid-stream failure: best-effort tell the receiver to roll back
            # (its all-stripes-closed detector is the backstop).
            try:
                await _round_trip(transport, address, {"request_id": request_id, "stream_abort": True})
            except Exception:
                logger.warning("stream abort for %s not delivered", request_id)
        for st in opened:
            try:
                await st.close()
            except Exception:
                pass
        await loop.run_in_executor(None, allocator.release, pages)


def _gather_page_stack(core: EngineCore, page_ids: list[int]):
    """Gather specific cache pages into stacked DEVICE arrays (never
    host-materialized). Page count is padded to a power of two (null page 0)
    so the gather reuses the runner's compiled shapes."""
    import jax.numpy as jnp

    from dynamo_tpu.engine.runner import next_pow2

    runner = core.runner
    n = len(page_ids)
    padded = np.zeros(next_pow2(n), np.int32)
    padded[:n] = page_ids
    with runner.io_lock:
        return runner._gather_pages_fn(runner.k_cache, runner.v_cache, jnp.asarray(padded))


async def _round_trip(transport: Transport, address: str, request: dict) -> dict:
    result: dict = {}
    async for item in transport.generate(address, request, Context()):
        result = item
    return result


async def send_pull_offer(
    transport: Transport,
    address: str,
    request_id: str,
    core: EngineCore,
    block_hashes: list[int],
) -> dict | None:
    """Two-phase device-path pull. Returns the receiver's summary, or None
    when the pull path didn't complete (caller falls back to packed bytes).

    Phase 1 (``pull_query``) asks the receiver which chain blocks it is
    missing; phase 2 gathers and offers ONLY those pages for a
    transfer-engine pull. A fully-cached chain therefore costs one control
    message — no gather, no transfer-server staging — and an offer that the
    receiver never consumed is drained (``finish_offer(consumed=False)``)
    instead of pinning device buffers on the TransferServer forever
    (ADVICE r3)."""
    from dynamo_tpu.disagg.pull_transport import device_pull_supported, get_transport

    if not device_pull_supported():
        return None
    loop = asyncio.get_running_loop()
    allocator = core.allocator
    # Hold the chain's refcounts across both phases so eviction can't reuse
    # the source pages between the query and the gather.
    pages = await loop.run_in_executor(None, allocator.match_prefix, block_hashes)
    staged_on_receiver = False
    try:
        if not pages:
            return None
        hashes = list(block_hashes[: len(pages)])
        parents = [allocator.page_parent_hash(pid) for pid in pages]
        resp = await _round_trip(
            transport, address,
            {"request_id": request_id, "pull_query": {"hashes": hashes, "parents": parents}},
        )
        if resp.get("pull_unsupported") or not resp.get("pull"):
            return None
        miss = resp.get("miss")
        if not miss:
            # Warm cache: the receiver already has the whole chain.
            return resp if "injected" in resp else None
        staged_on_receiver = True
        k, v = await loop.run_in_executor(
            None, _gather_page_stack, core, [pages[i] for i in miss]
        )
        t = get_transport()
        uuid = t.new_uuid()
        t.offer(uuid, [k, v])
        consumed = False
        try:
            resp2 = await _round_trip(
                transport, address,
                {"request_id": request_id, "pull": {
                    "address": t.address(), "uuid": uuid, "total": len(hashes),
                    "k_shape": list(k.shape), "v_shape": list(v.shape),
                    "k_dtype": str(k.dtype), "v_dtype": str(v.dtype),
                }},
            )
            # The receiver popped its staging on any pull response (success
            # or pull_failed); only a transport failure leaves it pending.
            staged_on_receiver = False
            ok = "injected" in resp2 and not resp2.get("pull_failed")
            # Consumed also when the wire pull succeeded but the receiver's
            # scatter failed afterwards — draining a consumed one-shot offer
            # would block.
            consumed = ok or bool(resp2.get("pulled"))
            return resp2 if ok else None
        finally:
            await loop.run_in_executor(None, t.finish_offer, uuid, consumed)
    finally:
        if staged_on_receiver:
            # Best-effort: tell the receiver to roll back its staged pages
            # before we fall back to the packed-bytes path.
            try:
                await _round_trip(transport, address, {"request_id": request_id, "pull_abort": True})
            except Exception:
                logger.warning("pull abort for %s not delivered", request_id)
        await loop.run_in_executor(None, allocator.release, pages)


def collect_prefill_blocks(core: EngineCore, block_hashes: list[int]) -> list[dict]:
    """Read the committed pages for a hash chain out of a (prefill) engine.

    Acquires the pages (refcount) while reading so eviction can't reuse them
    mid-copy, then releases.
    """
    allocator = core.allocator
    pages = allocator.match_prefix(block_hashes)
    try:
        payloads = core.runner.read_pages(pages)  # one gather + one transfer
        return [
            pack_block(block_hashes[i], allocator.page_parent_hash(pid), [], k, v)
            for i, (pid, (k, v)) in enumerate(zip(pages, payloads))
        ]
    finally:
        allocator.release(pages)
