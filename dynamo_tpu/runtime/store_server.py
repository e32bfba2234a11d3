"""The discovery store served over TCP — this deployment's etcd.

One process runs ``StoreServer`` around a MemoryStore; every other process
connects with ``StoreClient``, which implements the same ``KeyValueStore``
interface — nothing above the store can tell local from remote. Leases live
server-side, so a client process dying (keep-alives stop) expires its keys
exactly like etcd.

Protocol: length-prefixed msgpack frames (runtime.codec). RPCs are
request/response on a single multiplexed connection (correlation ids);
watches each hold a dedicated streaming connection, as does a follower
replica's ``op="replicate"`` log subscription (``runtime/replication.py``).

High availability: with ``--store tcp://a,tcp://b,...`` the client holds the
full replica list. All mutations go to the leader; followers answer
``who_leads`` with a redirect, and on ``ConnectionError`` the client walks
the list, discovers the new leader, transparently retries idempotent
in-flight ops exactly once, and re-arms watches with a resync. A
single-endpoint client takes exactly the pre-HA code paths.

Parity: reference `transports/etcd.rs` (we speak to our own server instead
of etcd; an etcd-backed KeyValueStore can be slotted in unchanged when
available).
"""

from __future__ import annotations

import asyncio
import itertools
import logging
from typing import Any, AsyncIterator

from dynamo_tpu.runtime.codec import Frame, FrameType, read_frame, write_frame
from dynamo_tpu.runtime.discovery import (
    DEFAULT_LEASE_TTL,
    KeyValueStore,
    Lease,
    MemoryStore,
    WatchEvent,
    WatchEventType,
)
from dynamo_tpu.runtime.faults import FAULTS

logger = logging.getLogger(__name__)

#: Ops that mutate store state — leader-only under replication.
MUTATING_OPS = frozenset(
    {"put", "put_if_absent", "delete", "create_lease", "adopt_lease", "keep_alive", "revoke_lease"}
)

#: Ops the client may transparently retry once after a reconnect: replaying
#: them cannot change the outcome (``put`` re-sends the same payload;
#: ``create_lease``/``put_if_absent``/``revoke_lease`` could double-apply).
IDEMPOTENT_OPS = frozenset(
    {"get", "get_prefix", "keep_alive", "adopt_lease", "delete", "put", "who_leads"}
)


class NotLeaderError(RuntimeError):
    """Mutation sent to a follower replica; carries the leader's url hint."""

    def __init__(self, leader: str | None) -> None:
        super().__init__(f"not the store leader (leader: {leader or 'unknown'})")
        self.leader = leader


#: Client-side HA counters, surfaced by ``frontend/metrics.py`` as
#: dynamo_store_client_op_retries_total / dynamo_store_failovers_total (and
#: the role/epoch gauges when no in-process replica exists).
_CLIENT_STATS = {"retries": 0, "failovers": 0, "epoch": 0, "role": "unknown", "leader": None}


def store_client_snapshot() -> dict:
    """Process-wide StoreClient HA view (metrics sync-on-render source)."""
    return dict(_CLIENT_STATS)


class StoreServer:
    def __init__(self, store: KeyValueStore | None = None, *, host: str = "0.0.0.0", port: int = 0) -> None:
        self.store = store if store is not None else MemoryStore()
        self._host = host
        self._port = port
        self._server: asyncio.Server | None = None
        self._conn_tasks: set[asyncio.Task] = set()
        # Replication coordinator (runtime/replication.py); None = the
        # single-replica deployment, where every HA check below short-circuits
        # on one attribute load and behavior is identical to pre-HA.
        self.repl = None

    @property
    def port(self) -> int:
        return self._port

    async def start(self) -> "StoreServer":
        if self._server is None:
            self._server = await asyncio.start_server(self._handle, self._host, self._port)
            self._port = self._server.sockets[0].getsockname()[1]
            logger.info("store server on %s:%d", self._host, self._port)
        return self

    async def _handle(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter) -> None:
        task = asyncio.current_task()
        if task:
            self._conn_tasks.add(task)
        stream_task: asyncio.Task | None = None
        try:
            while True:
                frame = await read_frame(reader)
                if frame is None:
                    return
                op = frame.fields.get("op")
                rid = frame.fields.get("rid")
                if op == "watch":
                    # Connection becomes a one-way event stream.
                    stream_task = asyncio.create_task(
                        self._stream_watch(writer, frame.fields["prefix"], frame.fields.get("initial", True))
                    )
                    continue
                if op == "replicate":
                    # Connection becomes a one-way replication-log stream.
                    stream_task = asyncio.create_task(self._stream_replicate(writer, frame.fields))
                    continue
                try:
                    result = await self._execute(op, frame.fields)
                    write_frame(writer, FrameType.DATA, rid=rid, p=result)
                except NotLeaderError as exc:
                    write_frame(
                        writer, FrameType.ERROR, rid=rid, error=str(exc),
                        kind="not_leader", leader=exc.leader,
                    )
                except KeyError as exc:
                    write_frame(writer, FrameType.ERROR, rid=rid, error=str(exc), kind="key")
                except Exception as exc:
                    logger.exception("store op %s failed", op)
                    write_frame(writer, FrameType.ERROR, rid=rid, error=str(exc), kind="internal")
                await writer.drain()
        except (ConnectionResetError, asyncio.IncompleteReadError):
            pass
        finally:
            if stream_task is not None:
                stream_task.cancel()
            writer.close()
            if task:
                self._conn_tasks.discard(task)

    async def _stream_watch(self, writer: asyncio.StreamWriter, prefix: str, initial: bool) -> None:
        try:
            async for event in self.store.watch_prefix(prefix, initial=initial):
                write_frame(
                    writer, FrameType.DATA,
                    p={"type": event.type.value, "key": event.key, "value": event.value},
                )
                await writer.drain()
        except (ConnectionResetError, asyncio.CancelledError):
            pass
        except Exception:
            logger.exception("watch stream failed for %s", prefix)

    async def _stream_replicate(self, writer: asyncio.StreamWriter, fields: dict[str, Any]) -> None:
        """Serve one follower's log subscription: snapshot first, then every
        mutation record. The handshake is also an epoch fence in both
        directions — a follower that has seen a higher epoch proves this
        leader stale (it demotes), and a non-leader refuses outright."""
        repl = self.repl
        try:
            if repl is None:
                write_frame(writer, FrameType.ERROR, error="replication not enabled", kind="internal")
                await writer.drain()
                return
            follower_epoch = int(fields.get("epoch", 0) or 0)
            if follower_epoch > repl.epoch:
                write_frame(
                    writer, FrameType.ERROR, kind="stale_epoch", epoch=repl.epoch,
                    error=f"fenced: follower at epoch {follower_epoch} > leader {repl.epoch}",
                )
                await writer.drain()
                repl.note_stale(follower_epoch)
                return
            if repl.role != "leader":
                write_frame(
                    writer, FrameType.ERROR, kind="not_leader",
                    leader=repl.leader_url, error="not the store leader",
                )
                await writer.drain()
                return
            # Subscribe BEFORE snapshotting: a mutation landing in between
            # appears in both, and replay is idempotent; the follower skips
            # queued records with seq <= the snapshot's.
            queue = repl.subscribe()
            try:
                snapshot = await repl.export_snapshot()
                write_frame(
                    writer, FrameType.DATA,
                    p={"snapshot": snapshot, "e": repl.epoch, "s": repl.seq},
                )
                await writer.drain()
                logger.info("replica %s subscribed at (epoch %d, seq %d)",
                            fields.get("url", "?"), repl.epoch, repl.seq)
                while True:
                    rec = await queue.get()
                    if rec is None:  # coordinator demoted/closed: drop the stream
                        return
                    write_frame(writer, FrameType.DATA, p=rec)
                    await writer.drain()
            finally:
                repl.unsubscribe(queue)
        except (ConnectionResetError, asyncio.CancelledError):
            pass
        except Exception:
            logger.exception("replicate stream failed")

    async def _execute(self, op: str, f: dict[str, Any]) -> Any:
        s = self.store
        repl = self.repl
        if op == "who_leads":
            if repl is None:
                return {"role": "single", "leader": None, "epoch": 0, "seq": 0}
            return repl.status()
        if repl is not None and repl.role != "leader" and op in MUTATING_OPS:
            raise NotLeaderError(repl.leader_url)
        if op == "put":
            await s.put(f["key"], f["value"], lease_id=f.get("lease_id"))
            if repl is not None:
                repl.record("put", key=f["key"], value=f["value"], lease_id=f.get("lease_id"))
            return True
        if op == "put_if_absent":
            created = await s.put_if_absent(f["key"], f["value"], lease_id=f.get("lease_id"))
            if created and repl is not None:
                repl.record("put", key=f["key"], value=f["value"], lease_id=f.get("lease_id"))
            return created
        if op == "get":
            return await s.get(f["key"])
        if op == "get_prefix":
            return await s.get_prefix(f["prefix"])
        if op == "delete":
            existed = await s.delete(f["key"])
            if existed and repl is not None:
                repl.record("delete", key=f["key"])
            return existed
        if op == "create_lease":
            lease = await s.create_lease(f.get("ttl", DEFAULT_LEASE_TTL))
            if repl is not None:
                repl.record("lease", lease_id=lease.id, ttl=lease.ttl)
            return {"id": lease.id, "ttl": lease.ttl}
        if op == "adopt_lease":
            await s.adopt_lease(f["lease_id"], f["ttl"])
            if repl is not None:
                repl.record("lease", lease_id=f["lease_id"], ttl=f["ttl"])
            return True
        if op == "keep_alive":
            await s.keep_alive(f["lease_id"])
            if repl is not None:
                ttl = getattr(s, "_lease_ttl", {}).get(f["lease_id"], DEFAULT_LEASE_TTL)
                repl.record("keepalive", lease_id=f["lease_id"], ttl=ttl)
            return True
        if op == "revoke_lease":
            await s.revoke_lease(f["lease_id"])
            if repl is not None:
                repl.record("revoke", lease_id=f["lease_id"])
            return True
        raise ValueError(f"unknown op {op!r}")

    async def close(self) -> None:
        if self.repl is not None:
            await self.repl.close()
        if self._server is not None:
            self._server.close()
        # Connections first: since Python 3.12.1 ``wait_closed`` waits for
        # every open connection, and watch / replication streams never end
        # on their own.
        tasks = list(self._conn_tasks)
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        if self._server is not None:
            await self._server.wait_closed()
            self._server = None
        # The served store may hold resources (e.g. a persistence WAL).
        await self.store.close()


class StoreClient(KeyValueStore):
    """KeyValueStore speaking the wire protocol. One shared RPC connection
    (correlated by request id), one dedicated connection per watch.

    With multiple endpoints the client is HA-aware: it discovers the leader
    via ``who_leads``, follows ``not_leader`` redirects, retries idempotent
    in-flight ops exactly once after a reconnect, and re-arms dropped watches
    against whichever replica is reachable (synthesizing DELETE events for
    keys that vanished during the outage)."""

    def __init__(
        self,
        host: str,
        port: int,
        *,
        endpoints: list[tuple[str, int]] | None = None,
        failover_timeout_s: float = 5.0,
    ) -> None:
        self._endpoints = [(h, int(p)) for h, p in (endpoints or [(host, port)])]
        self._endpoint_idx = 0
        self._host, self._port = self._endpoints[0]
        self._failover_timeout_s = failover_timeout_s
        self._reader: asyncio.StreamReader | None = None
        self._writer: asyncio.StreamWriter | None = None
        self._pending: dict[int, asyncio.Future] = {}
        self._rid = itertools.count(1)
        self._reader_task: asyncio.Task | None = None
        self._lock = asyncio.Lock()
        self._watch_writers: list[asyncio.StreamWriter] = []

    @classmethod
    def from_url(cls, url: str) -> "StoreClient":
        """``tcp://host:port`` or ``tcp://a:p1,tcp://b:p2,...`` (replica list)."""
        endpoints: list[tuple[str, int]] = []
        for part in url.split(","):
            part = part.strip()
            if not part:
                continue
            rest = part.split("://", 1)[-1]
            host, port = rest.rsplit(":", 1)
            endpoints.append((host, int(port)))
        if not endpoints:
            raise ValueError(f"no store endpoints in {url!r}")
        if len(endpoints) > 1:
            from dynamo_tpu.config import load_store_settings

            return cls(
                endpoints[0][0], endpoints[0][1], endpoints=endpoints,
                failover_timeout_s=load_store_settings().client_failover_s,
            )
        return cls(endpoints[0][0], endpoints[0][1])

    @property
    def _multi(self) -> bool:
        return len(self._endpoints) > 1

    async def _ensure(self) -> None:
        if self._writer is not None and not self._writer.is_closing():
            return
        if not self._multi:
            self._reader, self._writer = await asyncio.open_connection(self._host, self._port)
            self._reader_task = asyncio.create_task(self._read_loop(self._reader, self._writer))
            return
        await self._connect_leader()

    async def _probe(self, host: str, port: int):
        """Open a connection and ask ``who_leads``; (reader, writer, info) on
        success, raising on any failure (caller walks the replica list)."""
        reader, writer = await asyncio.open_connection(host, port)
        try:
            write_frame(writer, FrameType.REQUEST, op="who_leads", rid=0)
            await writer.drain()
            frame = await asyncio.wait_for(read_frame(reader), 1.0)
            if frame is None or frame.type is not FrameType.DATA:
                raise ConnectionError("who_leads probe failed")
            return reader, writer, frame.payload
        except BaseException:
            writer.close()
            raise

    def _note_leader(self, info: dict, url: str) -> None:
        prev = _CLIENT_STATS["leader"]
        if prev is not None and prev != url:
            _CLIENT_STATS["failovers"] += 1
        _CLIENT_STATS["leader"] = url
        _CLIENT_STATS["role"] = info.get("role", "unknown")
        _CLIENT_STATS["epoch"] = max(_CLIENT_STATS["epoch"], int(info.get("epoch", 0) or 0))

    async def _connect_leader(self) -> None:
        """Walk the replica list until the leader answers; honors follower
        redirects and keeps trying (with backoff) until the failover window
        closes — promotion takes a beat after a leader SIGKILL."""
        loop = asyncio.get_running_loop()
        deadline = loop.time() + self._failover_timeout_s
        delay = 0.05
        while True:
            hint: str | None = None
            for i in range(len(self._endpoints)):
                idx = (self._endpoint_idx + i) % len(self._endpoints)
                host, port = self._endpoints[idx]
                try:
                    reader, writer, info = await self._probe(host, port)
                except (OSError, asyncio.TimeoutError, ConnectionError):
                    continue
                if info.get("role") in ("leader", "single"):
                    self._endpoint_idx = idx
                    self._host, self._port = host, port
                    self._reader, self._writer = reader, writer
                    self._reader_task = asyncio.create_task(self._read_loop(reader, writer))
                    self._note_leader(info, f"tcp://{host}:{port}")
                    return
                writer.close()
                hint = hint or info.get("leader")
            if hint:
                for j, (h, p) in enumerate(self._endpoints):
                    if hint.endswith(f"{h}:{p}"):
                        self._endpoint_idx = j
                        break
            if loop.time() >= deadline:
                eps = ",".join(f"{h}:{p}" for h, p in self._endpoints)
                raise ConnectionError(f"no store leader reachable among {eps}")
            await asyncio.sleep(delay)
            delay = min(delay * 2, 0.5)

    async def _read_loop(self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter) -> None:
        try:
            while True:
                frame = await read_frame(reader)
                if frame is None:
                    break
                fut = self._pending.pop(frame.fields.get("rid"), None)
                if fut is None or fut.done():
                    continue
                if frame.type is FrameType.ERROR:
                    kind = frame.fields.get("kind")
                    exc: Exception
                    if kind == "key":
                        exc = KeyError(frame.fields.get("error"))
                    elif kind == "not_leader":
                        exc = NotLeaderError(frame.fields.get("leader"))
                    else:
                        exc = RuntimeError(frame.fields.get("error"))
                    fut.set_exception(exc)
                else:
                    fut.set_result(frame.payload)
        finally:
            # Tear down this loop's connection so the next op reconnects
            # instead of writing into a dead socket and pending forever.
            writer.close()
            if self._writer is writer:
                self._writer = None
            for fut in self._pending.values():
                if not fut.done():
                    fut.set_exception(ConnectionError("store connection lost"))
            self._pending.clear()

    async def _reset(self) -> None:
        async with self._lock:
            if self._reader_task is not None:
                self._reader_task.cancel()
                self._reader_task = None
            if self._writer is not None:
                self._writer.close()
                self._writer = None

    async def _call_once(self, op: str, fields: dict[str, Any]) -> Any:
        async with self._lock:
            await self._ensure()
            rid = next(self._rid)
            fut: asyncio.Future = asyncio.get_running_loop().create_future()
            self._pending[rid] = fut
            write_frame(self._writer, FrameType.REQUEST, op=op, rid=rid, **fields)
            await self._writer.drain()
        return await fut

    async def _call(self, op: str, **fields: Any) -> Any:
        if FAULTS.armed:
            FAULTS.fire("store.op")
        retried = False
        redirects = 0
        while True:
            try:
                return await self._call_once(op, fields)
            except NotLeaderError:
                # The op never executed server-side — always safe to chase
                # the redirect, bounded so flapping leadership can't loop us.
                redirects += 1
                if redirects > len(self._endpoints) + 1:
                    raise ConnectionError("store leadership unstable; giving up")
                await self._reset()
            except ConnectionError:
                # In-flight op at connection death: outcome unknown. Replay
                # exactly once iff replaying cannot change it (IDEMPOTENT_OPS).
                if retried or op not in IDEMPOTENT_OPS:
                    raise
                retried = True
                _CLIENT_STATS["retries"] += 1
                await self._reset()

    # -- KeyValueStore API -------------------------------------------------

    async def put(self, key: str, value: bytes, lease_id: int | None = None) -> None:
        await self._call("put", key=key, value=value, lease_id=lease_id)

    async def put_if_absent(self, key: str, value: bytes, lease_id: int | None = None) -> bool:
        return await self._call("put_if_absent", key=key, value=value, lease_id=lease_id)

    async def get(self, key: str) -> bytes | None:
        return await self._call("get", key=key)

    async def get_prefix(self, prefix: str) -> dict[str, bytes]:
        return await self._call("get_prefix", prefix=prefix)

    async def delete(self, key: str) -> bool:
        return await self._call("delete", key=key)

    async def create_lease(self, ttl: float = DEFAULT_LEASE_TTL) -> Lease:
        d = await self._call("create_lease", ttl=ttl)
        return Lease(id=d["id"], ttl=d["ttl"], store=self)

    async def adopt_lease(self, lease_id: int, ttl: float) -> None:
        await self._call("adopt_lease", lease_id=lease_id, ttl=ttl)

    async def keep_alive(self, lease_id: int) -> None:
        await self._call("keep_alive", lease_id=lease_id)

    async def revoke_lease(self, lease_id: int) -> None:
        await self._call("revoke_lease", lease_id=lease_id)

    async def who_leads(self) -> dict:
        """Leadership view of whichever replica the RPC channel reaches."""
        return await self._call("who_leads")

    async def watch_prefix(self, prefix: str, initial: bool = True) -> AsyncIterator[WatchEvent]:
        if not self._multi:
            async for event in self._watch_single(prefix, initial):
                yield event
            return
        # HA watch: survive a replica death by re-arming against the next
        # reachable replica. Watches are served by followers too (they apply
        # the replicated log into their own store), so any live replica will
        # do. The server-side snapshot-on-subscribe replays PUTs; deletions
        # that happened during the outage are synthesized from the key set
        # this watch has already reported.
        known: set[str] = set()
        first = True
        down_since: float | None = None
        while True:
            conn = None
            for i in range(len(self._endpoints)):
                idx = (self._endpoint_idx + i) % len(self._endpoints)
                host, port = self._endpoints[idx]
                try:
                    conn = await asyncio.open_connection(host, port)
                    break
                except OSError:
                    continue
            if conn is None:
                now = asyncio.get_running_loop().time()
                down_since = down_since or now
                if now - down_since >= self._failover_timeout_s:
                    raise ConnectionError("watch stream closed")
                await asyncio.sleep(0.2)
                continue
            down_since = None
            reader, writer = conn
            self._watch_writers.append(writer)
            try:
                write_frame(
                    writer, FrameType.REQUEST, op="watch", prefix=prefix,
                    initial=True if not first else initial,
                )
                await writer.drain()
                if not first:
                    # Resync: anything we reported that no longer exists was
                    # deleted while we were dark. Diffed AFTER the subscribe
                    # frame so a concurrent delete lands in the diff or on the
                    # live stream — a duplicate DELETE is harmless, a missed
                    # one is not.
                    current = await self.get_prefix(prefix)
                    for key in sorted(known - set(current)):
                        known.discard(key)
                        yield WatchEvent(WatchEventType.DELETE, key, None)
                while True:
                    frame = await read_frame(reader)
                    if frame is None:
                        break  # replica died: re-arm on the next one
                    if FAULTS.armed:
                        FAULTS.fire("store.watch")
                    p = frame.payload
                    event = WatchEvent(WatchEventType(p["type"]), p["key"], p.get("value"))
                    if event.type is WatchEventType.PUT:
                        known.add(event.key)
                    else:
                        known.discard(event.key)
                    yield event
            finally:
                self._watch_writers.remove(writer)
                writer.close()
            first = False
            await asyncio.sleep(0.1)

    async def _watch_single(self, prefix: str, initial: bool) -> AsyncIterator[WatchEvent]:
        reader, writer = await asyncio.open_connection(self._host, self._port)
        self._watch_writers.append(writer)
        try:
            write_frame(writer, FrameType.REQUEST, op="watch", prefix=prefix, initial=initial)
            await writer.drain()
            while True:
                frame = await read_frame(reader)
                if frame is None:
                    raise ConnectionError("watch stream closed")
                if FAULTS.armed:
                    FAULTS.fire("store.watch")
                p = frame.payload
                yield WatchEvent(WatchEventType(p["type"]), p["key"], p.get("value"))
        finally:
            self._watch_writers.remove(writer)
            writer.close()

    async def close(self) -> None:
        if self._reader_task is not None:
            self._reader_task.cancel()
        if self._writer is not None:
            self._writer.close()
            self._writer = None
        for w in list(self._watch_writers):
            w.close()
