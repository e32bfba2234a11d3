"""Tests for the discovery store: put/get/watch, leases, cascade expiry."""

import asyncio

import pytest

from dynamo_tpu.runtime.discovery import MemoryStore, WatchEventType


async def test_put_get_delete():
    store = MemoryStore()
    await store.put("a/b", b"1")
    assert await store.get("a/b") == b"1"
    await store.put("a/b", b"2")
    assert await store.get("a/b") == b"2"
    assert await store.delete("a/b") is True
    assert await store.delete("a/b") is False
    assert await store.get("a/b") is None


async def test_get_prefix():
    store = MemoryStore()
    await store.put("models/ns/x", b"x")
    await store.put("models/ns/y", b"y")
    await store.put("instances/ns/z", b"z")
    got = await store.get_prefix("models/ns/")
    assert got == {"models/ns/x": b"x", "models/ns/y": b"y"}


async def test_put_if_absent():
    store = MemoryStore()
    assert await store.put_if_absent("k", b"first") is True
    assert await store.put_if_absent("k", b"second") is False
    assert await store.get("k") == b"first"


async def test_watch_snapshot_and_live_events():
    store = MemoryStore()
    await store.put("pre/a", b"1")
    events = []

    async def watcher():
        async for ev in store.watch_prefix("pre/"):
            events.append(ev)
            if len(events) == 3:
                return

    task = asyncio.create_task(watcher())
    await asyncio.sleep(0.05)
    await store.put("pre/b", b"2")
    await store.put("other/c", b"x")  # outside prefix: not delivered
    await store.delete("pre/a")
    await asyncio.wait_for(task, timeout=5)
    assert [(e.type, e.key) for e in events] == [
        (WatchEventType.PUT, "pre/a"),
        (WatchEventType.PUT, "pre/b"),
        (WatchEventType.DELETE, "pre/a"),
    ]


async def test_lease_expiry_cascades_and_notifies():
    store = MemoryStore(reap_interval=0.05)
    lease = await store.create_lease(ttl=0.15)
    await store.put("instances/w1", b"i", lease_id=lease.id)
    await store.put("unleased", b"u")
    deletes = []

    async def watcher():
        async for ev in store.watch_prefix("instances/", initial=False):
            if ev.type is WatchEventType.DELETE:
                deletes.append(ev.key)
                return

    task = asyncio.create_task(watcher())
    await asyncio.sleep(0.4)  # no keep-alive -> lease expires
    await asyncio.wait_for(task, timeout=5)
    assert deletes == ["instances/w1"]
    assert await store.get("instances/w1") is None
    assert await store.get("unleased") == b"u"
    await store.close()


async def test_keep_alive_extends_lease():
    store = MemoryStore(reap_interval=0.05)
    lease = await store.create_lease(ttl=0.2)
    await store.put("k", b"v", lease_id=lease.id)
    for _ in range(5):
        await asyncio.sleep(0.1)
        await lease.keep_alive()
    assert await store.get("k") == b"v"
    await lease.revoke()
    assert await store.get("k") is None
    with pytest.raises(KeyError):
        await store.keep_alive(lease.id)
    await store.close()


@pytest.mark.parametrize("remote", [False, True], ids=["memory-store", "tcp-store"])
async def test_runtime_reregisters_after_lease_loss(remote):
    """A holder that could not renew for a whole TTL (seen on the chip's
    shared host: the process did not run for 8-10 s) is not dead. Its
    keep-alive loop takes the lease back under the same id and restores its
    records, so watchers see the instance leave and return."""
    import time

    from dynamo_tpu.runtime.component import DistributedRuntime
    from dynamo_tpu.runtime.store_server import StoreClient, StoreServer

    server = None
    if remote:
        server = await StoreServer(MemoryStore(reap_interval=0.05), host="127.0.0.1", port=0).start()
        store = StoreClient("127.0.0.1", server.port)
    else:
        store = MemoryStore(reap_interval=0.05)
    rt = DistributedRuntime(store, lease_ttl=0.3)
    lease = await rt.primary_lease()
    await rt.put_leased("instances/ns/w/gen:1", b"record", lease)
    events = []

    async def watch():
        async for ev in store.watch_prefix("instances/", initial=False):
            events.append((ev.type, ev.key))

    watcher = asyncio.create_task(watch())
    await asyncio.sleep(0.05)
    time.sleep(0.5)  # blocks the loop past the TTL: no keep-alive can run
    await asyncio.sleep(0.4)  # the reaper expires the lease; the keep-alive loop repairs it
    assert await store.get("instances/ns/w/gen:1") == b"record"
    await lease.keep_alive()  # same id, alive again
    assert events == [
        (WatchEventType.DELETE, "instances/ns/w/gen:1"),
        (WatchEventType.PUT, "instances/ns/w/gen:1"),
    ]
    watcher.cancel()
    await rt.close()
    if server is not None:
        await server.close()


async def test_put_with_unknown_lease_rejected():
    store = MemoryStore()
    with pytest.raises(KeyError):
        await store.put("k", b"v", lease_id=999)
