"""Cross-process device-path KV pull transport.

The reference's NIXL writes KV blocks straight into a remote worker's GPU
memory (`lib/llm/src/block_manager/block/transfer/nixl.rs:86`). The TPU
equivalent is JAX's cross-slice transfer engine
(``jax.experimental.transfer``): the source stages device arrays under a
uuid on its ``TransferServer``; the destination connects to the source's
transfer address and *pulls* them — bytes move device-to-device over
ICI/DCN through the PJRT transfer engine, never through Python or the
host heap.

Protocol shape (sender-initiated, receiver-pulled):

1. The prefill worker gathers the chain's pages into stacked device arrays
   and ``offer()``s them under a fresh uuid.
2. It sends a *descriptor* (address, uuid, shapes, dtypes, hash chain) to
   the decode worker's ``kv_transfer`` endpoint — a tiny control message on
   the ordinary transport.
3. The decode worker allocates destination pages, ``pull()``s the arrays
   with its own cache sharding (the transfer engine delivers each shard to
   the device that owns it), scatters them into the paged cache, commits.
4. The response releases the sender's staged arrays.

Not every PJRT plugin implements the transfer-engine API (the CPU backend
does not): :func:`device_pull_supported` probes once,
and senders fall back to the packed-bytes TCP path (``disagg/transfer.py``)
when either end lacks support — same fallback the reference takes when
NIXL is unavailable.
"""

from __future__ import annotations

import itertools
import logging
import threading
from typing import Any, Sequence

logger = logging.getLogger(__name__)

_uuid_counter = itertools.count(1)
_lock = threading.Lock()


class JaxPullTransport:
    """``jax.experimental.transfer`` wrapper: one server + cached peer
    connections per process."""

    def __init__(self) -> None:
        self._server = None
        self._connections: dict[str, Any] = {}
        # Offered arrays are kept alive until acknowledged: the transfer
        # engine holds device buffers, but the Python references pin them
        # against donation/GC races on our side.
        self._offers: dict[int, Any] = {}

    def _ensure_server(self):
        if self._server is None:
            import jax
            from jax.experimental import transfer

            self._server = transfer.start_transfer_server(
                jax.local_devices()[0].client
            )
        return self._server

    def address(self) -> str:
        """This process's transfer address (host-reachable form)."""
        import socket

        addr = self._ensure_server().address()
        if addr.startswith("[::]"):
            addr = socket.gethostbyname(socket.gethostname()) + addr[4:]
        return addr

    def new_uuid(self) -> int:
        return next(_uuid_counter)

    def offer(self, uuid: int, arrays: Sequence[Any]) -> None:
        """Source side: stage device arrays for a remote pull."""
        server = self._ensure_server()
        with _lock:
            self._offers[uuid] = list(arrays)
        server.await_pull(uuid, list(arrays))

    #: How long a loopback drain may run before we stop waiting for it.
    DRAIN_TIMEOUT = 10.0

    def finish_offer(self, uuid: int, consumed: bool = True) -> None:
        """Release an offer. ``consumed=False`` means the receiver never
        pulled it — TransferServer has no cancel/deregister API (jax 0.9),
        and an un-pulled offer pins the staged device buffers forever, so we
        drain it ourselves with a loopback self-pull (the same mechanism the
        capability probe uses) to make the server release them.

        ``consumed`` is inferred from the receiver's phase-2 reply, which can
        be lost *after* a successful pull — in that case the drain would
        re-pull a consumed one-shot offer and block forever. The drain
        therefore runs on a daemon thread bounded by :attr:`DRAIN_TIMEOUT`:
        on timeout we give up and log the (possible) buffer leak instead of
        hanging the caller's executor thread (ADVICE r4)."""
        with _lock:
            arrays = self._offers.pop(uuid, None)
        if consumed or arrays is None:
            return

        def _drain() -> None:
            try:
                import jax

                specs = [
                    jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
                    for a in arrays
                ]
                for drained in self.pull(self.address(), uuid, specs):
                    drained.block_until_ready()
            except Exception as e:
                logger.warning("draining un-pulled offer %d failed: %s", uuid, e)

        t = threading.Thread(target=_drain, name=f"drain-offer-{uuid}", daemon=True)
        t.start()
        t.join(self.DRAIN_TIMEOUT)
        if t.is_alive():
            logger.warning(
                "drain of offer %d still blocked after %.0fs (receiver likely "
                "consumed it and the reply was lost); abandoning the drain — "
                "staged buffers may stay pinned until process exit", uuid,
                self.DRAIN_TIMEOUT,
            )

    def pull(self, address: str, uuid: int, specs: Sequence[Any]) -> list:
        """Destination side: fetch staged arrays device-path (blocking —
        call via run_in_executor). ``specs``: ShapeDtypeStructs carrying the
        *destination* sharding."""
        server = self._ensure_server()
        with _lock:
            conn = self._connections.get(address)
        if conn is None:
            conn = server.connect(address)
            with _lock:
                self._connections[address] = conn
        return conn.pull(uuid, list(specs))


_supported: bool | None = None
_transport: JaxPullTransport | None = None


def device_pull_supported() -> bool:
    """Whether this process's PJRT backend implements the transfer engine
    (probed once with a loopback self-pull of a tiny array)."""
    global _supported
    if _supported is None:
        try:
            import jax
            import jax.numpy as jnp

            t = get_transport()
            probe = jnp.zeros((8,), jnp.float32)
            uuid = t.new_uuid()
            t.offer(uuid, [probe])
            sds = jax.ShapeDtypeStruct(
                probe.shape, probe.dtype,
                sharding=jax.sharding.SingleDeviceSharding(jax.local_devices()[0]),
            )
            [back] = t.pull(t.address(), uuid, [sds])
            back.block_until_ready()
            t.finish_offer(uuid)
            _supported = True
        except Exception as e:  # UNIMPLEMENTED where the runtime lacks it
            logger.info("device pull transport unavailable (%s); TCP fallback", e)
            _supported = False
    return _supported


def get_transport() -> JaxPullTransport:
    """Process-wide transport (tests may substitute a stub via
    ``set_transport``)."""
    global _transport
    if _transport is None:
        _transport = JaxPullTransport()
    return _transport


def set_transport(transport, supported: bool | None = None) -> None:
    """Test seam: install a stub transport and force the capability probe."""
    global _transport, _supported
    _transport = transport
    _supported = supported
