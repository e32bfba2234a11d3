"""Child process for tests/test_multihost.py: one node of a 2-host worker.

Rank 0 serves the discovery store and leads the barrier; rank 1 joins via
StoreClient. After bring-up both ranks hold one global 8-device CPU mesh
(4 virtual devices per process), run the same sharded forward, and compare
against a locally-computed single-device reference.
"""

import os
import sys

RANK = int(sys.argv[1])
STORE_PORT = int(sys.argv[2])
COORD_PORT = int(sys.argv[3])

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax  # noqa: E402

import asyncio  # noqa: E402

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


async def main() -> None:
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS
    from dynamo_tpu.parallel.mesh import MeshPlan, make_mesh
    from dynamo_tpu.parallel.multihost import MultiNodeConfig, bringup
    from dynamo_tpu.parallel.sharding import param_shardings
    from dynamo_tpu.runtime.component import DistributedRuntime
    from dynamo_tpu.runtime.tcp import TcpTransport

    if RANK == 0:
        from dynamo_tpu.runtime.store_server import StoreServer

        server = await StoreServer(host="127.0.0.1", port=STORE_PORT).start()
        store = server.store
    else:
        from dynamo_tpu.runtime.store_server import StoreClient

        # The leader's store may not be listening yet: wait for the port.
        deadline = asyncio.get_event_loop().time() + 60
        while True:
            try:
                _r, _w = await asyncio.open_connection("127.0.0.1", STORE_PORT)
                _w.close()
                break
            except OSError:
                if asyncio.get_event_loop().time() > deadline:
                    raise
                await asyncio.sleep(0.2)
        store = StoreClient.from_url(f"tcp://127.0.0.1:{STORE_PORT}")
    runtime = DistributedRuntime(store, TcpTransport(host="127.0.0.1"))

    cfg = MultiNodeConfig(
        num_nodes=2, node_rank=RANK,
        leader_addr=f"127.0.0.1:{COORD_PORT}" if RANK == 0 else None,
    )
    # Leader pins its coordinator port and publishes it through the barrier;
    # the follower discovers it from the store (leader_addr=None).
    addr = await bringup(cfg, runtime)
    assert addr is not None
    devs = jax.devices()
    assert len(devs) == 8, f"rank {RANK}: expected 8 global devices, got {len(devs)}"

    model = PRESETS["test-tiny"]
    params = llama.init_params(model, 0)
    mesh = make_mesh(MeshPlan(dp=2, tp=2, sp=2), devs)
    placed = jax.tree.map(jax.device_put, params, param_shardings(mesh, params))

    b, t, ps = 2, 8, 4
    tokens = jnp.asarray(np.arange(b * t).reshape(b, t) % model.vocab_size, jnp.int32)
    positions = jnp.tile(jnp.arange(t, dtype=jnp.int32)[None], (b, 1))
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    slots = jnp.take_along_axis(tables, positions // ps, axis=1) * ps + positions % ps
    last = jnp.full((b,), t - 1, jnp.int32)

    def fwd(p):
        kc, vc = llama.init_kv_cache(model, num_pages=8, page_size=ps)
        logits, _, _ = llama.forward(
            p, model, tokens, positions, kc, vc, tables, slots, last,
            attn_impl="reference",
        )
        return logits

    want = np.asarray(fwd(params))  # local single-device reference
    got_fn = jax.jit(fwd, out_shardings=NamedSharding(mesh, P()))
    got = np.asarray(got_fn(placed))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    print(f"MH_OK rank={RANK} devices={len(devs)}", flush=True)
    await runtime.close()


asyncio.run(main())
