"""Cross-process KV-wire bandwidth probe: the DCN-path number on hardware.

Measures the packed-bytes TCP fallback — the prefill->decode transfer path
that runs anywhere (`disagg/transfer.py`), unlike the PJRT transfer engine
(`disagg/pull_transport.py` probes for it) — between the CHIP-holding process
and a second, CPU-mesh receiver process on the same host:

  sender (this process, real TPU): prefill commits page chains ->
  `send_blocks_chunked` (wire v3: chunks striped round-robin over
  DYN_KV_WIRE_STREAMS duplex TCP connections, raw blob frames, deferred
  acks; ``streams=0`` pins the single-stream msgpack v2 baseline) ->
  receiver (child OS process, CPU): per chunk crc-verify -> reassemble in
  seq order -> allocate -> write_pages -> incremental commit -> ack.

``sweep_cross_process`` runs a stream-count x chunk-size grid (one receiver
child per combo) and reports the headline ``kv_wire_gbps`` /
``kv_wire_overlap_frac`` / ``speedup_vs_v2`` keys that bench.py promotes to
the stable top level of the bench document.

Each iteration ships a DISTINCT hash chain (a repeat would dedup against
the receiver's prefix cache and measure nothing). Iteration 0 is reported
as "cold" (includes both sides' jit compiles and connection setup); the
rest average into "amortized" — the two numbers BENCH r4 left unreconciled
for the in-process probe (VERDICT r4 weak #5 / item 3a).

The transferred KV uses a wide-cache geometry (`wire_config`) so a few
thousand prefill tokens move hundreds of MB: the point is to saturate the
WIRE, not the model.

Parity: the reference measures NIXL RDMA block-descriptor transfers
(`lib/llm/src/block_manager/block/transfer/nixl.rs:86`); this is the
TCP/DCN-class equivalent, reported by bench.py under
``detail.kv_wire_cross_process`` (the in-process gather stays in
``detail.kv_pull``).

Child entrypoint: ``python -m dynamo_tpu.bench.kv_wire`` (CPU platform,
prints ``ADDR <kv_transfer addr>`` once serving, exits on stdin EOF).
"""

from __future__ import annotations

import dataclasses
import time

from dynamo_tpu.models.config import ModelConfig

PAGE_SIZE = 128


def wire_config(num_layers: int = 4, num_kv_heads: int = 32, head_dim: int = 128) -> ModelConfig:
    """Wide-KV / tiny-weights geometry: 8 MiB per 128-token page at the
    defaults (4L * 2(K,V) * 32kv * 128hd * 2B * 128 tokens), ~50 MB of
    weights — the default 8-page chain moves ~64 MB per iteration."""
    return ModelConfig(
        name="kv-wire-proxy", vocab_size=512, hidden_size=512,
        num_layers=num_layers, num_heads=num_kv_heads, num_kv_heads=num_kv_heads,
        head_dim=head_dim, intermediate_size=1024, rope_theta=10000.0,
        max_position=16384, tie_embeddings=True,
    )


def _build_core(cfg: ModelConfig, num_pages: int, page_size: int, prefill_tokens: int):
    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models import llama

    params = llama.init_params(cfg, 0)
    runner = ModelRunner(
        cfg, params, num_pages=num_pages, page_size=page_size,
        max_batch_size=2, prefill_bucket=max(prefill_tokens, 64),
    )
    return EngineCore(runner, EngineConfig(
        num_pages=num_pages, page_size=page_size, max_batch_size=2,
        max_prefill_tokens=prefill_tokens + page_size,
        max_seq_len=prefill_tokens + page_size,
    ))


def _prefill_chain(core, tokens: list[int], request_id: str) -> list[int]:
    """Run a 1-token generation so the prompt's full pages commit to the
    prefix cache (what a prefill worker does before shipping KV); returns
    the committed chain's hashes."""
    from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.tokens import compute_block_hashes

    core.add_request(PreprocessedRequest(
        token_ids=tokens, sampling=SamplingOptions(temperature=0.0),
        stop=StopConditions(max_tokens=1, ignore_eos=True), request_id=request_id,
    ), Context())
    for _ in range(200):
        if not core.has_work:
            break
        core.step()
    return compute_block_hashes(tokens, core.config.page_size, salt=core.config.salt)


async def measure_cross_process(
    *,
    pages_per_chain: int = 8,
    iters: int = 5,
    cfg: ModelConfig | None = None,
    page_size: int = PAGE_SIZE,
    child_cmd: list[str] | None = None,
    chunk_pages: int | None = None,
    streams: int | None = None,
    _core=None,
    _seed: int = 0,
) -> dict:
    """Parent side. Spawns the CPU receiver child, ships ``iters`` distinct
    chains over the chunked stream (``send_blocks_chunked``: gather, pack
    and wire pipelined; v3 striped over ``streams`` duplex connections,
    ``streams=0`` pins the v2 single-stream baseline), returns the labeled
    measurement dict. Per-iter phase sums exceeding ``total_s`` is the
    direct overlap signal. ``_core``/``_seed`` let sweep_cross_process reuse
    one compiled parent core across combos with distinct chains each."""
    import subprocess
    import sys

    import numpy as np

    from dynamo_tpu.disagg.transfer import send_blocks_chunked
    from dynamo_tpu.runtime.tcp import TcpTransport

    cfg = cfg or wire_config()
    chain_tokens = pages_per_chain * page_size
    cmd = child_cmd or [
        sys.executable, "-m", "dynamo_tpu.bench.kv_wire",
        str(cfg.num_layers), str(cfg.num_kv_heads), str(cfg.head_dim),
        str(page_size), str(pages_per_chain * iters + 4),
        str(chain_tokens),
    ]
    import asyncio

    # The parent holds the chip (one process per chip): the receiver child
    # is pinned to the CPU platform through its environment.
    import os

    proc = subprocess.Popen(
        cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    try:
        def _await_addr() -> str:
            tail: list[str] = []
            for line in proc.stdout:
                if line.startswith("ADDR "):
                    return line.split()[1]
                tail.append(line)
            raise RuntimeError(
                f"kv_wire child exited without ADDR (rc={proc.wait()}): "
                + "".join(tail[-5:])
            )

        # Bounded + off the event loop: a child hung before ADDR (jax
        # import, port bind) must not wedge the bench with no diagnostic.
        kv_addr = await asyncio.wait_for(
            asyncio.get_running_loop().run_in_executor(None, _await_addr),
            timeout=180,
        )
        # Keep draining the merged stdout/stderr afterwards: a chatty child
        # filling the 64 KiB pipe would block mid-write and deadlock the
        # un-timed send_blocks round trips.
        import threading

        threading.Thread(
            target=lambda: [None for _ in proc.stdout], daemon=True,
            name="kv-wire-child-drain",
        ).start()

        core = _core or _build_core(cfg, pages_per_chain * iters + 4, page_size, chain_tokens)
        transport = TcpTransport(host="127.0.0.1")
        # >= 4 chunks per chain by default, so the double buffer has room to
        # overlap (one chunk can't pipeline with itself).
        chunk = chunk_pages or max(1, pages_per_chain // 4)
        try:
            rng = np.random.default_rng(_seed)
            per_iter = []
            protocol = "v2"
            n_streams = 0
            for i in range(iters):
                tokens = rng.integers(1, cfg.vocab_size - 1, size=chain_tokens).tolist()
                hashes = _prefill_chain(core, tokens, f"wire-{_seed}-{i}")
                t0 = time.perf_counter()
                resp = await send_blocks_chunked(
                    transport, kv_addr, f"wire-{_seed}-{i}", core, hashes,
                    chunk_pages=chunk, streams=streams,
                )
                t1 = time.perf_counter()
                protocol = resp.get("protocol", "v2")
                n_streams = resp.get("streams", 0)
                if resp.get("injected") != len(hashes):
                    raise RuntimeError(f"iter {i}: injected {resp.get('injected')} != {len(hashes)}")
                ph = resp["phases"]
                scatter = (resp.get("stats") or {}).get("scatter_s", 0.0)
                per_iter.append({
                    "bytes": resp["bytes"],
                    "gather_s": ph["gather_s"],   # dispatch -> host buffers landed
                    "pack_s": ph["pack_s"],       # msgpack framing (tobytes)
                    "wire_s": ph["wire_s"],       # TCP round trips + receiver ingest
                    "scatter_s_cum": round(scatter, 6),  # receiver-side, cumulative
                    "total_s": round(t1 - t0, 4),
                    "overlap_s": round(ph["gather_s"] + ph["pack_s"] + ph["wire_s"] - (t1 - t0), 4),
                })
            # scatter_s per iter = delta of the receiver's cumulative counter.
            prev = 0.0
            for p in per_iter:
                p["scatter_s"] = round(p.pop("scatter_s_cum") - prev, 6)
                prev += p["scatter_s"]
            amortized = per_iter[1:] or per_iter
            phase_sum = sum(
                p["gather_s"] + p["pack_s"] + p["wire_s"] for p in amortized)
            overlap_s = sum(p["overlap_s"] for p in amortized)
            return {
                "wire": "tcp_cross_process",
                "receiver": "separate OS process, cpu mesh",
                "definition": (
                    "cold = iter 0 (both sides' compiles + connection setup); "
                    f"amortized = mean of the rest. Chunked {protocol} stream "
                    f"({chunk} pages/chunk, {n_streams or 1} stream(s)): "
                    "gather_s = device gather -> host DMA span, pack_s = "
                    "framing (v3: zero-copy blob views; v2: msgpack), wire_s "
                    "= per-stream-attributed TCP + receiver ingest wall time, "
                    "scatter_s = receiver write_pages. Phases overlap, so sum "
                    "of phases > total_s measures the pipeline win directly "
                    "(overlap_s; overlap_frac = overlap_s / sum of phases)"
                ),
                "protocol": protocol,
                "streams": n_streams,
                "chain_mb": round(per_iter[0]["bytes"] / 1e6, 1),
                "iters": iters,
                "chunk_pages": chunk,
                "cold_gbytes_per_sec": round(
                    per_iter[0]["bytes"] / per_iter[0]["total_s"] / 1e9, 6),
                "amortized_gbytes_per_sec": round(
                    sum(p["bytes"] for p in amortized)
                    / max(sum(p["total_s"] for p in amortized), 1e-9) / 1e9, 6),
                "amortized_wire_only_gbytes_per_sec": round(
                    sum(p["bytes"] for p in amortized)
                    / max(sum(p["wire_s"] for p in amortized), 1e-9) / 1e9, 6),
                "amortized_overlap_s": round(overlap_s / max(len(amortized), 1), 4),
                "overlap_frac": round(
                    min(1.0, max(0.0, overlap_s / phase_sum)) if phase_sum > 0 else 0.0,
                    4),
                "per_iter": per_iter,
            }
        finally:
            await transport.close()
    finally:
        try:
            proc.stdin.close()
            proc.wait(timeout=20)
        except Exception:
            proc.kill()


async def sweep_cross_process(
    *,
    pages_per_chain: int = 8,
    iters: int = 5,
    cfg: ModelConfig | None = None,
    page_size: int = PAGE_SIZE,
    child_cmd: list[str] | None = None,
    stream_counts: tuple[int, ...] = (0, 1, 2, 4, 8),
    chunk_pages_list: tuple[int, ...] = (0,),
) -> dict:
    """Stream-count x chunk-size grid over the cross-process wire.

    One receiver child per combo (fresh page pool, no prefix-cache dedup);
    the PARENT core — whose jit compiles dominate probe setup on hardware —
    is built once and reused, with a distinct chain seed per combo.

    ``stream_counts`` entry 0 is the v2 single-stream msgpack baseline; the
    headline ``speedup_vs_v2`` compares the best striped combo against the
    v2 run *at the same chunk size* (the acceptance comparison). Headline
    keys:

    - ``kv_wire_gbps``: best amortized end-to-end GB/s across the grid;
    - ``kv_wire_overlap_frac``: overlap fraction of that best combo
      (sum-of-phases time hidden by pipelining, 0..1);
    - ``speedup_vs_v2``: best-combo GB/s over same-chunk v2 GB/s.
    """
    cfg = cfg or wire_config()
    chunks = tuple(c or max(1, pages_per_chain // 4) for c in chunk_pages_list)
    chain_tokens = pages_per_chain * page_size
    core = _build_core(cfg, pages_per_chain * iters + 4, page_size, chain_tokens)
    combos = []
    seed = 0
    for chunk in chunks:
        for streams in stream_counts:
            seed += 1
            out = await measure_cross_process(
                pages_per_chain=pages_per_chain, iters=iters, cfg=cfg,
                page_size=page_size, child_cmd=child_cmd, chunk_pages=chunk,
                streams=streams, _core=core, _seed=seed,
            )
            combos.append({
                "streams_requested": streams,
                "streams": out["streams"],
                "protocol": out["protocol"],
                "chunk_pages": out["chunk_pages"],
                "chain_mb": out["chain_mb"],
                "amortized_gbytes_per_sec": out["amortized_gbytes_per_sec"],
                "amortized_wire_only_gbytes_per_sec":
                    out["amortized_wire_only_gbytes_per_sec"],
                "cold_gbytes_per_sec": out["cold_gbytes_per_sec"],
                "overlap_frac": out["overlap_frac"],
                "amortized_overlap_s": out["amortized_overlap_s"],
            })
    best = max(combos, key=lambda c: c["amortized_gbytes_per_sec"])
    v2_same_chunk = next(
        (c for c in combos
         if c["protocol"] == "v2" and c["chunk_pages"] == best["chunk_pages"]),
        None,
    )
    speedup = 0.0
    if v2_same_chunk and v2_same_chunk["amortized_gbytes_per_sec"] > 0:
        speedup = round(
            best["amortized_gbytes_per_sec"]
            / v2_same_chunk["amortized_gbytes_per_sec"], 3)
    return {
        "wire": "tcp_cross_process_sweep",
        "grid": {"stream_counts": list(stream_counts), "chunk_pages": list(chunks)},
        "iters": iters,
        "pages_per_chain": pages_per_chain,
        "chain_mb": combos[0]["chain_mb"],
        "kv_wire_gbps": best["amortized_gbytes_per_sec"],
        "kv_wire_overlap_frac": best["overlap_frac"],
        "speedup_vs_v2": speedup,
        "best": best,
        "v2_baseline": v2_same_chunk,
        "sweep": combos,
    }


def child_main(argv: list[str]) -> None:
    """Receiver: CPU platform (JAX_PLATFORMS=cpu from the spawning parent),
    real engine core + KvTransferService on TCP."""
    import asyncio
    import sys

    num_layers, num_kv_heads, head_dim, page_size, num_pages, chain_tokens = (
        int(a) for a in argv
    )
    cfg = wire_config(num_layers, num_kv_heads, head_dim)

    async def main() -> None:
        from dynamo_tpu.disagg.transfer import KV_TRANSFER_ENDPOINT, KvTransferService
        from dynamo_tpu.runtime.tcp import TcpTransport

        core = _build_core(cfg, num_pages, page_size, chain_tokens)
        svc = KvTransferService(core)
        transport = TcpTransport(host="127.0.0.1")
        await transport.register_engine(KV_TRANSFER_ENDPOINT, svc)
        print("ADDR", transport.address_of(KV_TRANSFER_ENDPOINT), flush=True)
        await asyncio.get_running_loop().run_in_executor(None, sys.stdin.read)
        await transport.close()

    asyncio.run(main())


if __name__ == "__main__":
    import sys

    child_main(sys.argv[1:])
