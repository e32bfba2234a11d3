#!/usr/bin/env python3
"""Find a rate cell's knee once: one process, one set-up, a few rates of 30 s.

    python3 benchmark/sweep.py --workload <cell> --rates 1.2,1.6,2.0,2.4,2.8 [--seconds 30] [--seed n]

For each rate: the backlog (requests waiting, prefilling or decoding) at the
window's start and end from the engine's flight records, how late the
generator ran, and the client's times. The knee is the highest rate at which
the backlog at the end is no larger than at the start and the generator's
lateness stays under one step. Run by hand on the chip; the rate chosen goes
into the cell's own file (benchmark/cells/) as a number, the table into PERF.md.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

import run as bench_run  # noqa: E402  (same directory)


async def amain(args) -> int:
    from benchmark import serving, stats, traffic

    bench = json.loads((bench_run.ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    state = await bench_run.bring_up(args, bench, cell, rehearsal)
    table = []
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = {**state["mix"], "rate_rps": rate, "lead_in_s": args.lead_in}
            plan = traffic.generate(mix, seed=args.seed + i, seconds=args.seconds, vocab=state["conf"]["hf"]["vocab_size"])
            ctx = await bench_run.offer(state, plan, args.seconds, trace=False)
            steps, lat = ctx["window"]["steps"], ctx["latencies"]
            backlog = [s["waiting"] + s["running"] + s["prefilling"] for s in steps]
            k = max(1, len(backlog) // 20)
            row = {"rate_rps": rate, "requests": len(ctx["results"]), "failed": lat["failed"],
                   "backlog_start": sum(backlog[:k]) / k, "backlog_end": sum(backlog[-k:]) / k,
                   "backlog_max": max(backlog), "waiting_max": max(s["waiting"] for s in steps),
                   "lateness_p95_ms": stats.percentile(lat["lateness_ms"], 95),
                   "ttft_p50_ms": stats.percentile(lat["ttft_ms"], 50), "ttft_p90_ms": stats.percentile(lat["ttft_ms"], 90),
                   "itl_p50_ms": stats.percentile(lat["gaps_ms"], 50), "itl_p95_ms": stats.percentile(lat["gaps_ms"], 95),
                   "decode_rows_mean": sum(s["decode_rows"] for s in steps) / len(steps),
                   "mixed_step_share": sum(1 for s in steps if s["step_kind"] == "mixed") / len(steps),
                   "compiles": ctx["window"]["backend_compiles"]}
            bench_run.say(sweep=row)
            table.append(row)
    finally:
        await serving.stop(state["handles"])
    print(json.dumps({"sweep": table}))
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--lead-in", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=2400000011)
    sys.exit(asyncio.run(amain(ap.parse_args())))
