#!/usr/bin/env python3
"""A closed-loop cell at several client counts: one process, one set-up.

    python3 tools/clients_sweep.py --workload <cell> --clients 4,6,8 [--seconds 51]

For each count: tokens per second, the gap between tokens, the rows decoding,
the share of mixed steps, preemptions inside the window, the fewest free pages
and the device's peak memory: what shows which count the pool holds. Uses the
benchmark's own ``run.bring_up`` / ``run.offer`` (as ``benchmark/sweep.py``
does for rates); a count above the cell's warmed rows would compile inside the
window and is refused. Run by hand on the chip.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

import run as bench_run  # noqa: E402


async def amain(args) -> int:
    from benchmark import serving, stats, traffic

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    state = await bench_run.bring_up(args, bench, cell, rehearsal)
    eng = state["conf"]["serve"]["engine"]
    table = []
    try:
        for i, clients in enumerate(int(c) for c in args.clients.split(",")):
            if clients > eng["max_batch_size"]:
                raise SystemExit(f"{clients} clients are beyond the configuration's {eng['max_batch_size']} rows")
            mix = {**state["mix"], "clients": min(clients, 4) if rehearsal else clients}
            plan = traffic.generate(mix, seed=args.seed + i, seconds=args.seconds, vocab=state["conf"]["hf"]["vocab_size"])
            ctx = await bench_run.offer(state, plan, args.seconds, trace=False)
            steps, lat = ctx["window"]["steps"], ctx["latencies"]
            decode = [s for s in steps if s["step_kind"] == "decode"]
            mem = state["devices"][0].memory_stats() or {}
            row = {"clients": clients, "requests": len(ctx["results"]), "failed": lat["failed"],
                   "out_tok_s": stats.tokens_in_window(ctx["all_results"], ctx["seconds"]) / ctx["seconds"],
                   "itl_p50_ms": stats.percentile(lat["gaps_ms"], 50), "itl_p90_ms": stats.percentile(lat["gaps_ms"], 90),
                   "decode_rows_mean": sum(s["decode_rows"] for s in decode) / max(1, len(decode)),
                   "mixed_step_share": sum(1 for s in steps if s["step_kind"] == "mixed") / max(1, len(steps)),
                   "preemptions": steps[-1]["preemptions"] - steps[0]["preemptions"] if steps else None,
                   "waiting_max": max((s["waiting"] for s in steps), default=None),
                   "free_pages_min": min((s["free_pages"] for s in steps), default=None),
                   "pool_pages": eng["pool_tokens"] // eng["page_size"],
                   "compiles": ctx["window"]["backend_compiles"], "memory_peak_bytes": mem.get("peak_bytes_in_use")}
            bench_run.say(clients_sweep=row)
            table.append(row)
    finally:
        await serving.stop(state["handles"])
    print(json.dumps({"clients_sweep": table}))
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--clients", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=2600000101)
    os.environ.setdefault("DYN_FLIGHT_BUFFER", "65536")
    sys.exit(asyncio.run(amain(ap.parse_args())))
