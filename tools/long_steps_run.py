#!/usr/bin/env python3
"""A cell's windows read for long steps and host pauses: one process, one set-up.

    python3 tools/long_steps_run.py --workload <cell> --seeds 1,2 [--traced-seeds 3] [--seconds 51]

The benchmark reads its per-layer metrics in a ``--trace 1`` run only; this
offers the cell's traffic once per seed from one set-up (``run.bring_up`` /
``run.offer``, as ``tools/clients_sweep.py`` does), untraced windows first, and
after each window prints what ``benchmark/long_steps.py`` finds in the program's
spans: the long steps with phase and cause, the collections by generation, the
profiler's own pauses, the process's collection counters over the window, and
each long step beside the STEP records round it. The first window is the
benchmark's own untraced run; the later ones are the same process, older.

Two watchers tell a pause of this process from one of the whole machine: a
thread of this process and a process of its own each sleep 2 ms at a time and
keep every wake-up that came 20 ms late, on ``perf_counter_ns`` (one clock for
every process of the machine). A long step under which only the thread was
late is this process's (the interpreter lock, or a lock of its address space);
one under which the other process was late too is the machine's (the
scheduler, the CPU quota of the container, the hypervisor). The container's
``cpu.stat`` and the machine's stolen time are printed per window beside them.
Run by hand on the chip.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import pathlib
import subprocess
import sys
import threading
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

import run as bench_run  # noqa: E402


LATE_NS = 20_000_000


def watch(late) -> None:
    """Sleeps 2 ms at a time, for ever, and hands ``late`` every wake-up that came 20 ms late."""
    last = time.perf_counter_ns()
    while True:
        time.sleep(0.002)
        now = time.perf_counter_ns()
        if now - last > LATE_NS:
            late((last, now))
        last = now


def machine_counters() -> dict:
    """The container's CPU quota and how often it was throttled, the stolen
    jiffies of the machine, the CPU pressure: whatever of them this kernel shows."""
    out = {}
    for name, path in (("cpu.stat", "/sys/fs/cgroup/cpu.stat"), ("cpu.stat.v1", "/sys/fs/cgroup/cpu/cpu.stat"),
                       ("cpu.max", "/sys/fs/cgroup/cpu.max"), ("pressure", "/proc/pressure/cpu")):
        try:
            out[name] = " ".join(pathlib.Path(path).read_text().split())
        except OSError:
            pass
    try:
        fields = pathlib.Path("/proc/stat").read_text().splitlines()[0].split()
        out["steal_jiffies"], out["cpus"] = int(fields[8]), os.cpu_count()
    except (OSError, IndexError, ValueError):
        pass
    return out


def late_ms(late: list, lo_ns: float, hi_ns: float) -> float:
    return round(sum(max(0, min(hi_ns, b) - max(lo_ns, a)) for a, b in late) / 1e6, 3)


def long_row(span: dict, steps: list[dict], late_here: list, late_there: list) -> dict:
    """One long step: its span's fields, when it began, how late each watcher
    woke under it, and the STEP records round it."""
    lo_ns = span["start_mono"] * 1e9
    hi_ns = lo_ns + span["duration_ms"] * 1e6
    keep = ("duration_ms", "expected_ms", "lost_ms", "phase", "phase_ms", "cause", "gc_ms", "gc_generation",
            "profiler_ms", "step_kind", "decode_rows", "traced", "seq")
    return {**{k: span[k] for k in keep}, "at_s": round(span["start_ts"] - steps[0]["ts"], 3),
            "late_ms_this_process": late_ms(late_here, lo_ns, hi_ns),
            "late_ms_other_process": late_ms(late_there, lo_ns, hi_ns), "around": around(steps, span["seq"])}


def around(steps: list[dict], seq: int) -> list[dict]:
    """The long step's STEP record and its two neighbours, phases in ms."""
    rows = []
    for s in steps:
        if abs(s["seq"] - seq) <= 1:
            rows.append({"seq": s["seq"], "kind": s["step_kind"], "rows": s["decode_rows"], "wall_ms": s["wall_ms"],
                         "gap_ms": s["gap_ms"], "traced": s["traced"],
                         **{k: round(v / 1e3, 3) for k, v in s["phases_us"].items() if v >= 500.0}})
    return rows


async def amain(args) -> int:
    from benchmark import long_steps, plugins, serving, stats, traffic
    from dynamo_tpu import tracing

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    windows = [(int(s), False) for s in args.seeds.split(",") if s] + [(int(s), True) for s in args.traced_seeds.split(",") if s]
    args.seed = windows[0][0]  # the weights' seed, as in the benchmark's run of the first window
    state = await bench_run.bring_up(args, bench, cell, rehearsal)
    table = []
    late_here: list = []
    threading.Thread(target=watch, args=(late_here.append,), name="late-watch", daemon=True).start()
    late_file = ROOT / ".bench_work" / f"late-{os.getpid()}.txt"
    late_file.parent.mkdir(parents=True, exist_ok=True)
    late_file.write_text("")
    watcher = subprocess.Popen([sys.executable, __file__, "--watch", str(late_file)])
    try:
        await bench_run.outputs_check(state, args.seed)
        for seed, traced in windows:
            machine0 = machine_counters()
            plan = traffic.generate(state["mix"], seed=seed, seconds=args.seconds, vocab=state["conf"]["hf"]["vocab_size"])
            gc0 = (list(tracing.HOST_PAUSES.gc_count), list(tracing.HOST_PAUSES.gc_ns))
            ctx = await bench_run.offer(state, plan, args.seconds, trace=traced and not rehearsal)
            gc1 = (list(tracing.HOST_PAUSES.gc_count), list(tracing.HOST_PAUSES.gc_ns))
            lat, steps = ctx["latencies"], ctx["window"]["steps"]
            row = {"seed": seed, "traced": traced, "requests": len(ctx["results"]), "failed": lat["failed"],
                   "out_tok_s": stats.tokens_in_window(ctx["all_results"], ctx["seconds"]) / ctx["seconds"],
                   "itl_p50_ms": stats.percentile(lat["gaps_ms"], 50), "itl_p99_ms": stats.percentile(lat["gaps_ms"], 99),
                   "engine_steps": len(steps), "compiles": ctx["window"]["backend_compiles"],
                   # every collection from the lead-in's start to the window's end, however short
                   "collections_by_generation": [b - a for a, b in zip(gc0[0], gc1[0])],
                   "collection_ms_by_generation": [round((b - a) / 1e6, 3) for a, b in zip(gc0[1], gc1[1])]}
            decode = [s for s in steps if s["step_kind"] == "decode"]
            if decode:  # what the median gap turns on, beside it: the decode steps' own period and what they read
                row["decode"] = {"steps": len(decode),
                                 "period_p50_ms": stats.percentile([s["wall_ms"] + s["gap_ms"] for s in decode], 50),
                                 **{k: sum(s.get(k, 0) for s in decode) / len(decode)
                                    for k in ("decode_rows", "kv_tokens_full", "kv_tokens_window", "moe_experts_touched")}}
            names = [m["name"] for m in bench_run.cell_metrics(bench, "per_layer", cell)
                     if traced and not rehearsal or m["source"] == "program_counter"]
            row["metrics"] = {n: plugins.load("layer_metrics", n).read(ctx) for n in names}
            row["notes"] = ctx["notes"]
            if ctx["device_trace"]:
                row["device_trace"] = ctx["device_trace"]
            spans = long_steps.window_spans(ctx, "engine_long_step") or []
            late_there = [tuple(int(x) for x in line.split()) for line in late_file.read_text().splitlines()]
            lo, hi = steps[0]["t0_ns"], steps[-1]["t0_ns"]
            row["machine"] = {"before": machine0, "after": machine_counters()}
            row["late_wakeups"] = {"this_process": [round((b - a) / 1e6, 1) for a, b in late_here if lo <= a <= hi],
                                   "other_process": [round((b - a) / 1e6, 1) for a, b in late_there if lo <= a <= hi]}
            row["long_steps"] = [long_row(s, steps, late_here, late_there) for s in spans]
            row["pauses"] = [{k: s.get(k) for k in ("cause", "duration_ms", "generation", "collected", "what", "thread")}
                             | {"at_s": round(s["start_ts"] - steps[0]["ts"], 3)}
                             for s in long_steps.window_spans(ctx, "host_pause") or []]
            bench_run.say(long_steps_run=row)
            table.append(row)
    finally:
        watcher.kill()
        watcher.wait()
        await serving.stop(state["handles"])
    out = ROOT / "chiprun_out" / f"long_steps-{args.workload}.json"
    out.parent.mkdir(exist_ok=True)
    out.write_text(json.dumps(table, indent=1))
    print(json.dumps({"long_steps_run": [{k: r[k] for k in ("seed", "traced", "out_tok_s", "itl_p50_ms", "engine_steps",
                                                             "collections_by_generation", "collection_ms_by_generation")}
                                         | {"long_steps": len(r["long_steps"]), "pauses": len(r["pauses"]),
                                            "decode": r.get("decode"),
                                            **{k: v for k, v in r["metrics"].items() if "long_step" in k or "gc_pause" in k}}
                                         for r in table]}))
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload")
    ap.add_argument("--watch", help="be the watcher process: append late wake-ups to this file")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--traced-seeds", default="")
    ap.add_argument("--seconds", type=float, default=51.0)
    os.environ.setdefault("DYN_FLIGHT_BUFFER", "65536")
    for var, sub in (("DYN_INCIDENT_DIR", "incidents"), ("DYN_FLIGHT_DUMP_DIR", "flight")):
        os.environ.setdefault(var, str(ROOT / ".bench_work" / sub))
    args = ap.parse_args()
    if args.watch:
        with open(args.watch, "a", buffering=1) as out:
            watch(lambda gap: out.write(f"{gap[0]} {gap[1]}\n"))
    sys.exit(asyncio.run(amain(args)))
