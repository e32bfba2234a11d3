#!/usr/bin/env python3
"""One run of one cell: ``python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

The harness knows no cell, model, traffic mix or metric by name. It finds the
cell in ``BENCHMARK.json``, its configuration in ``benchmark/configs/``, its
traffic in ``benchmark/traffic/``, its own rate and warm-up list in
``benchmark/cells/``, and each metric's reader in
``benchmark/end_to_end/`` or ``benchmark/layer_metrics/``. Set-up (runtime,
weights from the seed, cache pool, warm-up of the reachable step programs, the
outputs check, the lead-in) is timed phase by phase on lines of its own; the
last line of standard output is the result.

Without an accelerator this fails. With ``JAX_PLATFORMS=cpu`` pinned by the
caller it rehearses the whole control flow at the configuration's toy size,
names the device as cpu, prints no device metric and exits 3.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
HERE = ROOT / "benchmark"
sys.path.insert(0, str(ROOT))
EXIT_NO_DEVICE, EXIT_REHEARSAL = 2, 3
#: Seconds traced in the middle of the window of a ``--trace 1`` run.
TRACE_SECONDS = 3.0


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


class Phase:
    """Times one set-up phase and prints its seconds."""

    def __init__(self, name: str, **extra) -> None:
        self.name, self.extra = name, extra

    def __enter__(self):
        self.t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            say(phase=self.name, s=round(time.monotonic() - self.t0, 3), **self.extra)


def load_reader(kind: str, name: str):
    from benchmark import plugins

    return plugins.load(kind, name).read


def cell_metrics(bench: dict, kind: str, cell: dict) -> list[dict]:
    """The metrics of ``kind`` this cell reports: those that list it, and those
    that list nothing (end to end: every cell; per layer: every cell that
    reports the end-to-end metric they move)."""
    mine = {m["name"] for m in bench["end_to_end"] if cell["name"] in m.get("workloads", [cell["name"]])}
    if kind == "end_to_end":
        return [m for m in bench[kind] if m["name"] in mine]
    return [m for m in bench[kind]
            if (cell["name"] in m["workloads"] if "workloads" in m else m["moves"] in mine)]


def rehearsal_mix(mix: dict, engine: dict) -> dict:
    """The mix at toy size: lengths cut eightfold onto the toy chunk."""
    chunk = engine["chunk_prefill_tokens"]
    rows = [[max(chunk, p // 8 // chunk * chunk), max(2, o // 8)] for p, o in mix["lengths_per_100"]]
    ctx = max(p + o for p, o in rows)
    return {**mix, "lengths_per_100": rows, "lead_in_s": min(2.0, mix.get("lead_in_s", 0)),
            "clients": min(4, mix.get("clients", 0)),
            "warm": {"max_rows": min(mix["warm"]["max_rows"], engine["max_batch_size"]), "max_context_tokens": ctx}}


async def bring_up(args, bench: dict, cell: dict, rehearsal: bool, *, transform=None, warm: bool = True) -> dict:
    """Everything before the outputs check: device, configuration, weights from
    the seed (``transform`` re-codes them: the control of the outputs check),
    the serving stack, the warm-up. Returns the state the later steps share."""
    import jax

    from benchmark import serving, traffic, weights
    from dynamo_tpu.compile_cache import enable_compile_cache

    devices = jax.local_devices()
    dev0 = devices[0]
    if not rehearsal and (dev0.platform != "tpu" or len(devices) < cell["chips"]):
        print(f"no accelerator for this cell: platform {dev0.platform}, {len(devices)} device(s), "
              f"{cell['chips']} needed", file=sys.stderr)
        sys.exit(EXIT_NO_DEVICE)
    say(phase="runtime_start", s=round(time.monotonic() - T_START, 3), platform=dev0.platform,
        kind=dev0.device_kind, devices=len(devices), compile_cache=enable_compile_cache())
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    conf = serving.load_config(ROOT / config_entry["file"], rehearsal=rehearsal)
    mix = traffic.load_mix(HERE / "traffic" / f"{cell['traffic']}.json", HERE / "cells" / f"{cell['name']}.json")
    if rehearsal:
        mix = rehearsal_mix(mix, conf["serve"]["engine"])
    peaks_doc = json.loads((HERE / "peaks.json").read_text())
    if not rehearsal and dev0.device_kind not in peaks_doc:
        raise SystemExit(f"no peaks for device kind {dev0.device_kind!r} in benchmark/peaks.json")
    state = {"conf": conf, "mix": mix, "cell": cell, "rehearsal": rehearsal, "devices": devices,
             # the outputs check's lengths, cut to the rehearsal's toy context
             "check_scale": conf["serve"]["engine"]["max_seq_len"] / 4096 if rehearsal else 1.0,
             "peaks": peaks_doc.get(dev0.device_kind), "compiles": _compile_events()}
    mc = serving.model_config(conf)
    with Phase("weights", quant=conf["serve"]["quant"]):
        params = weights.make_weights(mc, args.seed, quant=conf["serve"]["quant"])
        if transform is not None:
            params = transform(params)
        jax.block_until_ready(params)
    with Phase("server_and_kv_pool"):
        handles = await serving.start(conf, mc, params)
        await serving.wait_listed(handles)
    state.update(params=params, handles=handles, service=handles["services"][0], core=handles["services"][0].core)
    if warm:
        compiles = state["compiles"]
        shapes = serving.warm_shapes(conf, mix["warm"])
        with Phase("warm_up", programs=len(shapes)):
            before = compiles.snapshot()
            slow: list = []
            await asyncio.get_running_loop().run_in_executor(
                None, serving.warm_up, state["core"], shapes,
                lambda b, t, n, s: slow.append([b, t, n, round(s, 2)]) if s > 1.0 else None)
            after = compiles.snapshot()
            say(warm_up=dict(programs=len(shapes), cache_hits=after["cache_hits"] - before["cache_hits"],
                             compiled=after["backend_compiles"] - before["backend_compiles"],
                             compile_s=round(after["backend_compile_s"] - before["backend_compile_s"], 1),
                             over_1s=slow[:40]))
    return state


_COMPILES = None


def _compile_events():
    """One listener per process, however many stacks it brings up."""
    global _COMPILES
    if _COMPILES is None:
        from benchmark import serving

        _COMPILES = serving.CompileEvents()
    return _COMPILES


async def outputs_check(state: dict, seed: int) -> dict:
    from benchmark import correct

    with Phase("outputs_check"):
        check = await correct.compare(state["service"], state["conf"], state["params"], seed,
                                      scale=state["check_scale"])
    say(outputs_check=check)
    return check


async def offer(state: dict, plan: dict, seconds: float, *, trace: bool, keep_trace: str | None = None) -> dict:
    """Offers one plan from a load generator process of its own and returns
    the readers' context: client results, the program's counters over the
    window and, traced, the reduced device trace of its middle seconds."""
    from benchmark import serving, stats, trace_reduce, traffic
    from dynamo_tpu import tracing

    handles, core, compiles = state["handles"], state["core"], state["compiles"]
    t0 = time.monotonic() + plan["lead_in_s"] + 1.0
    child = subprocess.Popen(
        [sys.executable, str(HERE / "loadgen.py")], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    loop = asyncio.get_running_loop()
    feed = loop.run_in_executor(None, child.communicate, json.dumps(
        {**plan, "base": handles["base"], "model": handles["model"], "t0": t0}).encode())
    reduced, device_trace = None, None
    try:
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        setup_s = time.monotonic() - T_START
        say(phase="lead_in", s=plan["lead_in_s"], traffic_digest=traffic.digest(plan))
        c0 = serving.counters(core, compiles)
        if trace:
            trace_dir = ROOT / ".bench_work" / f"trace-{state['cell']['name']}"
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True)
            await asyncio.sleep(max(0.0, seconds / 2 - TRACE_SECONDS / 2))
            await loop.run_in_executor(None, tracing.start_device_trace, str(trace_dir))
            await asyncio.sleep(TRACE_SECONDS)
            await loop.run_in_executor(None, tracing.stop_device_trace)
        await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
        c1 = serving.counters(core, compiles)
        stdout, _ = await feed
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited {child.returncode}")
    doc = json.loads(stdout)
    if trace:
        reduced = await loop.run_in_executor(None, trace_reduce.load_xplane, str(trace_dir))
        say(trace_shape=[[p["name"], ln["name"], len(ln["events"])] for p in reduced["planes"] for ln in p["lines"]])
        lo, hi = trace_reduce.window_of(reduced)
        device_trace = {"busy_s": trace_reduce.busy_seconds(reduced), "window_s": (hi - lo) / 1e9}
        if keep_trace:
            pathlib.Path(keep_trace).parent.mkdir(parents=True, exist_ok=True)
            pathlib.Path(keep_trace).write_text(json.dumps(reduced))
        shutil.rmtree(trace_dir, ignore_errors=True)
    results = doc["results"]
    if plan["loop"] == "closed":  # no due times: every request alive in the window is the window's
        counted = [r for r in results if r["counted"] and r["due"] < seconds and r["end"] >= 0.0]
        latencies = stats.request_latencies(counted, window_s=float(seconds))
    else:
        counted = [r for r in results if r["counted"] and 0.0 <= r["due"] < seconds]
        latencies = stats.request_latencies(counted)
    return {
        "seconds": float(seconds), "setup_s": setup_s, "results": counted, "all_results": results,
        "never_sent": doc["never_sent"], "latencies": latencies,
        "window": serving.window_counters(core, compiles, c0, c1), "trace": reduced,
        "step_programs": trace_reduce.step_programs(reduced) if reduced else [],
        "device_trace": device_trace, "conf": state["conf"], "peaks": state["peaks"], "notes": {},
        "mean_context_tokens": sum(r["prompt_tokens"] + r["want"] / 2 for r in counted) / max(1, len(counted)),
    }


async def run(args, bench: dict, cell: dict, rehearsal: bool) -> tuple[dict, int]:
    from benchmark import serving, trace_reduce, traffic

    state = await bring_up(args, bench, cell, rehearsal)
    try:
        check = await outputs_check(state, args.seed)
        plan = traffic.generate(state["mix"], seed=args.seed, seconds=float(args.seconds),
                                vocab=state["conf"]["hf"]["vocab_size"])
        longest = max(len(r["prompt"]) + r["max_tokens"] for r in plan["requests"])
        if longest > state["mix"]["warm"]["max_context_tokens"]:  # it would compile inside the window
            raise SystemExit(f"a request of {longest} tokens is beyond the cell's warmed "
                             f"{state['mix']['warm']['max_context_tokens']}")
        ctx = await offer(state, plan, float(args.seconds), trace=bool(args.trace) and not rehearsal,
                          keep_trace=args.keep_trace)
    finally:
        await serving.stop(state["handles"])
    lat = ctx["latencies"]
    metrics = {}
    for m in cell_metrics(bench, "per_layer" if args.trace else "end_to_end", cell):
        if rehearsal and m["source"] in ("device_trace", "program_span"):
            continue  # never a device number from a CPU run
        value = load_reader("layer_metrics" if args.trace else "end_to_end", m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if args.trace:  # the traced run's end-to-end numbers are printed, and are not the cell's
        say(traced_run_end_to_end={m["name"]: load_reader("end_to_end", m["name"])(ctx)
                                   for m in cell_metrics(bench, "end_to_end", cell)})
    from benchmark import stats

    # Steadier or coarser statistics of the same window, beside the cell's own: never judged.
    say(beside={"ttft_ms": {f"p{q}": stats.percentile(lat["ttft_ms"], q) for q in (50, 75, 90, 95)} if lat["ttft_ms"] else {},
                "itl_ms": {**{f"p{q}": stats.percentile(lat["gaps_ms"], q) for q in (50, 90, 95, 99)},
                           "mean": sum(lat["gaps_ms"]) / len(lat["gaps_ms"])} if lat["gaps_ms"] else {},
                "out_tok_s": stats.tokens_in_window(ctx["all_results"], ctx["seconds"]) / ctx["seconds"]})
    say(window=dict(requests=len(ctx["results"]), never_sent=len(ctx["never_sent"]), failed=lat["failed"],
                    gaps=len(lat["gaps_ms"]), engine_steps=len(ctx["window"]["steps"]),
                    flight_ring_wrapped=ctx["window"]["steps_lost"],
                    attn_dispatch=ctx["window"]["attn_dispatch"], notes=ctx["notes"]))
    devices = state["devices"]
    mem = [d.memory_stats() or {} for d in devices[: cell["chips"]]]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": 1 if rehearsal else cell["chips"],
              "memory_peak_bytes": max((s.get("peak_bytes_in_use", 0) for s in mem), default=0)}
    result = {"correct": bool(check["ok"]), "attempted": len(ctx["results"]) + len(ctx["never_sent"]),
              "failed": lat["failed"] + len(ctx["never_sent"]), "metrics": metrics, "device": device}
    if ctx["device_trace"]:
        device.update(ctx["device_trace"])
        result["breakdown"] = {"device_ops": trace_reduce.top_device_ops(ctx["trace"], 10),
                               "idle_gaps": trace_reduce.idle_gaps(ctx["trace"], 10)}
    return result, (EXIT_REHEARSAL if rehearsal else 0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, help="write the reduced trace of a --trace 1 run to this file")
    args = ap.parse_args()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if cell is None:
        print(f"no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 1
    rehearsal = os.environ.get("JAX_PLATFORMS", "") == "cpu"
    # Observability only: a ring that holds every step of the longest window,
    # and the program's incident and flight dumps inside the checkout, not /tmp.
    os.environ.setdefault("DYN_FLIGHT_BUFFER", "65536")
    for var, sub in (("DYN_INCIDENT_DIR", "incidents"), ("DYN_FLIGHT_DUMP_DIR", "flight")):
        os.environ.setdefault(var, str(ROOT / ".bench_work" / sub))
    result, code = asyncio.run(run(args, bench, cell, rehearsal))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
