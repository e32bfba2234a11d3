"""Where does the 1B decode's last 6% go? (VERDICT r4 item 9)

Runs the headline 1B config's steady-state decode under an XPlane trace,
then breaks one burst down: per-op device time from the trace's XLA op
events, host gaps between dispatches, and the modeled-bytes bandwidth
view. Prints a JSON summary; the trace directory is left for TensorBoard.

Usage (on the chip): python tools/profile_1b_decode.py [trace_dir]
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


PRESET = os.environ.get("PROFILE_PRESET", "llama-3.2-1b")


def build_core(batch: int, isl: int, osl: int):
    from dynamo_tpu.engine.core import EngineConfig, EngineCore
    from dynamo_tpu.engine.runner import ModelRunner
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import PRESETS
    from dynamo_tpu.protocols.common import PreprocessedRequest, SamplingOptions, StopConditions

    cfg = PRESETS[PRESET]
    page = int(os.environ.get("PROFILE_PAGE", "128"))
    pages_per_seq = (isl + osl) // page + 2
    num_pages = batch * pages_per_seq + 8
    params = llama.init_params(cfg, 0)
    runner = ModelRunner(cfg, params, num_pages=num_pages, page_size=page,
                         max_batch_size=batch, prefill_bucket=max(isl, 64))
    core = EngineCore(runner, EngineConfig(
        num_pages=num_pages, page_size=page, max_batch_size=batch,
        max_prefill_tokens=isl * 32, max_seq_len=isl + osl + 8,
        enable_prefix_caching=False,
        decode_steps=int(os.environ.get("PROFILE_DECODE_STEPS", "32")),
    ))
    rng = np.random.default_rng(0)
    for _ in range(batch):
        core.add_request(PreprocessedRequest(
            token_ids=rng.integers(1, cfg.vocab_size - 1, size=isl).tolist(),
            sampling=SamplingOptions(temperature=0.0),
            stop=StopConditions(max_tokens=osl, ignore_eos=True),
        ))
    return core, cfg, params


def op_breakdown(trace_dir: str) -> tuple[list[tuple[str, float]], float, int]:
    """Aggregate device-op microseconds from the trace's trace.json.gz.

    Returns ``(per_op_totals_sorted, total_us, num_device_cores)``. The
    per-op totals and ``total_us`` are SUMMED over every device core pid,
    so busy-fraction math must divide by ``num_device_cores`` — an 8-core
    trace's op time can legitimately be 8x the wall window.
    """
    paths = glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True)
    if not paths:
        return [], 0.0, 0
    with gzip.open(sorted(paths)[-1], "rt") as f:
        doc = json.load(f)
    events = doc.get("traceEvents", [])
    # Device rows: pid whose process_name metadata names an accelerator
    # ("/device:TPU:0" on chip). "/host:CPU" rows are the host runtime, not
    # XLA ops, but on a CPU-only trace they're all there is — include them
    # as fallback.
    def pids(pred):
        return {
            e["pid"] for e in events
            if e.get("ph") == "M" and e.get("name") == "process_name"
            and pred(str(e.get("args", {}).get("name", "")))
        }

    device_pids = pids(lambda n: "TPU" in n or "/device:" in n)
    if not device_pids:
        device_pids = pids(lambda n: "CPU" in n)
    totals: dict[str, float] = {}
    for e in events:
        if e.get("ph") == "X" and e.get("pid") in device_pids:
            name = e.get("name", "?")
            totals[name] = totals.get(name, 0.0) + float(e.get("dur", 0.0))
    ordered = sorted(totals.items(), key=lambda kv: -kv[1])
    return ordered, sum(totals.values()), len(device_pids)


def main() -> None:
    import bench as bench_mod
    from dynamo_tpu import tracing
    from dynamo_tpu.observability import cost as cost_mod

    trace_dir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/trace_1b"
    batch = int(os.environ.get("PROFILE_BATCH", "256"))
    isl = int(os.environ.get("PROFILE_ISL", "512"))
    osl = int(os.environ.get("PROFILE_OSL", "256"))
    page = int(os.environ.get("PROFILE_PAGE", "128"))
    core, cfg, params = build_core(batch, isl, osl)

    # Prefill + warm the burst programs.
    while core.waiting:
        core.step()
    for _ in range(3):
        core.step()

    # Traced steady-state decode window.
    tracing.start_device_trace(trace_dir)
    t0 = time.perf_counter()
    generated = 0
    steps = 0
    while core.has_work and steps < 6:  # ~6 bursts of 32 = 192 tokens/seq
        outs = core.step()
        generated += sum(len(o.token_ids) for _, o in outs)
        steps += 1
    elapsed = time.perf_counter() - t0
    tracing.stop_device_trace()

    tok_per_sec = generated / elapsed
    step_bytes = bench_mod.decode_step_bytes(params, cfg, batch, isl, osl, page)
    roofline = bench_mod.roofline_tok_per_sec(step_bytes, batch)
    # Same estimate helpers the serving-path CostRegistry uses — one tree
    # walk shared between this tool and the live ledger (ISSUE 19 dedupe).
    weight_bytes = cost_mod.weight_stream_bytes(params, cfg)
    # XLA's own per-dispatch byte count for the decode bucket, from the
    # runner's cost registry: the cross-check column against the modeled
    # accounting above (agreement within ~15% is the acceptance bar; a
    # larger gap means the model or the extraction is lying).
    cost_analysis_bytes = 0
    cost_source = "disabled"
    cost_reg = getattr(core.runner, "cost_registry", None)
    if cost_reg is not None:
        cost_reg.drain(timeout=60.0)
        decode_row = cost_reg.ledger().get("decode", {})
        cost_analysis_bytes = int(decode_row.get("bytes_per_step", 0))
        rec = cost_reg.record_for("multi_step") or cost_reg.record_for("step")
        cost_source = rec.source if rec is not None else "none"
    ops, device_us, num_cores = op_breakdown(trace_dir)
    # device_us sums op time over every device core pid; per-core busy time
    # is that total divided by the core count (the old code skipped the
    # divide and reported fractions like 3.06 on multi-core traces).
    busy = device_us / (num_cores * elapsed * 1e6) if num_cores else 0.0
    summary = {
        "tok_per_sec_window": round(tok_per_sec, 1),
        "vs_roofline": round(tok_per_sec / roofline, 4),
        "window_seconds": round(elapsed, 3),
        "decode_tokens": generated,
        "device_op_us_total": round(device_us, 0),
        "device_cores": num_cores,
        "wall_us": round(elapsed * 1e6, 0),
        "device_busy_fraction": round(busy, 4),
        # Weight traffic per generated token, from the measured tree (packed
        # quantized leaves at true size) — HBM-utilization claims in bench
        # notes derive from these instead of hand-computed weight sizes.
        "weight_bytes_per_step": weight_bytes,
        "weight_bytes_per_token": round(weight_bytes / batch, 1),
        "weight_frac_of_step_bytes": round(weight_bytes / step_bytes, 4),
        # XLA cost-analysis bytes per decode dispatch (0 = cost plane off),
        # next to the modeled column so the two instruments cross-check.
        "cost_analysis_bytes": cost_analysis_bytes,
        "cost_analysis_source": cost_source,
        "modeled_step_bytes": step_bytes,
        "cost_vs_modeled": (
            round(cost_analysis_bytes / step_bytes, 4) if step_bytes else 0.0
        ),
        "top_ops_us": [[n, round(us, 0)] for n, us in ops[:15]],
        "trace_dir": trace_dir,
    }
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
