"""Least time for the bytes the routed experts need in a decode step (the three
matrices of every held expert some row chose: ``experts_step`` of the
configuration's counts, from the distinct experts the step's own program
counted, ``moe_experts_touched``) at the chip's HBM peak, over the summed
device time of the ``moe_grouped_matmul_int8`` events inside that step's
program; median over the traced decode steps. Needed bytes, never moved bytes
(a kernel that reads an expert once per row tile moves more), and operations
are not counted: at most 100 by construction.

A program's counters are in the STEP record that took them when the program
had ended: the step's own in a synchronous step, the next step's in the
pipelined loop. The holder is checked by its ``moe_choices`` (a decode step
routes rows x choices a token); a step whose counters cannot be told is left
out. A program without the counters or the kernel, or a configuration whose
counts have no ``experts_step``, gives nothing to read."""
import bisect

from benchmark import attn_kernels, plugins, stats, trace_reduce

KERNEL = "moe_grouped_matmul_int8"


def read(ctx):
    if ctx.get("trace") is None or ctx.get("peaks") is None or not trace_reduce.device_planes(ctx["trace"]):
        return None
    counts = plugins.load("kernel_counts", ctx["conf"]["serve"]["kernel_counts"])
    if not hasattr(counts, "experts_step"):
        return None
    hf, hbm = ctx["conf"]["hf"], ctx["peaks"]["hbm_bytes_per_s"]
    weight_bytes = 1.0 if ctx["conf"]["serve"]["quant"] == "int8" else 2.0
    steps = ctx["window"]["steps"]
    traced = [i for i, s in enumerate(steps) if s.get("traced") and s.get("ann_ns")]
    mods, events_inside = attn_kernels.kernel_events_by_program(ctx["trace"], KERNEL)
    mod_starts = [m[1] for m in mods]
    rows = []
    for i, (name, a0, adur) in zip(traced, trace_reduce.host_spans(ctx["trace"])):
        rec, after = steps[i], steps[i + 1] if i + 1 < len(steps) else {}
        holder = after if after.get("overlap_mode") == "overlapped" else rec
        if rec["step_kind"] != "decode" or name != "engine.decode" or not holder.get("moe_experts_touched"):
            continue
        need = counts.experts_step(hf, experts_touched_total=holder["moe_experts_touched"], weight_bytes=weight_bytes)
        m = bisect.bisect_left(mod_starts, a0)
        if (holder["moe_choices"] != rec["decode_rows"] * need["choices_per_token"]
                or m == len(mods) or mods[m][1] >= a0 + adur):
            continue  # another program's counters, or a program that began outside its annotation
        kernel_s = sum(e[2] for e in events_inside(mods[m])) / 1e9
        if kernel_s > 0:
            rows.append({"share_pct": 100.0 * need["bytes"] / hbm / kernel_s, "kernel_ms": kernel_s * 1e3,
                         "needed_bytes": need["bytes"], "experts_touched": holder["moe_experts_touched"]})
    if not rows:
        return None
    ctx["notes"]["moe_expert_roofline"] = {"steps": len(rows), **{k: stats.percentile([r[k] for r in rows], 50)
                                                                for k in ("kernel_ms", "needed_bytes", "experts_touched")}}
    return stats.percentile([r["share_pct"] for r in rows], 50)
