"""The paged attention kernels' share of their roofline, step by step.

For each traced step of one kind (the k-th traced STEP record is the k-th
``engine.*`` annotation of the trace, as in ``program_spans.clock_join``): the
step program that began under the annotation, the summed device time of the
events of one kernel inside it, and the least time the chip's HBM could move
the bytes that kernel *needs* for the step (``attention_step`` of the
configuration's ``kernel_counts`` file, from the record's ``kv_tokens_full``
and ``kv_tokens_window``). A program without those fields, a configuration
whose counts have no ``attention_step``, or a trace without the kernel gives
nothing to read: ``None``, never an error.
"""

from __future__ import annotations

import bisect

from benchmark import plugins, stats, trace_reduce


def kernel_events_by_program(trace: dict, kernel: str):
    """``(step programs, events_inside)`` of the first device: the step modules
    by start, and a function giving one program's events of ``kernel`` in order."""
    plane = trace_reduce.device_planes(trace)[0]
    mods = sorted((e for e in trace_reduce.line_events(plane, trace_reduce.MODULES_LINE)
                   if trace_reduce.STEP_MARK in e[0]), key=lambda e: e[1])
    ops = sorted((e for e in trace_reduce.line_events(plane, trace_reduce.OPS_LINE) if e[0].startswith(kernel)),
                 key=lambda e: e[1])
    starts = [o[1] for o in ops]

    def inside(mod) -> list[list]:
        return ops[bisect.bisect_left(starts, mod[1]): bisect.bisect_left(starts, mod[1] + mod[2])]

    return mods, inside


def per_step_shares(ctx, *, kernel: str, step_kind: str) -> list[dict]:
    if ctx.get("trace") is None or ctx.get("peaks") is None:
        return []
    counts = plugins.load("kernel_counts", ctx["conf"]["serve"]["kernel_counts"])
    recs = [s for s in ctx["window"]["steps"] if s.get("traced") and s.get("ann_ns")]
    planes = trace_reduce.device_planes(ctx["trace"])
    if not hasattr(counts, "attention_step") or not planes or not any("kv_tokens_full" in s for s in recs):
        return []
    anns = trace_reduce.host_spans(ctx["trace"])
    mods, events_inside = kernel_events_by_program(ctx["trace"], kernel)
    mod_starts = [m[1] for m in mods]
    hf, hbm = ctx["conf"]["hf"], ctx["peaks"]["hbm_bytes_per_s"]
    out = []
    for rec, (name, a0, adur) in zip(recs, anns):
        if rec["step_kind"] != step_kind or name != f"engine.{step_kind}" or "kv_tokens_full" not in rec:
            continue
        i = bisect.bisect_left(mod_starts, a0)
        if i == len(mods) or mods[i][1] >= a0 + adur:
            continue  # the program began outside its annotation (the planes' skew): dropped
        inside = events_inside(mods[i])
        kernel_s = sum(e[2] for e in inside) / 1e9
        if kernel_s <= 0:
            continue
        new = rec["decode_rows"] + rec["chunk_tokens"]
        need = counts.attention_step(hf, kv_tokens_full=rec["kv_tokens_full"],
                                     kv_tokens_window=rec["kv_tokens_window"], rows=1, new_tokens=new)
        out.append({"share_pct": 100.0 * need["bytes"] / hbm / kernel_s, "kernel_ms": kernel_s * 1e3,
                    "events": len(inside), "needed_bytes": need["bytes"], "kv_tokens_full": rec["kv_tokens_full"],
                    "kv_tokens_window": rec["kv_tokens_window"]})
    return out


def roofline_pct(ctx, *, kernel: str, step_kind: str, note: str):
    rows = per_step_shares(ctx, kernel=kernel, step_kind=step_kind)
    if not rows:
        return None
    ctx["notes"][note] = {"steps": len(rows), **{k: stats.percentile([r[k] for r in rows], 50)
                                                  for k in ("kernel_ms", "events", "needed_bytes",
                                                            "kv_tokens_full", "kv_tokens_window")}}
    return stats.percentile([r["share_pct"] for r in rows], 50)
