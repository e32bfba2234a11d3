"""Least time for the bytes the Mamba-2 decode kernel needs in a decode step
(each touched row's recurrent state read once and written once in float32,
every layer, and the kernel's small inputs and output: ``state_step`` of the
configuration's counts, from the STEP record's ``state_rows``) at the chip's
HBM peak, over the summed device time of the ``mamba_decode_step`` events
inside that step's program; median over the traced decode steps. Memory-bound
by construction: operations are not counted. A program without ``state_rows``
or without the kernel (every program before the one that has a mixer), or a
configuration whose counts have no ``state_step``, gives nothing to read."""
import bisect

from benchmark import attn_kernels, plugins, stats, trace_reduce

KERNEL = "mamba_decode_step"


def read(ctx):
    if ctx.get("trace") is None or ctx.get("peaks") is None or not trace_reduce.device_planes(ctx["trace"]):
        return None
    counts = plugins.load("kernel_counts", ctx["conf"]["serve"]["kernel_counts"])
    recs = [s for s in ctx["window"]["steps"] if s.get("traced") and s.get("ann_ns")]
    if not hasattr(counts, "state_step") or not any(s.get("state_rows") for s in recs):
        return None
    hf, hbm = ctx["conf"]["hf"], ctx["peaks"]["hbm_bytes_per_s"]
    mods, events_inside = attn_kernels.kernel_events_by_program(ctx["trace"], KERNEL)
    mod_starts = [m[1] for m in mods]
    rows = []
    for rec, (name, a0, adur) in zip(recs, trace_reduce.host_spans(ctx["trace"])):
        if rec["step_kind"] != "decode" or name != "engine.decode" or not rec.get("state_rows"):
            continue
        m = bisect.bisect_left(mod_starts, a0)
        if m == len(mods) or mods[m][1] >= a0 + adur:
            continue  # the program began outside its annotation (the planes' skew): dropped
        inside = events_inside(mods[m])
        kernel_s = sum(e[2] for e in inside) / 1e9
        if kernel_s <= 0:
            continue
        need = counts.state_step(hf, rows=rec["state_rows"])
        rows.append({"share_pct": 100.0 * need["bytes"] / hbm / kernel_s, "kernel_ms": kernel_s * 1e3,
                     "events": len(inside), "needed_bytes": need["bytes"], "state_rows": rec["state_rows"]})
    if not rows:
        return None
    ctx["notes"]["ssm_decode_roofline"] = {"steps": len(rows), **{k: stats.percentile([r[k] for r in rows], 50)
                                                                for k in ("kernel_ms", "events", "needed_bytes", "state_rows")}}
    return stats.percentile([r["share_pct"] for r in rows], 50)
