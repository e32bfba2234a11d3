"""Least time for a decode step's needed bytes and operations at the chip's
peaks, over the measured device time of a decode step. The step is taken at
the window's mean decode rows and mean context; ``ctx["notes"]`` gets the bound."""
from benchmark import plugins, stats


def read(ctx):
    if ctx["trace"] is None or ctx["peaks"] is None:
        return None
    durs = [s["dur"] / 1e9 for s in ctx["step_programs"] if s["span"] == "engine.decode"]
    steps = [s for s in ctx["window"]["steps"] if s["step_kind"] == "decode" and s["decode_rows"]]
    if not durs or not steps:
        return None
    counts = plugins.load("kernel_counts", ctx["conf"]["serve"]["kernel_counts"])
    rows = sum(s["decode_rows"] for s in steps) / len(steps)
    need = counts.decode_step(
        ctx["conf"]["hf"], rows=rows, contexts_total=rows * ctx["mean_context_tokens"],
        weight_bytes=1.0 if ctx["conf"]["serve"]["quant"] == "int8" else 2.0)
    least, bound = counts.least_seconds(need, ctx["peaks"])
    ctx["notes"]["decode_roofline"] = {"bound": bound, "rows": rows, "needed_bytes": need["bytes"],
                                       "needed_flops": need["flops"], "experts_touched": need["experts_touched"],
                                       "least_ms": least * 1e3}
    return 100.0 * least / stats.percentile(durs, 50)
