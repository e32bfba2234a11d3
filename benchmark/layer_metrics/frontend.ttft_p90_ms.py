"""90th percentile, over the requests due in the window, of (first token
received - due time); a failed request lies beyond it. Not judged: PERF.md."""
from benchmark import stats


def read(ctx):
    ttft = ctx["latencies"]["ttft_ms"]
    return stats.percentile(ttft, 90) if ttft else None
