"""Median, over all requests due in the window, of (first token received -
due time); a failed or refused request lies beyond it. The median and not a
tail: below the knee a window of 51 s holds some thirty chat turns, three
beyond a 90th percentile (PERF.md has what the tail did)."""
from benchmark import stats


def read(ctx):
    return stats.percentile(ctx["latencies"]["ttft_ms"], 50)
