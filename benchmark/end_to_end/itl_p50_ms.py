"""Median of all gaps between output tokens of the window's requests, pooled:
open loop, the requests due in the window, followed to their end; closed
loop, every gap that ended inside the window."""
from benchmark import stats


def read(ctx):
    return stats.percentile(ctx["latencies"]["gaps_ms"], 50)
