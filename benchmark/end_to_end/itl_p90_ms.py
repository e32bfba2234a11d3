"""90th percentile of the pooled gaps between output tokens of the window's
requests: a gap in which the row rode a mixed step (a newcomer's 64-token
chunk) and not a plain decode step."""
from benchmark import stats


def read(ctx):
    return stats.percentile(ctx["latencies"]["gaps_ms"], 90)
