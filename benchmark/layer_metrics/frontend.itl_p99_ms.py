"""99th percentile of the pooled gaps between output tokens, client clock.
Not judged: it sits on the edge between two lengths of mixed step (the rows
are padded to a power of two) and flips between them from run to run."""
from benchmark import stats


def read(ctx):
    gaps = ctx["latencies"]["gaps_ms"]
    return stats.percentile(gaps, 99) if gaps else None
