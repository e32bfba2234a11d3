"""How late the generator sent, 95th percentile of (sent - due), client clock."""
from benchmark import stats


def read(ctx):
    late = [x for x in ctx["latencies"]["lateness_ms"] if x < stats.FAILED_MS]
    return stats.percentile(late, 95) if late else None
