"""Median gap on the device between one step program's end and the next's start."""
from benchmark import stats, trace_reduce


def read(ctx):
    if ctx["trace"] is None:
        return None
    gaps = trace_reduce.step_gaps_ms(ctx["step_programs"])
    return stats.percentile(gaps, 50) if gaps else None
