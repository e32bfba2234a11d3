"""Median device time of a decode step program (those begun under ``engine.decode``)."""
from benchmark import stats


def read(ctx):
    if ctx["trace"] is None:
        return None
    durs = [s["dur"] / 1e6 for s in ctx["step_programs"] if s["span"] == "engine.decode"]
    return stats.percentile(durs, 50) if durs else None
