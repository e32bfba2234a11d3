"""Median device time of a mixed step program (those begun under ``engine.mixed``)."""
from benchmark import stats


def read(ctx):
    if ctx["trace"] is None:
        return None
    durs = [s["dur"] / 1e6 for s in ctx["step_programs"] if s["span"] == "engine.mixed"]
    return stats.percentile(durs, 50) if durs else None
