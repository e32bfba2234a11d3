"""Milliseconds the window's long steps took beyond their kind's expected
period: the sum of ``lost_ms`` over the ``engine_long_step`` spans whose
``cause`` is not ``profiler`` (the measurement's own pauses are named and left
out); 0.0 where the program has the tracker and no step was long, nothing where
it has none. ``ctx["notes"]`` gets the count, lost ms by phase and by cause,
the five longest and the share of the window."""
from benchmark import long_steps


def read(ctx):
    found = long_steps.long_steps(ctx)
    if found is None:
        return None
    ctx["notes"]["long_steps"] = found["note"]
    return found["lost_ms"]
