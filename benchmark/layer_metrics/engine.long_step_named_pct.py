"""Of the milliseconds ``engine.long_step_lost_ms`` sums, the share whose span
names a cause (``gc`` or ``compile``; the profiler's are out of both sides):
100 where nothing was lost, nothing where the program has no tracker."""
from benchmark import long_steps


def read(ctx):
    found = long_steps.long_steps(ctx)
    if found is None:
        return None
    return 100.0 * found["named_ms"] / found["lost_ms"] if found["lost_ms"] > 0 else 100.0
