"""``sched + build``: step start to a complete ``StepBatch`` (reap, schedule,
page reservation, the numpy fill, sampling arrays, masks). Decode steps, untraced."""
from benchmark import program_spans as ps


def read(ctx):
    return ps.phase_p50_ms(ctx, ("sched", "build"))
