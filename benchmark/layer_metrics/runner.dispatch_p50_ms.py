"""``dispatch``: pad, pack, host-to-device and the enqueue, until the jitted
call returns. Decode steps, untraced."""
from benchmark import program_spans as ps


def read(ctx):
    return ps.phase_p50_ms(ctx, ("dispatch",))
