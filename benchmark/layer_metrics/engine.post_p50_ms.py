"""``post``: result on the host to the outputs list (stop checks, emit, finish,
page commit). Decode steps, untraced."""
from benchmark import program_spans as ps


def read(ctx):
    return ps.phase_p50_ms(ctx, ("post",))
