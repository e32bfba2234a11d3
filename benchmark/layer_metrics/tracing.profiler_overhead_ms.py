"""Tracing on against off, in one run: host time of a decode step (as
``runner.step_host_p50_ms``) inside the profiled seconds less outside them."""
from benchmark import program_spans as ps


def read(ctx):
    on, off = ps.phase_p50_ms(ctx, ps.HOST_PHASES, traced=True), ps.phase_p50_ms(ctx, ps.HOST_PHASES)
    return on - off if on is not None and off is not None else None
