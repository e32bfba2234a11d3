"""``handoff + route + intake + submit``: from one step's return in the worker
thread to the next step's start, through the service's event loop (not
``no_work``: waiting for a request is not the host's cost). Decode steps, untraced."""
from benchmark import program_spans as ps


def read(ctx):
    return ps.phase_p50_ms(ctx, ps.BETWEEN_PHASES)
