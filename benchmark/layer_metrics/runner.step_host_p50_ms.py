"""Host time of one decode step with no profiler running: every phase of the
step and of the gap before it but ``wait`` (the blocking read-back) and
``no_work`` (no request). Median over the window's decode steps outside the
profiled seconds; ``ctx["notes"]`` gets the p50 of every phase by step kind."""
from benchmark import program_spans as ps


def read(ctx):
    table = ps.phase_table(ctx)
    if table:
        ctx["notes"]["phase_p50_ms"] = table
    return ps.phase_p50_ms(ctx, ps.HOST_PHASES)
