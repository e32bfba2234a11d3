"""``engine_prefill``: admission to the first token out of the step loop (the
prompt's chunks, each riding a mixed step)."""
from benchmark import program_spans as ps


def read(ctx):
    return ps.request_p50_ms(ctx, ("engine_prefill",))
