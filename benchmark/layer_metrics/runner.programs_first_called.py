"""Step programs first called in set-up: the count of set-up's
``runner_first_call`` spans, the part of the bucket lattice the cell reaches."""
from benchmark import setup_spans


def read(ctx):
    return setup_spans.first_calls_value(ctx, "programs")
