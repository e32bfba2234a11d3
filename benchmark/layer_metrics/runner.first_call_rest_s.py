"""Of ``runner.first_calls_s``, what none of JAX's compile events covers: the
summed ``rest_ms`` of set-up's ``runner_first_call`` spans, in seconds (the
cache key's hashing, pjit's argument work, the transfer, the first execution)."""
from benchmark import setup_spans


def read(ctx):
    return setup_spans.first_calls_value(ctx, "rest_s")
