"""Of ``runner.first_calls_s``, the backend: the summed ``backend_ms`` of
set-up's ``runner_first_call`` spans, in seconds: XLA's and Mosaic's compile in
a cold run, the persistent cache's read and load in a warm one."""
from benchmark import setup_spans


def read(ctx):
    return setup_spans.first_calls_value(ctx, "backend_s")
