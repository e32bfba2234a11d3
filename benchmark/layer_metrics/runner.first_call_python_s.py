"""Of ``runner.first_calls_s``, the host's own work, which no cache saves: the
summed ``trace_ms + lower_ms`` of set-up's ``runner_first_call`` spans (tracing
to a jaxpr and lowering it to MLIR, each as self time), in seconds."""
from benchmark import setup_spans


def read(ctx):
    return setup_spans.first_calls_value(ctx, "python_s")
