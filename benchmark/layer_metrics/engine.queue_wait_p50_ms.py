"""``engine_queue_wait + engine_admission_wait``: handed to the engine until
the scheduler admitted it (the step in flight, then the admission plane)."""
from benchmark import program_spans as ps


def read(ctx):
    return ps.request_p50_ms(ctx, ("engine_queue_wait", "engine_admission_wait"))
