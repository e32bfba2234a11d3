"""Seconds a worker took to come up, as the program timed it: the
``duration_ms`` of its ``worker_bring_up`` span (``dynamo_tpu.launch``: the
parameters, the runner's construction, the engine, the endpoints served and the
card published), summed where a process brought up several."""
from benchmark import setup_spans


def read(ctx):
    return setup_spans.bring_up_s(ctx)
