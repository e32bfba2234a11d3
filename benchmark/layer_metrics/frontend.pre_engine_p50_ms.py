"""``frontend_pre_engine``: ``http_request`` start to the engine's ``generate``
entered (preprocess, route, the hop). Requests that came in inside the window."""
from benchmark import program_spans as ps


def read(ctx):
    return ps.request_p50_ms(ctx, ("frontend_pre_engine",))
