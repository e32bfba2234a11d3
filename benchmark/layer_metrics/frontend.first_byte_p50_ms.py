"""``frontend_first_byte``: the engine's first token to its SSE chunk written
(the hop back, detokenizing, the frontend's event loop)."""
from benchmark import program_spans as ps


def read(ctx):
    return ps.request_p50_ms(ctx, ("frontend_first_byte",))
