"""Of the requests set-up's first calls made to the persistent compile cache,
the share that found their program: 100 x ``cache_hits`` / (``cache_hits`` +
``cache_misses``) over set-up's ``runner_first_call`` spans: 100 in a warm run,
0 in a cold one; nothing where no first call asked the cache."""
from benchmark import setup_spans


def read(ctx):
    found = setup_spans.first_calls(ctx)
    asked = found["cache_hits"] + found["cache_misses"] if found else 0
    return 100.0 * found["cache_hits"] / asked if asked else None
