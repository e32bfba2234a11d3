"""Seconds set-up spent in the first calls of its step programs: the summed
``duration_ms`` of the ``runner_first_call`` spans begun before the window's
first STEP record (the warm-up's, the outputs check's, the lead-in's); nothing
where the program writes no such span or the span ring has dropped spans.
``ctx["notes"]["set_up"]`` gets, by program kind (``t1``: one token a row;
``chunk``), the count and the mean of each part, the five longest with their
buckets, ``cache_saved_s`` (what the cache's entries cost to compile), the
first calls an engine step made, and the ring's ``dropped``."""
from benchmark import setup_spans


def read(ctx):
    found = setup_spans.first_calls(ctx)
    ctx["notes"]["set_up"] = found["note"] if found else {"ring_dropped": setup_spans.ring_dropped()}
    return found["first_calls_s"] if found else None
