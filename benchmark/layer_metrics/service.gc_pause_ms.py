"""Milliseconds the process spent in garbage collections of 1 ms and more
inside the window: the summed duration of the ``host_pause`` spans of cause
``gc``. A collection holds the interpreter on whatever thread runs it, so every
thread of the service stands still under it. ``ctx["notes"]`` gets count and ms
by generation and by thread, the longest, the profiler's own pauses and, where
the run has a trace, the device's idle seconds under a pause, by cause."""
from benchmark import long_steps


def read(ctx):
    found = long_steps.gc_pauses(ctx)
    if found is None:
        return None
    ctx["notes"]["host_pauses"] = found["note"]
    return found["ms"]
