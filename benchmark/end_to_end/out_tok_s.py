"""Output tokens received inside the window over the window's seconds
(every request counts, also those begun in the lead-in)."""
from benchmark import stats


def read(ctx):
    return stats.tokens_in_window(ctx["all_results"], ctx["seconds"]) / ctx["seconds"]
