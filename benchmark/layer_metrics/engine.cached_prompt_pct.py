"""Share of the counted requests' prompt tokens served from the prefix cache
(``num_cached_at_start``, as the engine reports it in each answer's usage)."""


def read(ctx):
    done = [r for r in ctx["results"] if r["counted"] and r["ok"]]
    total = sum(r["prompt_tokens"] for r in done)
    return 100.0 * sum(r["cached"] or 0 for r in done) / total if total else None
