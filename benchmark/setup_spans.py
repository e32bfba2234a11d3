"""Set-up as the program recorded it, read for the per-layer metrics that move
``setup_s`` (source ``program_counter``: they sum and count what the program
wrote). The runner's compile tracker leaves one ``runner_first_call`` span for
the first call of every step program: its ``duration_ms`` and, from JAX's own
events on the calling thread, ``trace_ms``, ``lower_ms``, ``backend_ms`` (the
compile of a cold run, the persistent cache's read of a warm one) and
``rest_ms`` (what none of them covers), ``cache_hits`` / ``cache_misses``,
``cache_saved_ms``, ``in_step``. ``dynamo_tpu.launch`` leaves one
``worker_bring_up`` span a worker, root of the same trace. A span is set-up's
if its ``start_ts`` lies before the window's first STEP record: the warm-up's
first calls, the outputs check's and the lead-in's.

A program that writes no such span (the parent of the PR that added them)
gives every reader here nothing to read, and so does a span ring that has
dropped spans (the oldest go first, and set-up's are the oldest): they return
``None``, never 0, and raise nothing.
"""

from __future__ import annotations

FIRST_CALL, BRING_UP = "runner_first_call", "worker_bring_up"
PARTS = ("trace_ms", "lower_ms", "backend_ms", "rest_ms")


def ring_dropped() -> int:
    from dynamo_tpu import tracing

    return getattr(tracing.SPANS, "dropped", 0)


def setup_spans(ctx, name: str) -> list[dict] | None:
    """Set-up's spans of one name, oldest first; ``None`` where there are none
    or the ring has wrapped."""
    from dynamo_tpu import tracing

    if ring_dropped():
        return None
    steps = ctx["window"]["steps"]
    end = steps[0]["ts"] if steps else float("inf")
    spans = [s for s in tracing.SPANS.query(request_id=name) if s["name"] == name and s["start_ts"] < end]
    return spans or None


def _kind(span: dict) -> str:
    """``t1``: a decode program (one token a row); ``chunk``: any other. The
    bucket's second entry is the padded tokens per row at every dispatch site."""
    bucket = span.get("bucket") or []
    return "t1" if len(bucket) > 1 and bucket[1] == 1 else "chunk"


def _total_s(spans: list[dict], *keys: str) -> float:
    return sum(s.get(k, 0.0) for s in spans for k in keys) / 1e3


def first_calls(ctx) -> dict | None:
    """The sums and counts over set-up's first calls, and the note."""
    if "_set_up" in ctx:
        return ctx["_set_up"]
    spans = setup_spans(ctx, FIRST_CALL)
    out = None
    if spans is not None:
        by_kind = {}
        for kind in ("t1", "chunk"):
            mine = [s for s in spans if _kind(s) == kind]
            if mine:
                by_kind[kind] = {"programs": len(mine), "mean_s": round(_total_s(mine, "duration_ms") / len(mine), 4),
                                 **{f"mean_{p[:-3]}_s": round(_total_s(mine, p) / len(mine), 4) for p in PARTS},
                                 "mean_cache_read_s": round(_total_s(mine, "cache_read_ms") / len(mine), 4)}
        inside = [s for s in spans if s.get("in_step")]
        hits, misses = (sum(s.get(k, 0) for s in spans) for k in ("cache_hits", "cache_misses"))
        out = {
            "first_calls_s": _total_s(spans, "duration_ms"), "python_s": _total_s(spans, "trace_ms", "lower_ms"),
            "backend_s": _total_s(spans, "backend_ms"), "rest_s": _total_s(spans, "rest_ms"),
            "programs": len(spans), "cache_hits": hits, "cache_misses": misses,
            "note": {
                "by_kind": by_kind,
                "longest": [{"program": s.get("program"), "bucket": s.get("bucket"), "s": round(s["duration_ms"] / 1e3, 3),
                             **{p[:-3] + "_s": round(s.get(p, 0.0) / 1e3, 3) for p in PARTS}, "cache": s.get("cache")}
                            for s in sorted(spans, key=lambda s: -s["duration_ms"])[:5]],
                "cache_saved_s": round(_total_s(spans, "cache_saved_ms"), 3),
                "cache": {c: sum(1 for s in spans if s.get("cache") == c) for c in ("hit", "miss", "off", "none")},
                "modules": sum(s.get("modules", 0) for s in spans),
                # made by an engine step before the window: the outputs check's, and any of the lead-in
                "inside_steps": {"programs": len(inside), "s": round(_total_s(inside, "duration_ms"), 3),
                                 "buckets": [s.get("bucket") for s in inside[:8]]},
                "ring_dropped": 0,
            }}
    ctx["_set_up"] = out
    return out


def first_calls_value(ctx, key: str):
    found = first_calls(ctx)
    return None if found is None else float(found[key])


def bring_up_s(ctx) -> float | None:
    """Summed ``duration_ms`` of the workers' ``worker_bring_up`` spans, in seconds."""
    roots = setup_spans(ctx, BRING_UP)
    return None if roots is None else _total_s(roots, "duration_ms")
