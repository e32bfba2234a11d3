"""The program's long steps and host pauses, read for the per-layer metrics
(source ``program_counter``: they sum what the program counted). The engine
writes an ``engine_long_step`` span for each step whose period was over five
times its kind's (``lost_ms`` beyond it, the ``phase`` that holds most of it,
the ``cause``: ``gc``, ``profiler``, ``compile`` or none), and
``dynamo_tpu.tracing.HOST_PAUSES`` a ``host_pause`` span for each garbage
collection of a millisecond or more and each start and stop of the profiler,
with ``t0_ns`` on the clock of a STEP record's. A span is the window's if its
``start_ts`` lies between the first and the last STEP record's ``ts``.

A program without the tracker (the parent of the PR that added it) gives every
reader here nothing to read: they return ``None``, never 0, and raise nothing.
"""

from __future__ import annotations

import bisect

from benchmark import program_spans as ps
from benchmark import trace_reduce


def window_spans(ctx, name: str) -> list[dict] | None:
    """The window's spans of one name, oldest first; ``None`` without the tracker."""
    from dynamo_tpu import tracing

    tracker = getattr(tracing, "HOST_PAUSES", None)
    if tracker is None:
        return None
    tracker.flush()  # a pause's span waits for the next step of the engine, or for a reader
    steps = ctx["window"]["steps"]
    if not steps:
        return []
    lo, hi = steps[0]["ts"], steps[-1]["ts"]
    ctx["notes"].setdefault("long_steps_window", {"s": hi - lo, "ring_dropped": tracing.SPANS.dropped})
    return [s for s in tracing.SPANS.query(request_id=name) if s["name"] == name and lo <= s["start_ts"] <= hi]


def _by(spans: list[dict], key: str, value: str) -> dict:
    out: dict = {}
    for s in spans:
        out[str(s[key])] = out.get(str(s[key]), 0.0) + s[value]
    return {k: round(v, 3) for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def long_steps(ctx) -> dict | None:
    """``lost_ms`` over the window's long steps but the profiler's own, the
    part of it that has a cause, and the note."""
    if "_long_steps" in ctx:
        return ctx["_long_steps"]
    spans = window_spans(ctx, "engine_long_step")
    out = None
    if spans is not None:
        own = [s for s in spans if s["cause"] != "profiler"]
        lost = float(sum(s["lost_ms"] for s in own))
        window_ms = ctx["notes"].get("long_steps_window", {}).get("s", 0.0) * 1e3
        out = {"lost_ms": lost, "named_ms": sum(s["lost_ms"] for s in own if s["cause"]), "note": {
            "steps": len(own), "lost_ms_by_phase": _by(own, "phase", "lost_ms"),
            "lost_ms_by_cause": _by([{**s, "cause": s["cause"] or "unnamed"} for s in own], "cause", "lost_ms"),
            "profiler": {"steps": len(spans) - len(own),
                         "lost_ms": round(sum(s["lost_ms"] for s in spans if s["cause"] == "profiler"), 3)},
            "lost_pct_of_window": round(100.0 * lost / window_ms, 4) if window_ms else None,
            "longest": [{"period_ms": s["duration_ms"], "expected_ms": s["expected_ms"], "phase": s["phase"],
                         "phase_ms": s["phase_ms"], "cause": s["cause"], "gc_ms": s["gc_ms"], "kind": s["step_kind"],
                         "rows": s["decode_rows"], "traced": s["traced"]}
                        for s in sorted(spans, key=lambda s: -s["duration_ms"])[:5]]}}
    ctx["_long_steps"] = out
    return out


def idle_under_pauses(ctx, pauses: list[dict]) -> dict | None:
    """Seconds of the first device's idle gaps that lie under a host pause, by
    cause: ``t0_ns`` mapped onto the trace's clock by the steps' clock join."""
    join = ps.clock_join(ctx)
    planes = trace_reduce.device_planes(ctx["trace"]) if join else []
    if not join or not planes:
        return None
    busy = trace_reduce.union(trace_reduce.busy_events(planes[0]))
    gaps = [(a_end, b_start) for (_, a_end), (b_start, _) in zip(busy, busy[1:])]
    ends = [g[1] for g in gaps]
    out: dict[str, float] = {}
    for s in pauses:
        p0 = s["t0_ns"] + join["offset_ns"]
        p1 = p0 + s["duration_ms"] * 1e6
        cover = 0.0
        for g0, g1 in gaps[bisect.bisect_right(ends, p0):]:
            if g0 >= p1:
                break
            cover += min(g1, p1) - max(g0, p0)
        out[s["cause"]] = out.get(s["cause"], 0.0) + cover / 1e9
    return {k: round(v, 6) for k, v in out.items()}


def gc_pauses(ctx) -> dict | None:
    """Summed duration of the window's ``host_pause`` spans of cause ``gc``, and the note."""
    pauses = window_spans(ctx, "host_pause")
    if pauses is None:
        return None
    collections = [s for s in pauses if s["cause"] == "gc"]
    note = {"collections": len(collections), "ms_by_generation": _by(collections, "generation", "duration_ms"),
            "count_by_generation": _by([{**s, "n": 1} for s in collections], "generation", "n"),
            "ms_by_thread": _by(collections, "thread", "duration_ms"),
            "longest_ms": max((s["duration_ms"] for s in collections), default=0.0),
            "profiler": [{"what": s["what"], "ms": s["duration_ms"]} for s in pauses if s["cause"] == "profiler"]}
    if ctx.get("trace") is not None:
        note["device_idle_under_pause_s"] = idle_under_pauses(ctx, pauses)
    return {"ms": float(sum(s["duration_ms"] for s in collections)), "note": note}
